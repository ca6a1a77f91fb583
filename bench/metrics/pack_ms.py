"""pack_ms — device dispatch (``pallas_sweep.sharded_delta_state``).

Mean wall milliseconds per dispatch spent packing its inputs on the host:
stacking the chains' state, the padded sweep and patch arrays and the
contiguous copy of the base rows.  Read from the program's
``repro.dispatch.pack`` spans over the count of its ``repro.dispatch.run``
spans (``bench.program_trace``).
"""
from bench import program_trace


def read(ctx):
    prog = program_trace.of(ctx)
    if prog is None:
        return None
    packs = prog.named("repro.dispatch.pack")
    runs = len(prog.named("repro.dispatch.run"))
    if not packs or not runs:
        return None
    return sum(s.dur for s in packs) / runs / 1e6
