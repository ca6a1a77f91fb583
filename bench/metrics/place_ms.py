"""place_ms — device dispatch (``pallas_sweep.place_states``, ``copy_state``).

Mean wall milliseconds per polish iteration spent placing the chains'
state: the program that takes each chain's accepted post-swap rows and
best snapshot on its own device, and the exchange's copy of one chain's
best rows into another.  Read from the program's ``repro.polish.place``
spans over the ``iterations`` of its ``repro.polish`` spans
(``bench.program_trace``); nothing where the program has no such span.
"""
from bench import program_trace


def read(ctx):
    prog = program_trace.of(ctx)
    if prog is None:
        return None
    spans = prog.named("repro.polish.place")
    iterations = prog.stat("repro.polish", "iterations")
    if not spans or not iterations:
        return None
    return sum(s.dur for s in spans) / iterations / 1e6
