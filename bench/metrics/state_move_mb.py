"""state_move_mb — device dispatch (``pallas_sweep.copy_state``).

Megabytes (1e6 bytes) of chain state copied between devices per polish
iteration: the exchange's copy of one chain's (s, n) int32 best rows into
a chain on another device, and nothing else.  Read from the
``state_move_bytes`` count of the program's ``repro.polish.tally`` marks
over the ``iterations`` of its ``repro.polish`` spans
(``bench.program_trace``); nothing where the tally has no such count.
"""
from bench import program_trace


def read(ctx):
    prog = program_trace.of(ctx)
    if prog is None:
        return None
    tallies = [s for e, s in zip(prog.events, prog.stats)
               if e.name == "repro.polish.tally"]
    iterations = prog.stat("repro.polish", "iterations")
    if not iterations or not any("state_move_bytes" in s for s in tallies):
        return None
    return prog.stat("repro.polish.tally", "state_move_bytes") \
        / iterations / 1e6
