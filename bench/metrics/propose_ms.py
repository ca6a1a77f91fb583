"""propose_ms — search driver (``core.search._replica_polish``).

Mean wall milliseconds per polish iteration spent drawing the proposals:
the orbit-swap draws, the batched lost-parent test and the proposal
tables.  Read from the program's ``repro.polish.propose`` spans over the
``iterations`` of its ``repro.polish`` spans (``bench.program_trace``).
"""
from bench import program_trace


def read(ctx):
    prog = program_trace.of(ctx)
    if prog is None:
        return None
    spans = prog.named("repro.polish.propose")
    iterations = prog.stat("repro.polish", "iterations")
    if not spans or not iterations:
        return None
    return sum(s.dur for s in spans) / iterations / 1e6
