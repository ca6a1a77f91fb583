"""state_pull_ms — device dispatch (``core.search._replica_polish``).

Mean wall milliseconds per polish iteration spent pulling the post-swap
state of every proposal to the host, once in each iteration where a chain
accepted.  Read from the program's ``repro.polish.pull`` spans over the
``iterations`` of its ``repro.polish`` spans (``bench.program_trace``).
"""
from bench import program_trace


def read(ctx):
    prog = program_trace.of(ctx)
    if prog is None:
        return None
    spans = prog.named("repro.polish.pull")
    iterations = prog.stat("repro.polish", "iterations")
    if not spans or not iterations:
        return None
    return sum(s.dur for s in spans) / iterations / 1e6
