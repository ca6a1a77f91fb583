"""resync_ms — search driver (``core.search._resync_check``).

Mean wall milliseconds per drift-guard re-sweep: every chain's graph swept
in full in one dispatch (its packing and run included) and compared with
the state the polish maintains.  Read from the program's
``repro.polish.resync`` spans (``bench.program_trace``).
"""
from bench import program_trace


def read(ctx):
    prog = program_trace.of(ctx)
    if prog is None:
        return None
    spans = prog.named("repro.polish.resync")
    if not spans:
        return None
    return sum(s.dur for s in spans) / len(spans) / 1e6
