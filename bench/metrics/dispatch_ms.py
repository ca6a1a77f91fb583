"""dispatch_ms — device dispatch (``pallas_sweep.sharded_delta_state``).

Mean wall milliseconds per call, from the benchmark's ``bench.dispatch``
span: packing, upload, the one sharded program (sweep, merge, patch) and
the pull of the per-proposal totals, which return only when the program
has run.
"""


def read(ctx):
    spans = [s for s in ctx["trace"].spans if s.name == "bench.dispatch"]
    if not spans:
        return None
    return sum(s.dur for s in spans) / len(spans) / 1e6
