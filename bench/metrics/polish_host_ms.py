"""polish_host_ms — search driver (``core.search._replica_polish``).

Mean wall milliseconds per polish iteration that the host spends outside
the device dispatch: drawing proposals, the lost-parent test, packing,
uploading and the accept loop.  Read from the benchmark's ``bench.polish``
and ``bench.dispatch`` spans; iterations from the polish calls' ``n_iter``.
"""


def read(ctx):
    t = ctx["trace"]
    iterations = ctx["counters"].get("polish_iterations", 0)
    polish = [s for s in t.spans if s.name == "bench.polish"]
    if not polish or not iterations:
        return None
    inside = sum(d.dur for d in t.spans if d.name == "bench.dispatch"
                 and any(p.start <= d.start and d.end <= p.end for p in polish))
    return (sum(p.dur for p in polish) - inside) / iterations / 1e6
