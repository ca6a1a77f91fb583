"""circulant_chunk_ms — circulant pricer (``core.engines.jax_circulant``).

Mean wall milliseconds per priced candidate chunk (32 offset sets in one
jitted BFS sweep), blocked until the device has finished, from the
benchmark's ``bench.chunk`` span.
"""


def read(ctx):
    spans = [s for s in ctx["trace"].spans if s.name == "bench.chunk"]
    if not spans:
        return None
    return sum(s.dur for s in spans) / len(spans) / 1e6
