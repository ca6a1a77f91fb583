"""Bytes that each device kernel of the polish dispatch must move.

Counted from the arguments seen at the dispatch boundary
(``pallas_sweep.sharded_delta_state``), never from the padded shapes the
program compiles for: a faster implementation that skips padding must not
read above 100% of the roofline.  Both kernels do integer compares, adds and
mins with no published int32 vector peak, so their bound is memory: least
time = bytes / HBM bytes per second (``bench/peaks.json``).
"""
from __future__ import annotations

INT32 = 4


def sweep_bytes(nbrs_shape, sources_list) -> int:
    """BFS sweep: each graph with at least one real source reads its (n,
    kmax) int32 neighbour table once and writes one int32 distance row of
    length n per real source.  Idle lanes and graphs with no source move
    nothing that an implementation could not skip."""
    _, n, kmax = nbrs_shape
    total = 0
    for src in sources_list:
        m = len(src)
        if m:
            total += n * kmax * INT32 + m * n * INT32
    return total


def patch_bytes(base_shape, patches) -> int:
    """Min-plus insert patch: its chain's (s, n) int32 base rows are read at
    least once per dispatch, however many of that chain's proposals share
    them, and each patched proposal reads one int32 row of length n per
    endpoint of its added edges.  The post-swap state written back is not
    counted: an implementation that fuses the patch into the row totals
    need write only the accepted proposal's state.  Proposals with no added
    edge are not patched."""
    r, s, n = base_shape
    b = len(patches)
    per_chain = b // r
    patched = [i for i, p in enumerate(patches) if p]
    chains = {i // per_chain for i in patched}
    endpoints = sum(len({x for e in patches[i] for x in e}) for i in patched)
    return (len(chains) * s + endpoints) * n * INT32
