"""Warm-up of every program shape the polish dispatch can take.

The delta dispatch (``pallas_sweep.sharded_delta_state``) compiles one
program per bucket of source lanes and patch endpoints, and which buckets a
job reaches depends on its trajectory: one warm-up job does not reach them
all, and a bucket first met inside the window compiles there.  So set-up
also drives the dispatch once per bucket with inputs of those shapes: every
lane bucket the program's own ``source_lanes`` makes for 1..s sources, with
the patch of two swapped orbits (``2 * fold`` added edges, ``4 * fold``
endpoints), and the batch in which every proposal is a full rebuild (all
``s`` rows, no patch).  The values are arbitrary; only the shapes matter.
"""
from __future__ import annotations

import numpy as np


def delta_buckets(fields: dict) -> list:
    """(sources, patch or None) per bucket the dispatch can compile."""
    from repro.kernels import bfs_sweep

    n, fold = fields["n"], fields["fold"]
    s = n // fold
    counts, m = [], 1
    while m < s:
        counts.append(m)
        m *= 2
    counts.append(s)
    by_lanes = {}
    for m in counts:
        by_lanes.setdefault(bfs_sweep.source_lanes(m), m)
    patch = [(2 * i, 2 * i + 1) for i in range(2 * fold)]
    out = [(m, patch) for _, m in sorted(by_lanes.items())]
    out.append((s, None))
    return out


def circulant_table(n: int, k: int) -> np.ndarray:
    """(n, k) neighbour table of a ring plus chords, for shapes only."""
    steps = [o for j in range(1, k // 2 + 1) for o in (j, -j)]
    if k % 2:
        steps.append(n // 2)
    return (np.arange(n)[:, None] + np.asarray(steps)[None, :]) % n


def warm_delta(fields: dict) -> int:
    """Drive the dispatch once per bucket; returns how many."""
    from repro.core import engines
    from repro.core.engines import pallas_sweep

    n, k, fold, r = fields["n"], fields["k"], fields["fold"], fields["replicas"]
    b = r * fields.get("proposal_batch", 1)
    s = n // fold
    base = np.zeros((r, s, n), dtype=np.int32)
    nbrs = np.broadcast_to(circulant_table(n, k), (b, n, k)).astype(np.int32)
    buckets = delta_buckets(fields)
    # the engine resolved as the polish resolves it
    use_pallas = engines.resolve_rows(fields.get("engine")).device_sweep
    for m, patch in buckets:
        totals, _, state = pallas_sweep.sharded_delta_state(
            base, nbrs, [np.arange(m)] * b, [patch] * b, n,
            use_pallas=use_pallas)
        state.block_until_ready()
    return len(buckets)
