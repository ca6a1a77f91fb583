"""Byte counts of the polish kernels against hand counts at a small shape."""
import numpy as np

from bench import workcount


def test_sweep_bytes_hand_count():
    # b = 3 graphs, n = 16 vertices, kmax = 4; graph 0 sweeps 5 real
    # sources, graph 1 none (an idle slot), graph 2 sweeps 2
    srcs = [np.arange(5), np.empty(0, dtype=np.int64), np.array([3, 9])]
    table = 16 * 4 * 4  # n * kmax int32 entries, read once per swept graph
    rows = (5 + 2) * 16 * 4  # one int32 row of n per real source
    assert workcount.sweep_bytes((3, 16, 4), srcs) == 2 * table + rows


def test_patch_bytes_hand_count():
    # r = 2 chains, 2 proposals each (b = 4), s = 8 rows of n = 32
    base = 8 * 32 * 4  # one chain's (s, n) int32 base rows
    row = 32 * 4  # one endpoint row
    # chain 0 patches both proposals (2 and 4 endpoints), chain 1 neither:
    # one base read and six endpoint rows; no state write is counted
    patches = [[(0, 5)], [(1, 7), (2, 9)], None, []]
    assert workcount.patch_bytes((2, 8, 32), patches) == base + 6 * row
    # one patched proposal in each chain: two base reads, 2 + 2 endpoints
    patches = [[(0, 5)], None, None, [(3, 4)]]
    assert workcount.patch_bytes((2, 8, 32), patches) == 2 * base + 4 * row
    # an endpoint shared by two added edges is read once
    patches = [[(0, 5), (0, 6)], None, None, None]
    assert workcount.patch_bytes((2, 8, 32), patches) == base + 3 * row
    assert workcount.patch_bytes((2, 8, 32), [None] * 4) == 0
