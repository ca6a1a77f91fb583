"""The jitted batched circulant pricer (``engines.jax_circulant._jax_sweep``)
against the sequential ``search._circulant_profile``, row by row: hop
totals, diameters and connectivity of each candidate in a chunk.

The level loop rotates each frontier by its own shifts from static rolls
by powers of two, so the cases cover what that must get exact: n that is
not a power of two, the antipodal shift of odd degree, the deployment's
n = 8192, duplicate pad shifts, a disconnected candidate and a chunk
padded to ``CHUNK`` from fewer real rows.
"""
import numpy as np
import pytest

pytest.importorskip("jax")

from repro.core import search  # noqa: E402
from repro.core.engines import jax_circulant  # noqa: E402

CHUNK = jax_circulant.CHUNK


def _offset_lists(n, k, rows, seed):
    """``rows`` circulant offset sets of degree k (offset 1, k//2 - 1 random
    chords, n/2 for odd k), as ``circulant_search`` draws them."""
    rng = np.random.default_rng(seed)
    lists = []
    for _ in range(rows):
        chords = rng.choice(np.arange(2, n // 2), k // 2 - 1, replace=False)
        offs = [1] + sorted(chords.tolist())
        lists.append(offs + [n // 2] if k % 2 else offs)
    return lists


def _shift_rows(n, lists):
    """(len(lists), m) int32 shifts, each row cyclically padded to the
    longest, as ``profile_batch`` packs them."""
    shifts = [sorted({sh for s in offs if s % n
                      for sh in (s % n, n - s % n)}) for offs in lists]
    m = max(len(s) for s in shifts)
    return np.stack([np.resize(s, m) for s in shifts]).astype(np.int32)


def _check_rows(n, lists, arr):
    total, diam, conn = (np.asarray(x)
                         for x in jax_circulant._jax_sweep(n, arr.shape[1])(arr))
    assert total.dtype == diam.dtype == np.int32
    for i, offs in enumerate(lists):
        mpl, d = search._circulant_profile(n, offs)
        if np.isinf(mpl):
            assert not conn[i], (i, offs)
        else:
            assert conn[i], (i, offs)
            assert int(total[i]) / (n - 1) == mpl, (i, offs)
            assert float(diam[i]) == d, (i, offs)


def _mixed(n, k, rows, seed):
    """Random candidates plus rows whose shift lists are shorter than the
    chunk's m, so that their pad repeats shifts: a duplicated offset, an
    offset and its negative, and n/2 (its own negative) for even k."""
    lists = _offset_lists(n, k, rows, seed)
    extra = [[1, 1, 3], [1, 5, n - 5]]
    if k % 2 == 0:
        extra.append([1, n // 2] + list(range(7, 7 + k // 2 - 2)))
    return lists + extra


@pytest.mark.parametrize("n,k", [
    (1000, 8),   # not a power of two, not a multiple of 128
    (4096, 7),   # odd degree: the antipodal shift n/2
    (8192, 8),   # the deployment's size
    (1024, 6),
])
def test_sweep_matches_sequential_pricer(n, k):
    lists = _mixed(n, k, CHUNK - 3, seed=n + k)
    assert len(lists) <= CHUNK
    arr = _shift_rows(n, lists)
    assert any(len(set(row)) < arr.shape[1] for row in arr.tolist())
    _check_rows(n, lists, arr)


def test_sweep_prices_a_disconnected_candidate():
    """Even offsets alone reach only the even vertices of an even n."""
    n = 1000
    lists = _offset_lists(n, 8, 6, seed=5) + [[2, 4, 10, 100], [2, 500]]
    arr = _shift_rows(n, lists)
    total, diam, conn = jax_circulant._jax_sweep(n, arr.shape[1])(arr)
    assert not np.asarray(conn)[-2:].any()
    assert np.asarray(conn)[:-2].all()
    _check_rows(n, lists, arr)


@pytest.mark.parametrize("n,k,rows", [(1000, 8, 5), (4096, 7, CHUNK + 3)])
def test_profile_batch_pads_chunks_to_chunk_rows(n, k, rows):
    """``profile_batch`` pads the last chunk to ``CHUNK`` rows with copies
    of its first; only the real rows are yielded, equal to the numpy
    pricer's values (disconnected rows as inf)."""
    lists = _offset_lists(n, k, rows - 1, seed=rows) + [
        [2, 4, n // 2] if k % 2 else [2, 4, 6, 8]]
    got = list(jax_circulant.profile_batch(n, lists, "jax",
                                           search._circulant_profile))
    assert got == [search._circulant_profile(n, offs) for offs in lists]
    assert got[-1] == (float("inf"), float("inf"))
