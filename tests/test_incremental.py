"""Property tests for the incremental APSP evaluators (the search hot paths).

The contract under test: after any valid edge swap — a 2-out/2-in chord swap
on ``IncrementalAPSP``, a batched multi-edge change (edges may share
vertices), or an orbit-level swap on the row-restricted ``SymmetricAPSP`` —
``evaluate_swap`` produces *exactly* the distance rows, total, MPL and
diameter that a from-scratch ``metrics.apsp`` recompute yields: on the delta
path, the forced-full path, the C kernel and the pure numpy fallback alike,
including swaps that disconnect the graph.
"""
import itertools

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import metrics
from repro.core.graphs import circulant, from_edges, random_hamiltonian_regular, ring
from repro.core.search import _orbit


def _swap_space(n):
    return ring(n).adjacency()


def _random_swap(ev, ring_mask, rng):
    """A valid 2-edge swap on the evaluator's current graph, or None."""
    iu, ju = np.where(np.triu(ev.adj & ~ring_mask))
    if len(iu) < 2:
        return None
    e1, e2 = rng.choice(len(iu), size=2, replace=False)
    a, b = int(iu[e1]), int(ju[e1])
    c, d = int(iu[e2]), int(ju[e2])
    if len({a, b, c, d}) != 4:
        return None
    p1, p2 = ((a, c), (b, d)) if rng.integers(2) else ((a, d), (b, c))
    if ev.adj[p1] or ev.adj[p2]:
        return None
    return [(a, b), (c, d)], [p1, p2]


# redraws of an invalid swap before a step of a property test gives up
_SWAP_TRIES = 64


def _redrawn_swap(ev, ring_mask, rng):
    """``_random_swap``, redrawn while invalid, up to ``_SWAP_TRIES``
    draws; None only if every draw was invalid."""
    for _ in range(_SWAP_TRIES):
        swap = _random_swap(ev, ring_mask, rng)
        if swap is not None:
            return swap
    return None


def _has_valid_swap(adj, ring_mask) -> bool:
    """Whether any valid 2-edge swap exists on ``adj`` (every chord pair,
    both rewirings)."""
    iu, ju = np.where(np.triu(adj & ~ring_mask))
    for e1, e2 in itertools.combinations(range(len(iu)), 2):
        a, b, c, d = int(iu[e1]), int(ju[e1]), int(iu[e2]), int(ju[e2])
        if len({a, b, c, d}) == 4 and any(
                not adj[p1] and not adj[p2]
                for p1, p2 in (((a, c), (b, d)), ((a, d), (b, c)))):
            return True
    return False


def _reference(adj, removed, added):
    """From-scratch hop distances after applying the swap to a copy."""
    adj2 = adj.copy()
    for u, v in removed:
        adj2[u, v] = adj2[v, u] = False
    for u, v in added:
        adj2[u, v] = adj2[v, u] = True
    return metrics.apsp_hops(adj2)


@st.composite
def swap_instance(draw):
    n = draw(st.integers(12, 28))
    k = draw(st.sampled_from([3, 4, 5]))
    if n * (k - 2) % 2 or n <= 2 * k:
        n, k = 16, 4
    seed = draw(st.integers(0, 5_000))
    return n, k, seed


@settings(max_examples=30, deadline=None)
@given(swap_instance(), st.integers(0, 10_000))
def test_delta_matches_full_recompute(inst, swap_seed):
    """Delta-updated dist/MPL after random swaps == metrics.apsp recompute."""
    n, k, seed = inst
    try:
        g = random_hamiltonian_regular(n, k, seed=seed)
    except RuntimeError:
        return
    rng = np.random.default_rng(swap_seed)
    ring_mask = _swap_space(n)
    if not _has_valid_swap(g.adjacency(), ring_mask):
        return  # no swap to price on this instance
    ev = metrics.IncrementalAPSP(g.adjacency().copy(), full_rebuild_frac=1.1)
    ev_full = metrics.IncrementalAPSP(g.adjacency().copy(), force_full=True)
    for _ in range(6):
        # an invalid draw is redrawn, so that every example prices swaps
        swap = _redrawn_swap(ev, ring_mask, rng)
        if swap is None:
            continue
        removed, added = swap
        ref = _reference(ev.adj, removed, added)
        tok = ev.evaluate_swap(removed, added, want_diameter=False)
        tok_full = ev_full.evaluate_swap(removed, added)
        assert np.array_equal(tok.dist, ref)
        assert np.array_equal(tok_full.dist, ref)
        assert tok.total == tok_full.total == int(ref.sum(dtype=np.int64))
        assert tok.mpl == tok_full.mpl
        if rng.random() < 0.7:
            ev.commit(tok)
            ev_full.commit(tok_full)
            ev.verify()
            ev_full.verify()
            assert ev.diam == ev_full.diam
    assert ev.n_full == 0  # frac > 1: the delta path must have priced everything
    assert ev_full.n_delta == 0 and ev_full.n_full > 0  # forced fallback path


@settings(max_examples=20, deadline=None)
@given(swap_instance(), st.integers(0, 10_000))
def test_c_and_numpy_paths_identical(inst, swap_seed):
    """The C kernel and the numpy fallback are bit-identical (when C exists)."""
    n, k, seed = inst
    try:
        g = random_hamiltonian_regular(n, k, seed=seed)
    except RuntimeError:
        return
    ev_c = metrics.IncrementalAPSP(g.adjacency().copy())
    if ev_c.fast is None:
        pytest.skip("no C compiler in this environment")
    ev_np = metrics.IncrementalAPSP(g.adjacency().copy(), use_c=False)
    rng = np.random.default_rng(swap_seed)
    ring_mask = _swap_space(n)
    for _ in range(6):
        swap = _random_swap(ev_c, ring_mask, rng)
        if swap is None:
            continue
        removed, added = swap
        tc = ev_c.evaluate_swap(removed, added, want_diameter=False)
        tn = ev_np.evaluate_swap(removed, added)
        assert np.array_equal(tc.dist, tn.dist)
        assert tc.total == tn.total and tc.mpl == tn.mpl
        if rng.random() < 0.5:
            ev_c.commit(tc)
            ev_np.commit(tn)
            assert ev_c.diam == ev_np.diam and ev_c.total == ev_np.total


def test_disconnecting_swap_reports_inf_and_recovers():
    """The disconnect path: MPL/diameter go to inf, state stays exact, and a
    reconnecting swap restores finite values (fallback path exercised)."""
    edges = [(0, 1), (1, 2), (2, 3), (3, 0), (4, 5), (5, 6), (6, 7), (7, 4),
             (0, 4), (2, 6)]
    g = from_edges(8, edges)
    ev = metrics.IncrementalAPSP(g.adjacency().copy())
    tok = ev.evaluate_swap([(0, 4), (2, 6)], [(0, 2), (4, 6)])
    assert tok.mpl == float("inf")
    assert np.array_equal(tok.dist, _reference(ev.adj, [(0, 4), (2, 6)], [(0, 2), (4, 6)]))
    ev.commit(tok)
    ev.verify()
    assert not ev.connected and ev.mpl() == float("inf")
    # disconnected base forces the full-recompute fallback on the next swap
    tok2 = ev.evaluate_swap([(0, 2), (4, 6)], [(0, 4), (2, 6)])
    assert ev.n_full >= 1
    assert tok2.mpl < float("inf")
    ev.commit(tok2)
    ev.verify()
    assert ev.connected


# ------------------------------------------------------------------------------
# Batched multi-edge changes and the symmetry-aware orbit evaluator
# ------------------------------------------------------------------------------

def _random_orbit_swap(ev, rng):
    """A random orbit-level edge swap on a SymmetricAPSP's current graph:
    (removed, added) lists that are orbit-closed, with overlap cancelled, or
    None when the draw is invalid.  Mirrors symmetric_sa_search proposals."""
    n, s = ev.n, ev.s
    fold = ev.fold
    iu, ju = np.nonzero(np.triu(ev.adj))
    e1, e2 = rng.choice(len(iu), size=2, replace=False)
    o1 = _orbit(n, s, int(iu[e1]), int(ju[e1]))
    o2 = _orbit(n, s, int(iu[e2]), int(ju[e2]))
    if o1 == o2:
        return None
    (u1, v1), (u2, v2) = next(iter(o1)), next(iter(o2))
    tshift = int(rng.integers(fold)) * s
    if rng.integers(2):
        na, nb = (u1, (v2 + tshift) % n), ((u2 + tshift) % n, v1)
    else:
        na, nb = (u1, (u2 + tshift) % n), (v1, (v2 + tshift) % n)
    if na[0] == na[1] or nb[0] == nb[1]:
        return None
    new_edges = set(_orbit(n, s, *na)) | set(_orbit(n, s, *nb))
    cur = {(int(u), int(v)) for u, v in zip(iu, ju)}
    old_edges = set(o1) | set(o2)
    if new_edges & (cur - old_edges):
        return None
    removed = sorted(old_edges - new_edges)
    added = sorted(new_edges - old_edges)
    if not removed and not added:
        return None
    return removed, added


@settings(max_examples=20, deadline=None)
@given(st.sampled_from([(12, 3), (16, 4), (24, 4), (24, 6), (30, 5)]),
       st.integers(0, 10_000))
def test_orbit_delta_matches_full_recompute(shape, swap_seed):
    """SymmetricAPSP orbit swaps == from-scratch BFS rows, delta and forced
    full paths, including disconnecting swaps and recovery."""
    s, fold = shape
    n = s * fold
    rng = np.random.default_rng(swap_seed)
    offs = [1] + sorted(rng.choice(range(2, n // 2), size=2, replace=False).tolist())
    adj = circulant(n, offs).adjacency()
    ev = metrics.SymmetricAPSP(adj.copy(), shift=s, full_rebuild_frac=1.1)
    ev_full = metrics.SymmetricAPSP(adj.copy(), shift=s, force_full=True)
    for _ in range(6):
        swap = _random_orbit_swap(ev, rng)
        if swap is None:
            continue
        removed, added = swap
        ref = _reference(ev.adj, removed, added)[: s]
        tok = ev.evaluate_swap(removed, added)
        tok_full = ev_full.evaluate_swap(removed, added)
        assert np.array_equal(tok.dist, ref)
        assert np.array_equal(tok_full.dist, ref)
        assert tok.total == tok_full.total == int(ref.sum(dtype=np.int64))
        assert tok.mpl == tok_full.mpl and tok.diam == tok_full.diam
        if rng.random() < 0.7:
            ev.commit(tok)
            ev_full.commit(tok_full)
            ev.verify()
            ev_full.verify()
    if ev.connected:
        # frac > 1 and connected base: everything priced on the delta path
        assert ev.n_full == 0 or ev.n_delta > 0
    assert ev_full.n_delta == 0 and ev_full.n_full > 0


@settings(max_examples=15, deadline=None)
@given(st.sampled_from([(12, 3), (16, 4), (24, 4), (24, 6)]),
       st.integers(0, 10_000))
def test_orbit_c_and_numpy_paths_identical(shape, swap_seed):
    """The orbit-delta C kernel and the numpy fallback are bit-identical."""
    s, fold = shape
    n = s * fold
    rng = np.random.default_rng(swap_seed)
    offs = [1] + sorted(rng.choice(range(2, n // 2), size=2, replace=False).tolist())
    adj = circulant(n, offs).adjacency()
    ev_c = metrics.SymmetricAPSP(adj.copy(), shift=s)
    if ev_c.fast is None:
        pytest.skip("no C compiler in this environment")
    ev_np = metrics.SymmetricAPSP(adj.copy(), shift=s, use_c=False)
    for _ in range(6):
        swap = _random_orbit_swap(ev_c, rng)
        if swap is None:
            continue
        tc = ev_c.evaluate_swap(*swap)
        tn = ev_np.evaluate_swap(*swap)
        assert np.array_equal(tc.dist, tn.dist)
        assert tc.total == tn.total and tc.diam == tn.diam and tc.mpl == tn.mpl
        assert ev_c.n_delta == ev_np.n_delta and ev_c.n_full == ev_np.n_full
        if rng.random() < 0.5:
            ev_c.commit(tc)
            ev_np.commit(tn)
            assert np.array_equal(ev_c.npar, ev_np.npar)
            assert ev_c.diam == ev_np.diam and ev_c.total == ev_np.total


@settings(max_examples=15, deadline=None)
@given(st.integers(12, 26), st.integers(0, 10_000))
def test_batched_multiedge_matches_full_recompute(n, swap_seed):
    """IncrementalAPSP with arbitrary batched edge lists (shared vertices
    allowed) == from-scratch recompute — the generalized cascade contract."""
    rng = np.random.default_rng(swap_seed)
    try:
        g = random_hamiltonian_regular(n, 4, seed=swap_seed)
    except RuntimeError:
        return
    ev = metrics.IncrementalAPSP(g.adjacency().copy(), use_c=False)
    for _ in range(4):
        iu, ju = np.nonzero(np.triu(ev.adj))
        m = int(rng.integers(1, min(5, len(iu))))
        picks = rng.choice(len(iu), size=m, replace=False)
        removed = [(int(iu[e]), int(ju[e])) for e in picks]
        absent = np.argwhere(np.triu(~ev.adj, k=1))
        adds = rng.choice(len(absent), size=int(rng.integers(0, 4)), replace=False)
        added = [(int(a), int(b)) for a, b in absent[adds]]
        ref = _reference(ev.adj, removed, added)
        tok = ev.evaluate_swap(removed, added)
        assert np.array_equal(tok.dist, ref)
        assert tok.total == int(ref.sum(dtype=np.int64))
        if rng.random() < 0.6:
            ev.commit(tok)
            ev.verify()


# ------------------------------------------------------------------------------
# Word-packed (bitset-frontier) BFS backend
# ------------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.integers(10, 90), st.sampled_from([3, 4, 5, 6]), st.integers(0, 10_000))
def test_bitset_rows_match_dense_bfs(n, k, seed):
    """Word-packed BFS distances exactly equal dense BFS on random regular
    graphs — including source counts not divisible by 64 and source subsets."""
    if n * k % 2 or n <= k:
        n, k = 23, 4  # deliberately not divisible by 64
    try:
        g = random_hamiltonian_regular(n, k, seed=seed)
    except RuntimeError:
        return
    adj = g.adjacency()
    nbr = metrics._nbr_table(adj)
    ref = metrics.apsp_hops(adj)
    assert np.array_equal(metrics.bitset_bfs_rows(nbr, np.arange(n), n), ref)
    rng = np.random.default_rng(seed)
    m = int(rng.integers(1, n))
    srcs = rng.choice(n, size=m, replace=False)
    assert np.array_equal(metrics.bitset_bfs_rows(nbr, srcs, n), ref[srcs])


def test_bitset_rows_disconnected_and_sentinel():
    """Disconnected components hold the sentinel, for any sentinel value."""
    edges = [(i, (i + 1) % 5) for i in range(5)] + \
            [(5 + i, 5 + (i + 1) % 5) for i in range(5)]
    adj = from_edges(10, edges).adjacency()
    nbr = metrics._nbr_table(adj)
    ref = metrics.apsp_hops(adj, sentinel=99)
    got = metrics.bitset_bfs_rows(nbr, np.arange(10), 99)
    assert np.array_equal(got, ref)
    assert (got == 99).sum() == 50  # 2 components of 5: half the pairs


def test_bitset_c_and_numpy_sweeps_identical():
    """The C word-packed sweep and the numpy word ops are bit-identical."""
    from repro.core import _fastpath

    lib = _fastpath.get_lib()
    if lib is None:
        pytest.skip("no C compiler in this environment")
    fast = _fastpath.FastEval(lib)
    for n, offs in [(100, [1, 7]), (130, [2, 9, 31]), (64, [1, 5])]:
        adj = circulant(n, offs).adjacency()
        nbr = metrics._nbr_table(adj)
        ref = metrics.bitset_bfs_rows(nbr, np.arange(n), n)
        assert np.array_equal(metrics.bitset_bfs_rows(nbr, np.arange(n), n,
                                                      fast=fast), ref)
        srcs = np.array([0, 3, n - 1])
        assert np.array_equal(metrics.bitset_bfs_rows(nbr, srcs, n, fast=fast),
                              ref[srcs])


@settings(max_examples=15, deadline=None)
@given(st.sampled_from([(12, 3), (16, 4), (24, 4), (24, 6)]),
       st.integers(0, 10_000))
def test_orbit_bitset_engine_matches_other_engines(shape, swap_seed):
    """Every registered engine — dense numpy, bitset, the Pallas device
    sweep (interpret mode) and the C kernel when available — prices orbit
    swaps bit-identically, with identical delta/full counters, through
    commits and disconnections alike."""
    s, fold = shape
    n = s * fold
    rng = np.random.default_rng(swap_seed)
    offs = [1] + sorted(rng.choice(range(2, n // 2), size=2, replace=False).tolist())
    adj = circulant(n, offs).adjacency()
    from repro.core import _fastpath

    engines = ["numpy", "bitset", "pallas"] \
        + (["c"] if _fastpath.get_lib() is not None else [])
    evs = {e: metrics.SymmetricAPSP(adj.copy(), shift=s, engine=e) for e in engines}
    for _ in range(6):
        swap = _random_orbit_swap(evs["numpy"], rng)
        if swap is None:
            continue
        toks = {e: ev.evaluate_swap(*swap) for e, ev in evs.items()}
        ref = toks["numpy"]
        for e, tok in toks.items():
            assert np.array_equal(tok.dist, ref.dist), e
            assert tok.total == ref.total and tok.diam == ref.diam, e
            assert tok.mpl == ref.mpl, e
        if rng.random() < 0.6:
            for e, ev in evs.items():
                ev.commit(toks[e])
                ev.verify()
    assert len({(ev.n_delta, ev.n_full) for ev in evs.values()}) == 1


def test_symmetric_engine_validation():
    adj = circulant(24, [1, 5]).adjacency()
    with pytest.raises(ValueError, match="engine"):
        metrics.SymmetricAPSP(adj, shift=6, engine="bogus")
    ev = metrics.SymmetricAPSP(adj, shift=6, engine="bitset")
    assert ev.engine == "bitset" and ev.fast is None and ev.a32 is None


def test_engine_registry_is_the_single_validation_point():
    """core.engines owns names, capabilities and availability probes."""
    from repro.core import engines

    assert engines.ROWS_ENGINES == ("c", "numpy", "bitset", "pallas")
    assert metrics.SymmetricAPSP.ENGINES == engines.ROWS_ENGINES
    # numpy/bitset have no external dependency and are always available
    assert {"numpy", "bitset"} <= set(engines.available_engines())
    with pytest.raises(ValueError, match="engine"):
        engines.get_engine("bogus")
    eng = engines.resolve_rows(None, use_c=False)
    assert eng.name == "numpy" and eng.needs_dense_mirror and not eng.uses_nbr
    assert engines.resolve_rows("bitset").uses_nbr
    with pytest.raises(ValueError, match="engine"):
        engines.resolve_circulant("bogus", 64)
    assert engines.resolve_circulant("auto", 64) == "numpy"
    # out-of-tree engines registered at runtime resolve like the built-ins
    class _Probe(engines.Engine):
        name = "probe-test"

    engines.register(_Probe())
    try:
        assert "probe-test" in engines.ROWS_ENGINES
        assert metrics.SymmetricAPSP.ENGINES == engines.ROWS_ENGINES  # live view
        assert engines.get_engine("probe-test").name == "probe-test"
    finally:  # keep the process-wide registry clean for other tests
        engines._REGISTRY.pop("probe-test")
        engines.ROWS_ENGINES = tuple(
            nm for nm in engines.ROWS_ENGINES if nm != "probe-test")


def test_engine_env_override(monkeypatch):
    """REPRO_ENGINE forces the auto resolution (the CI engine-matrix knob);
    an explicit engine= still wins."""
    adj = circulant(24, [1, 5]).adjacency()
    monkeypatch.setenv("REPRO_ENGINE", "bitset")
    assert metrics.SymmetricAPSP(adj.copy(), shift=6).engine == "bitset"
    assert metrics.SymmetricAPSP(adj.copy(), shift=6, engine="numpy").engine == "numpy"
    monkeypatch.setenv("REPRO_ENGINE", "bogus")
    with pytest.raises(ValueError, match="engine"):
        metrics.SymmetricAPSP(adj.copy(), shift=6)


def test_symmetric_evaluator_rejects_asymmetric_input():
    adj = circulant(24, [1, 5]).adjacency()
    adj[0, 9] = adj[9, 0] = True  # break the rotational symmetry
    with pytest.raises(ValueError, match="not invariant"):
        metrics.SymmetricAPSP(adj, shift=6)
    with pytest.raises(ValueError, match="divisor"):
        metrics.SymmetricAPSP(circulant(24, [1, 5]).adjacency(), shift=7)


def test_symmetric_evaluator_rejects_non_orbit_swap():
    ev = metrics.SymmetricAPSP(circulant(24, [1, 5]).adjacency(), shift=6)
    with pytest.raises(ValueError, match="not closed"):
        ev.evaluate_swap([(0, 5)], [])  # single edge, orbit has 4
    with pytest.raises(ValueError, match="not closed"):
        ev.evaluate_swap([], [(0, 9)])


def test_orbit_disconnecting_swap_reports_inf_and_recovers():
    """Removing the ring orbit disconnects C_24(1,8) rows -> inf; the next
    (forced-full) swap restores it — both paths stay exact throughout."""
    n, s = 24, 6
    ev = metrics.SymmetricAPSP(circulant(n, [1, 8]).adjacency(), shift=s)
    ring_orbit = sorted({(i, (i + 1) % n) if i + 1 < n else (0, n - 1)
                         for i in range(n)})
    tok = ev.evaluate_swap(ring_orbit, [])
    assert tok.mpl == float("inf")
    assert np.array_equal(tok.dist, _reference(ev.adj, ring_orbit, [])[: s])
    ev.commit(tok)
    ev.verify()
    assert not ev.connected and ev.mpl() == float("inf")
    tok2 = ev.evaluate_swap([], ring_orbit)
    assert ev.n_full >= 1  # disconnected base forces the full path
    assert tok2.mpl < float("inf")
    ev.commit(tok2)
    ev.verify()
    assert ev.connected


# ------------------------------------------------------------------------------
# Pallas device sweep (engine="pallas", interpret mode on CPU)
# ------------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(st.integers(10, 70), st.sampled_from([3, 4, 6]), st.integers(0, 10_000))
def test_pallas_rows_match_bitset_sweep(n, k, seed):
    """The Pallas packed sweep (32-bit words, VMEM level loop) is
    bit-identical to the host uint64 bitset sweep on random regular graphs —
    full and subset source sets, counts not divisible by the word width."""
    pytest.importorskip("jax")
    from repro.kernels import bfs_sweep

    if n * k % 2 or n <= k:
        n, k = 23, 4  # deliberately not divisible by the 32-bit word width
    try:
        g = random_hamiltonian_regular(n, k, seed=seed)
    except RuntimeError:
        return
    nbr = metrics._nbr_table(g.adjacency())
    ref = metrics.bitset_bfs_rows(nbr, np.arange(n), n)
    assert np.array_equal(bfs_sweep.bfs_rows(nbr, np.arange(n), n), ref)
    rng = np.random.default_rng(seed)
    m = int(rng.integers(1, n))
    srcs = rng.choice(n, size=m, replace=False)
    assert np.array_equal(bfs_sweep.bfs_rows(nbr, srcs, n), ref[srcs])


def test_pallas_rows_disconnected_and_sentinel():
    """Disconnected components hold the sentinel, for any sentinel value —
    same contract as the host bitset sweep."""
    pytest.importorskip("jax")
    from repro.kernels import bfs_sweep

    edges = [(i, (i + 1) % 5) for i in range(5)] + \
            [(5 + i, 5 + (i + 1) % 5) for i in range(5)]
    nbr = metrics._nbr_table(from_edges(10, edges).adjacency())
    ref = metrics.bitset_bfs_rows(nbr, np.arange(10), 99)
    got = bfs_sweep.bfs_rows(nbr, np.arange(10), 99)
    assert np.array_equal(got, ref)
    assert (got == 99).sum() == 50  # 2 components of 5: half the pairs


def test_pallas_engine_empty_sources_and_blocks():
    """Zero sources short-circuit; source counts spanning multiple word
    blocks (> 128) slice back to exactly m rows."""
    pytest.importorskip("jax")
    from repro.kernels import bfs_sweep

    nbr = metrics._nbr_table(circulant(150, [1, 7]).adjacency())
    assert bfs_sweep.bfs_rows(nbr, np.arange(0), 150).shape == (0, 150)
    ref = metrics.bitset_bfs_rows(nbr, np.arange(150), 150)
    assert np.array_equal(bfs_sweep.bfs_rows(nbr, np.arange(150), 150), ref)


def test_swap_token_diameter_deferred_then_committed():
    g = random_hamiltonian_regular(20, 4, seed=1)
    ev = metrics.IncrementalAPSP(g.adjacency().copy())
    rng = np.random.default_rng(0)
    ring_mask = _swap_space(20)
    swap = None
    while swap is None:
        swap = _random_swap(ev, ring_mask, rng)
    tok = ev.evaluate_swap(*swap, want_diameter=False)
    ev.commit(tok)
    ref = metrics.apsp_hops(ev.adj)
    assert ev.diam == int(ref.max())
    assert ev.total == int(ref.sum(dtype=np.int64))
