"""Per-kernel allclose sweeps (interpret=True) against the pure-jnp oracles,
shape/dtype parametrized per assignment."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


def _fa_case(b, sq, skv, h, kv, hd, dtype):
    q = jnp.asarray(RNG.normal(size=(b, sq, h, hd)), dtype)
    k = jnp.asarray(RNG.normal(size=(b, skv, kv, hd)), dtype)
    v = jnp.asarray(RNG.normal(size=(b, skv, kv, hd)), dtype)
    return q, k, v


FA_CASES = [
    # b, sq, skv, h, kv, hd, causal, dtype, tol
    (2, 128, 128, 4, 2, 64, True, jnp.float32, 5e-5),
    (2, 128, 128, 4, 4, 64, False, jnp.float32, 5e-5),
    (1, 256, 256, 4, 1, 128, True, jnp.float32, 5e-5),
    (1, 256, 256, 8, 8, 128, True, jnp.bfloat16, 3e-2),
    (2, 128, 256, 6, 2, 112, False, jnp.float32, 5e-5),  # hd-padding path
    (1, 128, 384, 8, 2, 128, True, jnp.bfloat16, 3e-2),  # q_offset path
    (1, 512, 512, 2, 2, 64, True, jnp.float32, 5e-5),    # multi-q-block
]


@pytest.mark.parametrize("b,sq,skv,h,kv,hd,causal,dtype,tol", FA_CASES)
def test_flash_attention_vs_ref(b, sq, skv, h, kv, hd, causal, dtype, tol):
    q, k, v = _fa_case(b, sq, skv, h, kv, hd, dtype)
    off = skv - sq
    got = ops.flash_attention(q, k, v, causal=causal, q_offset=off)
    want = ref.flash_attention_ref(q, k, v, causal=causal, q_offset=off)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_block_shape_sweep():
    q, k, v = _fa_case(1, 256, 256, 2, 2, 64, jnp.float32)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    for bq, bk in [(64, 64), (128, 64), (64, 128), (256, 256)]:
        got = ops.flash_attention(q, k, v, causal=True, blk_q=bq, blk_k=bk)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-5, rtol=5e-5)


SSD_CASES = [
    # b, s, h, p, n, chunk
    (2, 64, 4, 8, 16, 16),
    (1, 96, 2, 64, 128, 32),
    (2, 100, 4, 8, 16, 32),   # padding path
    (1, 256, 2, 16, 32, 256), # single chunk
]


@pytest.mark.parametrize("b,s,h,p,n,chunk", SSD_CASES)
@pytest.mark.parametrize("with_init", [False, True])
def test_ssd_scan_vs_ref(b, s, h, p, n, chunk, with_init):
    x = jnp.asarray(RNG.normal(size=(b, s, h, p)), jnp.float32)
    dt = jnp.asarray(np.abs(RNG.normal(size=(b, s, h))) * 0.5, jnp.float32)
    A = jnp.asarray(-np.abs(RNG.normal(size=(h,))), jnp.float32)
    B = jnp.asarray(RNG.normal(size=(b, s, h, n)), jnp.float32)
    C = jnp.asarray(RNG.normal(size=(b, s, h, n)), jnp.float32)
    H0 = jnp.asarray(RNG.normal(size=(b, h, p, n)), jnp.float32) if with_init else None
    y, H = ops.ssd_scan(x, dt, A, B, C, chunk=chunk, init_state=H0)
    y_r, H_r = ref.ssd_scan_ref(x, dt, A, B, C, init_state=H0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_r), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(H), np.asarray(H_r), atol=2e-4, rtol=2e-4)


def test_model_paths_match_with_pallas():
    """End-to-end: model losses identical with/without the Pallas kernels."""
    from repro.configs import get_config, reduced_config
    from repro.models import build_model

    for arch in ("qwen3-32b", "mamba2-2.7b", "zamba2-2.7b"):
        cfg = reduced_config(get_config(arch))
        m0, m1 = build_model(cfg), build_model(cfg, use_pallas=True)
        params = m0.init(jax.random.key(0))
        batch = {"tokens": jax.random.randint(jax.random.key(1), (2, 32), 0, cfg.vocab),
                 "labels": jax.random.randint(jax.random.key(2), (2, 32), 0, cfg.vocab)}
        l0, _ = m0.loss(params, batch)
        l1, _ = m1.loss(params, batch)
        assert abs(float(l0) - float(l1)) < 5e-3, arch


# ------------------------------------------------------------------------------
# Word-packed BFS frontier sweep (kernels.bfs_sweep)
# ------------------------------------------------------------------------------

def test_bfs_sweep_kernel_matches_jnp_oracle():
    """The Pallas kernel and its pure-jnp twin (sweep_rows_ref) agree
    bit-exactly, including the packing helpers."""
    from repro.core import metrics
    from repro.core.graphs import circulant
    from repro.kernels import bfs_sweep

    for n, offs, m in [(96, [1, 7], 96), (130, [2, 9, 31], 37), (64, [1, 5], 64)]:
        nbr = metrics._nbr_table(circulant(n, offs).adjacency())
        srcs = np.arange(m)
        nb, src = bfs_sweep.pack_sweep(nbr[None], [srcs])
        assert src.shape[1] == bfs_sweep.source_lanes(m)
        assert (src[0, m:] == n).all()  # idle lanes seed nothing
        oracle = np.asarray(jax.jit(bfs_sweep.sweep_rows_ref, static_argnums=2)(
            nb[0], src[0], n))[:m]
        got = bfs_sweep.bfs_rows(nbr, srcs, n)
        assert np.array_equal(got, oracle)
        assert np.array_equal(got, metrics.bitset_bfs_rows(nbr, srcs, n))


def test_bfs_sweep_batched_stack():
    """The batched grid (replica axis) prices each stacked graph exactly as
    the single-graph path does."""
    from repro.core import metrics
    from repro.core.graphs import circulant
    from repro.kernels import bfs_sweep

    n, m = 60, 15
    nbrs = np.stack([metrics._nbr_table(circulant(n, offs).adjacency())
                     for offs in ([1, 7], [1, 11], [2, 9])])
    out = np.asarray(bfs_sweep.bfs_rows_batched(nbrs, np.arange(m), n))
    for r in range(3):
        assert np.array_equal(out[r], bfs_sweep.bfs_rows(nbrs[r], np.arange(m), n))


def test_sharded_rows_totals_match_host():
    """The shard_map-batched (total, max) pricing of whole graphs (every
    representative row swept, no patch: the polish's warm start and
    full-rebuild slots) equals host BFS sums, on both the Pallas and jnp
    device paths."""
    from repro.core import metrics
    from repro.core.engines import pallas_sweep
    from repro.core.graphs import circulant

    n, m = 60, 15
    nbrs = np.stack([metrics._nbr_table(circulant(n, offs).adjacency())
                     for offs in ([1, 7], [1, 11])])
    want = np.stack([metrics.bitset_bfs_rows(nbrs[r], np.arange(m), n)
                     for r in range(2)])
    for use_pallas in (True, False):
        tot, mx, state = pallas_sweep.sharded_delta_state(
            np.zeros((2, m, n), np.int32), nbrs, [np.arange(m)] * 2,
            [None] * 2, n, use_pallas=use_pallas)
        assert np.array_equal(tot, want.sum((1, 2), dtype=np.int64)), use_pallas
        assert np.array_equal(mx, want.max((1, 2))), use_pallas
        assert np.array_equal(np.asarray(state), want), use_pallas


def _orbit_graph(fold: int, s: int, chords: int, odd_kmax: bool, seed: int,
                 disconnected: bool = False) -> np.ndarray:
    """Padded (n, kmax) neighbour table of a random graph on n = fold * s
    vertices that is invariant under rotation by s: a ring and ``chords``
    random chord orbits a residue (or, ``disconnected``, only the edges
    (i, i + s), so that no orbit reaches another).  At least one pad
    column, and kmax odd or even as asked."""
    rng = np.random.default_rng(seed)
    n = fold * s
    adj = [set() for _ in range(n)]

    def add_orbit(u, v):
        for q in range(0, n, s):
            a, b = (u + q) % n, (v + q) % n
            if a != b:
                adj[a].add(b)
                adj[b].add(a)

    for i in range(s):
        if disconnected:
            add_orbit(i, i + s)
            continue
        add_orbit(i, i + 1)
        for _ in range(chords):
            add_orbit(i, int(rng.integers(n)))
    deg = max(len(a) for a in adj)
    kmax = deg + 1 if (deg + 1) % 2 == odd_kmax else deg + 2
    nbr = np.full((n, kmax), -1, dtype=np.int32)
    for v, a in enumerate(adj):
        nbr[v, :len(a)] = sorted(a)
    return nbr


# fold, s, chords, odd kmax, sources of the two graphs, disconnected
ORBIT_CASES = [
    (2, 150, 2, True, (150, 131), False),  # two 128-lane blocks
    (4, 12, 1, False, (7, 12), False),  # idle lanes
    (8, 20, 1, True, (20, 3), True),  # sentinels
    (16, 9, 2, False, (9, 4), False),
    (8, 13, 2, False, (13, 13), False),
]


@pytest.mark.parametrize("fold,s,chords,odd,ms,disconnected", ORBIT_CASES)
def test_orbit_sweep_matches_row_sweep_and_oracle(fold, s, chords, odd, ms,
                                                  disconnected):
    """The orbit kernel, the row kernel and the jnp twin give the same
    hop counts, sentinels and idle lanes included, on graphs invariant
    under rotation by s (s not a power of two, kmax odd and even, pad
    entries, more than 128 source lanes)."""
    from repro.kernels import bfs_sweep

    nbr = _orbit_graph(fold, s, chords, odd, seed=fold * s,
                       disconnected=disconnected)
    n, kmax = nbr.shape
    assert kmax % 2 == odd and (nbr < 0).any()
    rng = np.random.default_rng(s)
    srcs = [np.sort(rng.choice(s, m, replace=False)) for m in ms]
    nb, src = bfs_sweep.pack_sweep(np.stack([nbr, nbr]), srcs)
    b, lanes = src.shape
    assert bfs_sweep.sweep_fold(n, s) == fold
    oracle = np.asarray(jax.jit(jax.vmap(
        lambda t, x: bfs_sweep.sweep_rows_ref(t, x, n)))(nb, src))
    orbit = np.asarray(bfs_sweep._pallas_orbit_sweep(
        b, n, s, kmax, lanes, n, interpret=True)(nb, src))
    rows = np.asarray(bfs_sweep._pallas_sweep(
        b, n, kmax, lanes, n, interpret=True)(nb, src))
    assert np.array_equal(orbit, oracle)
    assert np.array_equal(rows, oracle)
    for r, x in enumerate(srcs):
        # sentinels where an orbit reaches no other; idle lanes sweep nothing
        assert (orbit[r, :len(x)] == n).any() == disconnected
        assert (orbit[r, len(x):] == n).all()


def test_orbit_sweep_stages_the_table_in_chunks(monkeypatch):
    """Where s residues do not fit one SMEM staging buffer, the table is
    staged a chunk at a time, every level, with the same result."""
    from repro.kernels import bfs_sweep

    monkeypatch.setattr(bfs_sweep, "NB_ROWS", 8)
    nbr = _orbit_graph(4, 21, 2, True, seed=3)
    n, kmax = nbr.shape
    nb, src = bfs_sweep.pack_sweep(nbr[None], [np.arange(21)])
    oracle = np.asarray(jax.jit(bfs_sweep.sweep_rows_ref, static_argnums=2)(
        nb[0], src[0], n))
    orbit = bfs_sweep._pallas_orbit_sweep(1, n, 21, kmax, src.shape[1], n,
                                          interpret=True)
    assert np.array_equal(np.asarray(orbit(nb, src))[0], oracle)


def test_sweep_picks_the_kernel_by_shape(monkeypatch):
    """The delta dispatch's sweep takes the orbit kernel where the fold
    n / s is 2 or more and divides 8 or is a multiple of it, the row kernel
    otherwise (fold 1, folds 3, 6 and 12, no s), and the jnp twin with the
    Pallas kernels off."""
    from repro.core.engines import pallas_sweep
    from repro.kernels import bfs_sweep

    took = []
    monkeypatch.setattr(bfs_sweep, "_pallas_orbit_sweep",
                        lambda *a, **k: lambda nb, src: took.append("orbit"))
    monkeypatch.setattr(bfs_sweep, "_pallas_sweep",
                        lambda *a, **k: lambda nb, src: took.append("row"))
    n = 96
    for s, want in [(48, "orbit"), (24, "orbit"), (12, "orbit"),
                    (6, "orbit"), (96, "row"), (32, "row"), (16, "row"),
                    (8, "row"), (None, "row")]:
        fold = n // s if s else 1
        assert bfs_sweep.sweep_fold(n, s) == (fold if want == "orbit" else 1)
        took.clear()
        pallas_sweep._sweep(True, True, n, 4, 32, n, s)(
            np.zeros((1, n, 4), np.int32), np.zeros((1, 32), np.int32))
        assert took == [want], (s, took)
    assert bfs_sweep.sweep_fold(100, 30) == 1  # no whole fold
    took.clear()
    pallas_sweep._sweep(False, True, n, 4, 32, n, 12)
    assert took == []
