"""Batched BFS row sweep as a Pallas TPU kernel (the device engine).

Computes the same hop distances as ``repro.core.metrics.bitset_bfs_rows``
— one BFS level advancing every source at once over the padded neighbour
table — for a stack of graphs, each swept from its own source list.

Two implementations share one wire format: ``nb`` (n, kmax) int32 gather
tables whose pad entries point at the vertex's own row (harmless: a vertex
already in the frontier is already visited), and ``src`` (lanes,) int32
source vertices, idle lanes holding ``n`` (no vertex).

* ``sweep_rows_ref`` is the jnp twin: frontier and visited sets packed into
  32-bit words along the source axis (the host bitset engine's uint64 words
  split into lower/upper halves — TPU vector units have no 64-bit lanes),
  advanced by word-parallel OR/AND-NOT gathers.  It runs on any backend and
  under ``vmap``.
* ``_pallas_sweep`` is the row kernel, for any graph.  Grid ``(graph,
  source block)``; one cell owns ``LANES`` = 128 sources (one int32 vreg
  row per vertex) and keeps its ``(n, 128)`` distance state resident in
  VMEM for the whole level loop (4 MiB at n = 8192).  A level is a row loop
  over vertices: the neighbour indices are read as scalars from SMEM,
  staged from HBM ``NB_ROWS`` vertices at a time, and each neighbour's
  distance row is a dynamic single-row load.  Vertex ``v`` is reached at
  level ``d + 1`` in lane j iff it is unreached and some neighbour sits at
  exactly ``d``; rows are updated in place, which is safe because a row
  written during level d holds ``d + 1`` and can never match ``== d``.
  The finished block is copied to HBM vertex-major and transposed to
  ``(lanes, n)`` by XLA.
* ``_pallas_orbit_sweep`` is the orbit kernel, for graphs invariant under
  rotation by ``s`` (the symmetric polish's, swept from sources below
  ``s``).  The neighbours of ``v = q * s + i`` are ``{(u + q * s) mod n :
  u in nb[i]}``, so the state is one ``(h, 128)`` tile per residue ``i``,
  ``h = max(8, fold)``, whose row q holds vertex ``(q mod fold) * s + i``,
  and neighbour slot k of the whole orbit is the tile of residue
  ``u mod s`` rolled along the orbit axis by ``u // s``.  A level loops
  over the s residues with whole-tile loads, not over the n vertices, by
  the same in-place rule; only the first s table rows are read, packed as
  (residue, roll) pairs and staged in SMEM once a cell where they fit.
  ``sweep_fold`` says where it applies: a fold of at least 2 that divides
  8 or is a multiple of 8, so the tile meets the (8, 128) tiling.

Both return exact integer hop counts, with ``sentinel`` for unreachable
pairs, so they are bit-identical (property tests in ``tests/test_kernels.py``
and ``tests/test_incremental.py``).  Whether the kernel runs compiled or in
Pallas interpret mode is the caller's argument, resolved from the platform
by ``repro.core.engines.pallas_sweep.get_interpret``.
"""
from __future__ import annotations

import functools

import numpy as np

WORD = 32  # uint32 packing of the jnp twin (no 64-bit vector lanes on TPU)
LANES = 128  # sources per sweep-kernel grid cell: one int32 vreg row
NB_ROWS = 1024  # neighbour-table rows staged in SMEM per copy (kmax * 4 KiB)
# the orbit kernel's packed table entry: residue << _ROT_BITS | rotation
_ROT_BITS = 16
_ROT_MASK = (1 << _ROT_BITS) - 1
# residues the orbit kernel advances between two rounds of stores: on a v5e
# at 512 source lanes, 2 swept n = 8192 (fold 8) and n = 4096 (fold 4)
# 1.16x and 1.18x faster than 1, and 1.12x and 1.04x faster than 4
ORBIT_GROUP = 2
# an orbit kernel state larger than this (fold 2 or 4 at large n, up to 4x
# the row kernel's) asks the compiler for its own size plus this much VMEM
SWEEP_VMEM_STATE = 8 << 20
# in-kernel "not reached yet": never equal to a level counter (<= n), mapped
# to the caller's sentinel after the sweep
UNREACHED = np.int32(np.iinfo(np.int32).max)
# VMEM the patch kernel's double-buffered blocks may take: half of v5e's
# 16 MiB default scoped limit, the rest is left to the compiler's temporaries
PATCH_VMEM_BUDGET = 8 << 20
# "unreachable" weight for masked patch entries: real hop distances are
# <= sentinel = n <= 46340, and the patch adds at most two PATCH_INF terms
# plus one distance (2^21 + n), so int32 arithmetic never overflows while
# masked terms can never undercut a real path
PATCH_INF = np.int32(1 << 20)

__all__ = [
    "WORD",
    "LANES",
    "PATCH_INF",
    "bfs_rows",
    "bfs_rows_batched",
    "pack_nbr",
    "pack_patch",
    "pack_sweep",
    "patch_apply_ref",
    "patch_prologue",
    "source_lanes",
    "sweep_fold",
    "sweep_rows_ref",
]

_CACHE: dict = {}


def pack_nbr(nbr: np.ndarray) -> np.ndarray:
    """(..., n, kmax) int32 gather table from padded neighbour tables: pad
    entries (< 0) point at the vertex's own row, so neither sweep needs a
    validity mask or bounds logic."""
    n = nbr.shape[-2]
    return np.where(nbr >= 0, nbr, np.arange(n)[:, None]).astype(np.int32)


def _pow2(x: int) -> int:
    """Smallest power of two >= max(x, 1) — pads variable per-iteration
    shapes (affected-row words, patch endpoints) into a bounded bucket set so
    the jit/pallas caches stay small."""
    return 1 << max(0, int(x) - 1).bit_length()


def source_lanes(m: int) -> int:
    """Padded source-lane count for ``m`` sources: a power of two of 32-bit
    words (the twin's packing; per-iteration delta sweeps then reuse a few
    compiled shapes), which is a whole number of ``LANES`` blocks once it
    exceeds one (the kernel's tiling)."""
    return _pow2(-(-m // WORD)) * WORD


def pack_sweep(nbrs: np.ndarray,
               sources_list) -> tuple[np.ndarray, np.ndarray]:
    """Pack a (b, n, kmax) neighbour-table stack for the batched sweep,
    graph r swept from ``sources_list[r]``: returns ``(nb, src)`` in the
    wire format both sweeps take.  ``src[r, j]`` is the vertex lane j of
    graph r sweeps from; idle lanes hold ``n``, which seeds nothing and
    which the delta merge's out-of-range scatter drops."""
    b, n, _ = nbrs.shape
    lanes = source_lanes(max((len(x) for x in sources_list), default=0))
    src = np.full((b, lanes), n, dtype=np.int32)
    for r, x in enumerate(sources_list):
        src[r, : len(x)] = x
    return pack_nbr(nbrs), src


def _unpack_bits(words, jnp):
    """(n, w) uint32 -> (w*32, n) bool; bit j of word w = row w*32 + j."""
    n, w = words.shape
    shifts = jnp.arange(WORD, dtype=jnp.uint32)
    bits = (words[:, :, None] >> shifts[None, None, :]) & jnp.uint32(1)
    return bits.reshape(n, w * WORD).T.astype(bool)


def sweep_rows_ref(nb, src, sentinel: int):
    """Pure-jnp packed sweep: (n, kmax) gather table and (lanes,) source
    vertices -> (lanes, n) int32 hop distances.

    The jittable oracle for the Pallas kernel, and the ``vmap``-able device
    path the replica-sharded polish takes when the Pallas kernel is off.
    """
    import jax
    import jax.numpy as jnp

    n, kmax = nb.shape
    lanes = src.shape[0]
    j = jnp.arange(lanes)
    bit = jnp.left_shift(jnp.uint32(1), (j % WORD).astype(jnp.uint32))
    # distinct (vertex, word, bit) triples: the add is an OR; idle lanes
    # (src == n) fall off the end
    F0 = jnp.zeros((n, lanes // WORD), jnp.uint32).at[src, j // WORD].add(
        bit, mode="drop")
    dist0 = jnp.where(_unpack_bits(F0, jnp), 0, sentinel).astype(jnp.int32)

    def body(st):
        d, F, V, dist, _ = st
        N = jnp.zeros_like(F)
        for k in range(kmax):  # static unroll: kmax = max degree, small
            N = N | jnp.take(F, nb[:, k], axis=0)
        newF = N & ~V
        d = d + 1
        dist = jnp.where(_unpack_bits(newF, jnp), d, dist)
        return (d, newF, V | newF, dist, jnp.any(newF != jnp.uint32(0)))

    st = (jnp.int32(0), F0, F0, dist0, jnp.any(F0 != jnp.uint32(0)))
    return jax.lax.while_loop(lambda st: st[4], body, st)[3]


def _sweep_kernel(src_ref, nb_hbm, out_hbm, dist_ref, nb_smem, *, n, kmax,
                  rows):
    # one grid cell = one (graph, 128-source block); dist_ref is the
    # (n_pad, blk) state, nb_smem the staged slice of the neighbour table
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r = pl.program_id(0)
    blk = dist_ref.shape[1]
    n_chunks = dist_ref.shape[0] // rows
    dist_ref[...] = jnp.full(dist_ref.shape, UNREACHED, jnp.int32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1)

    def seed(j, carry):
        v = src_ref[0, 0, 0, j]

        @pl.when(v < n)
        def _():
            row = dist_ref[pl.ds(v, 1), :]
            dist_ref[pl.ds(v, 1), :] = jnp.where(lane == j, 0, row)

        return carry

    jax.lax.fori_loop(0, blk, seed, 0)

    def level(st):
        d, _ = st

        def chunk(c, acc):
            pltpu.sync_copy(nb_hbm.at[r, c], nb_smem)

            def vertex(lv, acc):
                v = c * rows + lv
                cur = dist_ref[pl.ds(v, 1), :]
                hit = jnp.zeros((1, blk), jnp.bool_)
                for k in range(kmax):  # static unroll: kmax = max degree
                    u = nb_smem[0, lv * kmax + k]
                    hit = hit | (dist_ref[pl.ds(u, 1), :] == d)
                new = hit & (cur == UNREACHED)
                dist_ref[pl.ds(v, 1), :] = jnp.where(new, d + 1, cur)
                return acc | new.astype(jnp.int32)

            return jax.lax.fori_loop(0, rows, vertex, acc)

        acc = jax.lax.fori_loop(0, n_chunks, chunk,
                                jnp.zeros((1, blk), jnp.int32))
        return d + 1, jnp.max(acc) > 0

    jax.lax.while_loop(lambda st: st[1], level,
                       (jnp.int32(0), jnp.bool_(True)))
    pltpu.sync_copy(dist_ref.at[pl.ds(0, n)], out_hbm.at[r, pl.program_id(1)])


def _pallas_sweep(b: int, n: int, kmax: int, lanes: int, sentinel: int,
                  interpret: bool):
    """Compiled batched sweep: (b, n, kmax) tables and (b, lanes) sources
    -> (b, lanes, n) int32 distances."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    key = ("sweep", b, n, kmax, lanes, sentinel, interpret)
    fn = _CACHE.get(key)
    if fn is not None:
        return fn
    # always whole 128-lane blocks: a narrower VMEM block is lane-padded to
    # 128 anyway, and its copy-out slice would break the (8, 128) tiling
    nblk = -(-lanes // LANES)
    rows = min(n, NB_ROWS)
    n_pad = -(-n // rows) * rows
    call = pl.pallas_call(
        functools.partial(_sweep_kernel, n=n, kmax=kmax, rows=rows),
        grid=(b, nblk),
        in_specs=[
            pl.BlockSpec((1, 1, 1, LANES), lambda r, i: (r, i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.HBM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.HBM),
        out_shape=jax.ShapeDtypeStruct((b, nblk, n, LANES), jnp.int32),
        scratch_shapes=[pltpu.VMEM((n_pad, LANES), jnp.int32),
                        pltpu.SMEM((1, rows * kmax), jnp.int32)],
        interpret=interpret,
    )

    def sweep(nb, src):
        # pad vertices (n <= v < n_pad) loop to themselves: never reached
        pad = jnp.broadcast_to(
            jnp.arange(n, n_pad, dtype=jnp.int32)[None, :, None],
            (b, n_pad - n, kmax))
        nbp = jnp.concatenate([nb, pad], axis=1).reshape(
            b, n_pad // rows, 1, rows * kmax)
        srcp = jnp.pad(src, ((0, 0), (0, nblk * LANES - lanes)),
                       constant_values=n)
        out = call(srcp.reshape(b, nblk, 1, LANES), nbp)
        out = out.transpose(0, 1, 3, 2).reshape(b, nblk * LANES, n)[:, :lanes]
        return jnp.where(out == UNREACHED, sentinel, out)

    fn = jax.jit(sweep)
    _CACHE[key] = fn
    return fn


def sweep_fold(n: int, s: int | None) -> int:
    """The fold ``n / s`` the orbit kernel sweeps a graph invariant under
    rotation by ``s`` with, or 1 where the row kernel does: the orbit
    kernel needs a whole fold of at least 2 that divides 8 or is a
    multiple of 8, so that an ``(8, 128)``-aligned tile of
    ``max(8, fold)`` rows holds the orbit a whole number of times."""
    if not s or n % s:
        return 1
    fold = n // s
    return fold if fold >= 2 and (8 % fold == 0 or fold % 8 == 0) else 1


def _orbit_kernel(src_ref, tab_hbm, out_hbm, dist_ref, tab_smem, *, s, fold,
                  kmax, rows):
    # one grid cell = one (graph, 128-source block); dist_ref is the
    # (s_pad, h, blk) state, one tile per residue i whose row q holds vertex
    # (q mod fold) * s + i, and tab_smem the staged slice of the packed
    # (residue, rotation) table
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r = pl.program_id(0)
    _, h, blk = dist_ref.shape
    n_chunks = dist_ref.shape[0] // rows
    dist_ref[...] = jnp.full(dist_ref.shape, UNREACHED, jnp.int32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (h, blk), 1)
    orbit_row = jax.lax.broadcasted_iota(jnp.int32, (h, blk), 0)
    # the rows that hold vertex 0 * s + i of the orbit (every copy of it)
    head = functools.reduce(jnp.logical_or,
                            [orbit_row == q for q in range(0, h, fold)])

    def seed(j, carry):
        v = src_ref[0, 0, 0, j]

        @pl.when(v < s)
        def _():
            dist_ref[v] = jnp.where(head & (lane == j), 0, dist_ref[v])

        return carry

    jax.lax.fori_loop(0, blk, seed, 0)
    if n_chunks == 1:  # the whole table fits: stage it once, not per level
        pltpu.sync_copy(tab_hbm.at[r, 0], tab_smem)

    def level(st):
        d, _ = st

        def chunk(c, acc):
            if n_chunks > 1:
                pltpu.sync_copy(tab_hbm.at[r, c], tab_smem)

            def group(g, acc):
                # every hit of the group is read before any of its tiles is
                # written, so the loads need not wait for the stores: an
                # entry written at level d holds d + 1, never d, so the
                # hits come out the same in either order
                upd = []
                for t in range(ORBIT_GROUP):
                    li = g * ORBIT_GROUP + t
                    cur = dist_ref[c * rows + li]
                    hit = jnp.zeros((h, blk), jnp.bool_)
                    for k in range(kmax):  # static unroll: kmax = max degree
                        code = tab_smem[0, li * kmax + k]
                        # neighbour slot k of the whole orbit: the tile of
                        # the neighbour's residue, rotated along the orbit
                        nbr = pltpu.roll(dist_ref[code >> _ROT_BITS],
                                         code & _ROT_MASK, 0)
                        hit = hit | (nbr == d)
                    upd.append((li, cur, hit & (cur == UNREACHED)))
                for li, cur, new in upd:
                    dist_ref[c * rows + li] = jnp.where(new, d + 1, cur)
                    acc = acc | new.astype(jnp.int32)
                return acc

            return jax.lax.fori_loop(0, rows // ORBIT_GROUP, group, acc)

        acc = jax.lax.fori_loop(0, n_chunks, chunk,
                                jnp.zeros((h, blk), jnp.int32))
        return d + 1, jnp.max(acc) > 0

    jax.lax.while_loop(lambda st: st[1], level,
                       (jnp.int32(0), jnp.bool_(True)))
    pltpu.sync_copy(dist_ref.at[pl.ds(0, s)], out_hbm.at[r, pl.program_id(1)])


def _pallas_orbit_sweep(b: int, n: int, s: int, kmax: int, lanes: int,
                        sentinel: int, interpret: bool):
    """Compiled batched sweep of graphs invariant under rotation by ``s``,
    by vertex orbit: the wire format of ``_pallas_sweep`` ((b, n, kmax)
    tables, (b, lanes) sources below ``s``, idle lanes at ``n``) ->
    (b, lanes, n) int32 distances.  Only the first ``s`` rows of each
    table are read; ``sweep_fold(n, s)`` must be at least 2."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    fold = n // s
    h = max(8, fold)  # whole (8, 128) tiles: the orbit repeats h / fold times
    # both halves of a packed entry fit: residue < 2^15, rotation < 2^16
    assert sweep_fold(n, s) == fold > 1 and s >> (31 - _ROT_BITS) == 0 \
        and h <= _ROT_MASK, (n, s)
    # whole groups of residues a chunk, NB_ROWS at most
    rows = min(-(-s // ORBIT_GROUP) * ORBIT_GROUP, NB_ROWS)
    key = ("orbit", b, n, s, kmax, lanes, sentinel, rows, ORBIT_GROUP,
           interpret)
    fn = _CACHE.get(key)
    if fn is not None:
        return fn
    nblk = -(-lanes // LANES)
    s_pad = -(-s // rows) * rows
    state = s_pad * h * LANES * 4
    params = None
    if state > SWEEP_VMEM_STATE:
        params = pltpu.CompilerParams(
            vmem_limit_bytes=state + SWEEP_VMEM_STATE)
    call = pl.pallas_call(
        functools.partial(_orbit_kernel, s=s, fold=fold, kmax=kmax,
                          rows=rows),
        grid=(b, nblk),
        in_specs=[
            pl.BlockSpec((1, 1, 1, LANES), lambda r, i: (r, i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.HBM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.HBM),
        out_shape=jax.ShapeDtypeStruct((b, nblk, s, h, LANES), jnp.int32),
        scratch_shapes=[pltpu.VMEM((s_pad, h, LANES), jnp.int32),
                        pltpu.SMEM((1, rows * kmax), jnp.int32)],
        compiler_params=params,
        interpret=interpret,
    )

    def sweep(nb, src):
        # neighbour u of residue i is, for the whole orbit of i, residue
        # u mod s with the orbit rotated by u // s; pad residues
        # (s <= i < s_pad) loop to themselves and are never reached
        u = nb[:, :s, :]
        code = (u % s) << _ROT_BITS | (-(u // s)) % h
        pad = jnp.broadcast_to(
            (jnp.arange(s, s_pad, dtype=jnp.int32) << _ROT_BITS)[None, :, None],
            (b, s_pad - s, kmax))
        tab = jnp.concatenate([code, pad], axis=1).reshape(
            b, s_pad // rows, 1, rows * kmax)
        srcp = jnp.pad(src, ((0, 0), (0, nblk * LANES - lanes)),
                       constant_values=n)
        out = call(srcp.reshape(b, nblk, 1, LANES), tab)[:, :, :, :fold]
        # (graph, block, residue, orbit row, lane) -> vertex q * s + i
        out = out.transpose(0, 1, 4, 3, 2).reshape(b, nblk * LANES, n)
        out = out[:, :lanes]
        return jnp.where(out == UNREACHED, sentinel, out)

    fn = jax.jit(sweep)
    _CACHE[key] = fn
    return fn


def bfs_rows_batched(
    nbrs: np.ndarray,
    sources: np.ndarray,
    sentinel: int,
    interpret: bool | None = None,
):
    """Batched device BFS: (b, n, kmax) neighbour tables -> (b, m, n) int32.

    All graphs share the same ``sources`` (the representative rows of the
    symmetric polish tier).  Returns a jax array; callers slice/convert.
    ``interpret=None`` takes the platform's mode (``get_interpret``).
    """
    if interpret is None:
        from ..core.engines.pallas_sweep import get_interpret

        interpret = get_interpret()
    b, n, kmax = nbrs.shape
    nb, src = pack_sweep(nbrs, [sources] * b)
    out = _pallas_sweep(b, n, kmax, src.shape[1], sentinel, interpret)(nb, src)
    return out[:, : len(sources), :]


def bfs_rows(
    nbr: np.ndarray,
    sources: np.ndarray,
    sentinel: int,
    interpret: bool | None = None,
) -> np.ndarray:
    """Hop distances from ``sources`` via the Pallas sweep, as a
    (len(sources), n) int32 numpy array — the drop-in device twin of
    ``repro.core.metrics.bitset_bfs_rows`` (bit-identical, sentinel
    included; any source count works, idle lanes are sliced away)."""
    m = len(sources)
    n = nbr.shape[0]
    if m == 0:
        return np.full((0, n), sentinel, dtype=np.int32)
    out = bfs_rows_batched(nbr[None], np.asarray(sources), sentinel,
                           interpret=interpret)
    return np.asarray(out[0])


# ------------------------------------------------------------------------------
# Delta sweep: incremental pricing of batched orbit swaps (the device twin of
# ``metrics.SymmetricAPSP.evaluate_swap``).  The host runs the exact batched
# lost-parent removal test on the columns of the state it reads, gathered
# from the device, and packs, per proposal, only the *affected*
# representative rows as sources; the
# sweep then repairs those rows on the post-removal graph, the merged
# state keeps the provably-unchanged rows, and the min-plus insert patch
# applies the added edges — exact integer hop counts end to end, so the delta
# path is bit-identical to a full re-sweep (property-tested).
# ------------------------------------------------------------------------------

def pack_patch(patches, s: int) -> tuple[np.ndarray, ...]:
    """Pack per-proposal min-plus insert patches for the delta sweep.

    ``patches[r]`` is the proposal's added edge list (empty/None for no
    patch).  Returns the seven padded arrays ``patch_prologue`` consumes:
    rolled-row gather metadata (``crow_src``, ``crow_shift``), the endpoint
    index set (``pts_idx``, ``pmask``) and the added-edge clamp
    (``add_i``, ``add_j``, ``add_w``).  Endpoint/edge counts are bucketed to
    powers of two; masked slots carry ``PATCH_INF`` weights so they can
    never undercut a real path.
    """
    b = len(patches)
    pts_all = [sorted({x for e in (p or ()) for x in e}) for p in patches]
    mmax = _pow2(max((len(p) for p in pts_all), default=0))
    amax = _pow2(max((len(p or ()) for p in patches), default=0))
    crow_src = np.zeros((b, mmax), dtype=np.int32)
    crow_shift = np.zeros((b, mmax), dtype=np.int32)
    pts_idx = np.zeros((b, mmax), dtype=np.int32)
    pmask = np.zeros((b, mmax), dtype=bool)
    add_i = np.zeros((b, amax), dtype=np.int32)
    add_j = np.zeros((b, amax), dtype=np.int32)
    add_w = np.full((b, amax), PATCH_INF, dtype=np.int32)
    for r, added in enumerate(patches):
        pts = pts_all[r]
        if not pts:
            continue
        idx = {p: i for i, p in enumerate(pts)}
        m = len(pts)
        crow_src[r, :m] = [p % s for p in pts]
        crow_shift[r, :m] = [p - p % s for p in pts]
        pts_idx[r, :m] = pts
        pmask[r, :m] = True
        for a, (u, v) in enumerate(added):
            add_i[r, a], add_j[r, a], add_w[r, a] = idx[u], idx[v], 1
    return crow_src, crow_shift, pts_idx, pmask, add_i, add_j, add_w


def patch_prologue(new, crow_src, crow_shift, pts_idx, pmask, add_i, add_j,
                   add_w):
    """Per-proposal patch head (jnp): rolled endpoint rows + min-plus closure.

    ``new`` is the merged (s, n) post-removal state of one proposal.  The
    post-removal graph is still rotationally symmetric, so the full row of
    any added-edge endpoint p is ``roll(new[p % s], p - p % s)``; a
    Floyd–Warshall closure over the (masked) endpoint set with the added
    edges clamped to weight 1 gives exact endpoint-to-endpoint distances —
    the same integer math as ``SymmetricAPSP._insert_patch``, with
    ``PATCH_INF`` in masked slots (bucketed shapes) instead of dropping
    them.  Returns ``(tmp, crows)``: ``tmp[r, j] = min_p new[r, p] + w[p, j]``
    and the rolled rows, everything ``patch_apply_ref`` (or the Pallas patch
    kernel) needs for the O(s * n * m) passes.
    """
    import jax
    import jax.numpy as jnp

    mmax = pts_idx.shape[0]
    crows = jax.vmap(lambda r, sh: jnp.roll(new[r], sh))(crow_src, crow_shift)
    ok = pmask[:, None] & pmask[None, :]
    w = jnp.where(ok, jnp.take(crows, pts_idx, axis=1), PATCH_INF)
    w = w.at[add_i, add_j].min(add_w)
    w = w.at[add_j, add_i].min(add_w)
    for kk in range(mmax):  # static unroll: mmax <= a few dozen endpoints
        w = jnp.minimum(w, w[:, kk : kk + 1] + w[kk : kk + 1, :])
    a = jnp.where(pmask[None, :], jnp.take(new, pts_idx, axis=1), PATCH_INF)
    tmp = (a[:, :, None] + w[None, :, :]).min(axis=1)
    return tmp, crows


def patch_apply_ref(dist, tmp, crows):
    """Batched min-plus patch application (jnp twin of the Pallas kernel):
    ``d'(r, y) = min(d(r, y), min_j tmp[r, j] + crows[j, y])`` over the
    (b, s, n) merged states."""
    import jax.numpy as jnp

    mmax = crows.shape[1]
    for j in range(mmax):  # static unroll, one vectorized pass per endpoint
        dist = jnp.minimum(dist, tmp[:, :, j : j + 1] + crows[:, j : j + 1, :])
    return dist


def _patch_kernel(dist_ref, tmp_ref, crows_ref, out_ref, *, mmax):
    # one grid cell = one (proposal, row-block) pair: the O(rb * n * m)
    # min-plus passes run with the distance tile, endpoint rows and tmp
    # staged in VMEM
    import jax.numpy as jnp

    d = dist_ref[0]
    tmp = tmp_ref[0]
    crows = crows_ref[0]
    for j in range(mmax):
        d = jnp.minimum(d, tmp[:, j : j + 1] + crows[j : j + 1, :])
    out_ref[0] = d


def _patch_rows(s: int, n: int, mmax: int,
                budget: int = PATCH_VMEM_BUDGET) -> int:
    """Row-tile height of the patch kernel: the largest divisor of ``s``
    that meets the (8, 128) tiling rule (a multiple of 8, or all of ``s``)
    and whose double-buffered blocks — dist and out (rb, n), tmp (rb, mmax),
    crows (mmax, n), each padded to whole (8, 128) int32 tiles — fit
    ``budget`` bytes; the smallest legal divisor when none fits."""
    def tiles(rows: int, cols: int) -> int:
        return -(-rows // 8) * 8 * -(-cols // 128) * 128 * 4

    legal = [d for d in range(1, s + 1) if s % d == 0 and (d % 8 == 0 or d == s)]
    fits = [d for d in legal
            if 2 * (2 * tiles(d, n) + tiles(d, mmax) + tiles(mmax, n)) <= budget]
    return max(fits) if fits else min(legal)


def _pallas_patch(b: int, s: int, n: int, mmax: int, interpret: bool):
    """Compiled batched patch for (b, s, n)/(b, s, mmax)/(b, mmax, n) inputs."""
    import jax
    from jax.experimental import pallas as pl

    rb = _patch_rows(s, n, mmax)
    key = ("patch", b, s, n, mmax, rb, interpret)
    fn = _CACHE.get(key)
    if fn is not None:
        return fn
    kernel = functools.partial(_patch_kernel, mmax=mmax)
    fn = pl.pallas_call(
        kernel,
        grid=(b, s // rb),
        in_specs=[
            pl.BlockSpec((1, rb, n), lambda r, i: (r, i, 0)),
            pl.BlockSpec((1, rb, mmax), lambda r, i: (r, i, 0)),
            pl.BlockSpec((1, mmax, n), lambda r, i: (r, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, rb, n), lambda r, i: (r, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, n), jax.numpy.int32),
        interpret=interpret,
    )
    fn = jax.jit(fn)
    _CACHE[key] = fn
    return fn
