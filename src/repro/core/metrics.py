"""Graph invariants used by the paper: MPL, diameter, girth, bisection width,
and the Cerf et al. (1974) lower bounds for regular graphs.

All routines are pure numpy and deterministic.  ``apsp`` is the workhorse —
a frontier-expansion BFS over the dense boolean adjacency, O(D · N^3 / word)
via boolean matmul, comfortably fast for the paper's N ≤ 1024.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from . import engines
from .graphs import Graph

__all__ = [
    "apsp",
    "apsp_hops",
    "bitset_bfs_rows",
    "IncrementalAPSP",
    "SymmetricAPSP",
    "mpl",
    "diameter",
    "eccentricities",
    "girth",
    "is_connected",
    "bisection_width",
    "moore_bound_vertices",
    "diameter_lower_bound",
    "mpl_lower_bound",
    "edge_betweenness_proxy",
    "GraphStats",
    "stats",
]


def apsp(g: Graph) -> np.ndarray:
    """All-pairs shortest-path hop distances. inf for disconnected pairs."""
    n = g.n
    adj = g.adjacency()
    dist = np.full((n, n), np.inf)
    np.fill_diagonal(dist, 0.0)
    reach = np.eye(n, dtype=bool)
    frontier = np.eye(n, dtype=bool)
    d = 0
    while frontier.any():
        d += 1
        # vertices reachable in exactly <= d hops
        nxt = frontier @ adj
        frontier = nxt & ~reach
        dist[frontier] = d
        reach |= frontier
    return dist


def is_connected(g: Graph) -> bool:
    return bool(np.isfinite(apsp(g)).all())


# --------------------------------------------------------------------------------
# Incremental APSP under 2-edge swaps (the search engine's hot path)
# --------------------------------------------------------------------------------

def _bfs_rows(a32: np.ndarray, sources: np.ndarray, sentinel: int) -> np.ndarray:
    """Hop distances from ``sources`` via frontier BFS over float32 matmuls.

    Returns an int32 (len(sources), n) matrix; unreachable = ``sentinel``.
    """
    n = a32.shape[0]
    s = len(sources)
    dist = np.full((s, n), sentinel, dtype=np.int32)
    reach = np.zeros((s, n), dtype=bool)
    dist[np.arange(s), sources] = 0
    reach[np.arange(s), sources] = True
    frontier = reach.astype(np.float32)
    d = 0
    while True:
        nxt = (frontier @ a32) > 0
        newf = nxt & ~reach
        if not newf.any():
            break
        d += 1
        dist[newf] = d
        reach |= newf
        frontier = newf.astype(np.float32)
    return dist


def bitset_bfs_rows(
    nbr: np.ndarray,
    sources: np.ndarray,
    sentinel: int,
    fast=None,
) -> np.ndarray:
    """Word-packed batched BFS: hop distances from ``sources`` as int32.

    The frontier and visited sets are packed into ``uint64`` words along the
    *source* dimension — ``F[v]`` is a ``ceil(len(sources)/64)``-word bitset
    whose bit ``j`` says "source j's frontier contains vertex v" — so one
    level advances every source at once with word-parallel OR/AND-NOT sweeps:

        N[v]  = OR_{u in nbr(v)} F[u]      (gather over the neighbour table)
        newF  = N & ~V;  V |= newF

    For a k-regular graph this is O(n * k * len(sources) / 64) words per
    level, replacing the dense O(n^2)-per-level matmul BFS — at N=8192 the
    whole frontier/visited state for the 1024 representative sources is ~1 MB
    per set.  ``fast`` is an optional ``_fastpath.FastEval`` whose C sweep
    replaces the numpy word ops (bit-identical either way; unreachable
    vertices hold ``sentinel``).  Works for any source count, including
    counts not divisible by 64 (tail bits simply stay zero).
    """
    n = nbr.shape[0]
    sources = np.ascontiguousarray(sources, dtype=np.int32)
    m = len(sources)
    dist = np.full((m, n), sentinel, dtype=np.int32)
    if m == 0:
        return dist
    if fast is not None:
        fast.bitset_bfs_rows(nbr, sources, dist)
        if sentinel != n:  # the C sweep writes n for unreachable
            dist[dist >= n] = sentinel
        return dist
    sw = (m + 63) >> 6
    j = np.arange(m)
    F = np.zeros((n, sw), dtype=np.uint64)
    # sources are distinct vertices (rows of a distance matrix), so plain
    # fancy assignment cannot collide
    F[sources, j >> 6] = np.uint64(1) << (j & 63).astype(np.uint64)
    V = F.copy()
    dist[j, sources] = 0
    valid = nbr >= 0
    nb = np.where(valid, nbr, 0)
    vmask = np.where(valid, ~np.uint64(0), np.uint64(0))[:, :, None]
    d = 0
    while True:
        N = np.bitwise_or.reduce(F[nb] & vmask, axis=1)
        newF = N & ~V
        if not newF.any():
            break
        d += 1
        V |= newF
        # unpack the new-frontier bits to (n, m) bool; the explicit
        # little-endian cast (a no-op view on LE hosts) + LSB-first unpack
        # matches the 1 << (j & 63) packing above on any byte order
        cols = np.unpackbits(newF.astype("<u8", copy=False).view(np.uint8),
                             axis=1, bitorder="little")[:, :m]
        dist[cols.T.astype(bool)] = d
        F = newF
    return dist


def apsp_hops(adj: np.ndarray, sentinel: int | None = None) -> np.ndarray:
    """All-pairs hop distances from a boolean adjacency as int32.

    Unreachable pairs hold ``sentinel`` (default n, one more than any real
    distance) so delta tests stay in integer arithmetic.
    """
    n = adj.shape[0]
    return _bfs_rows(adj.astype(np.float32), np.arange(n), sentinel if sentinel is not None else n)


def _nbr_table(adj: np.ndarray, kmax: int | None = None) -> np.ndarray:
    """Padded (n, kmax) neighbour table (pad -1) from a boolean adjacency."""
    n = adj.shape[0]
    deg = adj.sum(1)
    kmax = kmax or max(1, int(deg.max()))
    nbr = np.full((n, kmax), -1, dtype=np.int32)
    for u in range(n):
        ws = np.nonzero(adj[u])[0]
        nbr[u, : len(ws)] = ws
    return nbr


def _parent_counts(adj: np.ndarray, dist: np.ndarray, nbr: np.ndarray | None = None) -> np.ndarray:
    """npar[s, x] = number of BFS-DAG parents of x w.r.t. source s.

    A neighbour w of x is a parent when dist[s, w] + 1 == dist[s, x].  Used
    for the exact edge-removal test: deleting a set of edges changes
    distances from s iff some vertex loses *all* of its parent edges.
    ``dist`` may be row-restricted (shape (n_sources, n)); the counts are
    returned with the same shape.  Passing the maintained ``nbr`` table
    avoids rebuilding it (the counts come from a vectorized gather over it).
    """
    if nbr is None:
        nbr = _nbr_table(adj)
    valid = nbr >= 0
    nb = np.where(valid, nbr, 0)
    # chunk over source rows so the (rows, n, kmax) gather temp stays ~64 MB
    # regardless of n (at N=8192 the unchunked temp is 268 MB per call)
    out = np.empty(dist.shape, dtype=np.int16)
    step = max(1, (1 << 24) // max(1, dist.shape[1] * nbr.shape[1]))
    for lo in range(0, dist.shape[0], step):
        d = dist[lo : lo + step]
        out[lo : lo + step] = (((d[:, nb] + np.int32(1)) == d[:, :, None])
                               & valid[None, :, :]).sum(-1, dtype=np.int16)
    return out


def _removal_affected(dist: np.ndarray, npar: np.ndarray, removed) -> np.ndarray:
    """Boolean mask over the source rows of ``dist``: rows whose distances
    change when the ``removed`` edges are all deleted simultaneously.

    Exact batched test: per source, count how many removed edges are BFS-DAG
    parent edges of each endpoint vertex; the row is affected iff some vertex
    loses every parent it had (count == npar).  If an endpoint keeps a
    parent, every vertex keeps a parent (induction on hop distance) and all
    old distances stay achievable.  For vertex-disjoint removals this reduces
    to the classic sole-parent test (npar == 1).
    """
    aff = np.zeros(dist.shape[0], dtype=bool)
    lost: dict[int, np.ndarray] = {}
    for a, b in removed:
        da, db = dist[:, a], dist[:, b]
        pa_of_b = (da + 1 == db).astype(np.int16)
        pa_of_a = (db + 1 == da).astype(np.int16)
        lost[b] = pa_of_b if b not in lost else lost[b] + pa_of_b
        lost[a] = pa_of_a if a not in lost else lost[a] + pa_of_a
    for x, cnt in lost.items():
        aff |= (cnt > 0) & (cnt == npar[:, x])
    return aff


def _parent_count_cols(dist: np.ndarray, nbr: np.ndarray, cols) -> np.ndarray:
    """``_parent_counts`` restricted to the vertex columns ``cols``:
    (rows, len(cols)) int16 from an O(rows x len(cols) x kmax) gather, so
    callers that only probe a few columns (the removal test probes the
    removed edges' endpoints) need not maintain the full (rows, n) table."""
    cols = np.asarray(cols, dtype=np.int64)
    nb = nbr[cols]
    valid = nb >= 0
    nbx = np.where(valid, nb, 0)
    return (((dist[:, nbx] + np.int32(1)) == dist[:, cols][:, :, None])
            & valid[None, :, :]).sum(-1, dtype=np.int16)


def _removal_affected_nbr(dist: np.ndarray, nbr: np.ndarray, removed) -> np.ndarray:
    """``_removal_affected`` with the parent counts gathered on demand from
    the neighbour table instead of a maintained (rows, n) count table — the
    counts are only ever read at the removed edges' endpoint columns, so the
    host-side test of the device delta tier stays O(rows x endpoints x kmax)
    per proposal."""
    pts = sorted({x for e in removed for x in e})
    idx = {p: i for i, p in enumerate(pts)}
    npc = _parent_count_cols(dist, nbr, pts)
    aff = np.zeros(dist.shape[0], dtype=bool)
    lost: dict[int, np.ndarray] = {}
    for a, b in removed:
        da, db = dist[:, a], dist[:, b]
        pa_of_b = (da + 1 == db).astype(np.int16)
        pa_of_a = (db + 1 == da).astype(np.int16)
        lost[b] = pa_of_b if b not in lost else lost[b] + pa_of_b
        lost[a] = pa_of_a if a not in lost else lost[a] + pa_of_a
    for x, cnt in lost.items():
        aff |= (cnt > 0) & (cnt == npc[:, idx[x]])
    return aff


def _removal_columns(nbr: np.ndarray, removed, width: int):
    """The columns ``_removal_affected_nbr`` reads, for a compact gather.

    The test reads ``dist`` only at the removed edges' endpoints and at
    their neighbours.  Returns ``(cols, nbr_c, removed_c)``: ``cols``
    (width,) int32 lists the P endpoints, then each endpoint's kmax
    neighbour slots (a pad slot reads column 0), then zeros; ``nbr_c`` and
    ``removed_c`` are the endpoints' neighbour rows and the removed edges
    in positions of ``cols``.  So ``_removal_affected_nbr(dist[:, cols],
    nbr_c, removed_c)`` equals ``_removal_affected_nbr(dist, nbr,
    removed)``: the endpoints keep their order, and pads stay -1."""
    pts = sorted({x for e in removed for x in e})
    p, kmax = len(pts), nbr.shape[1]
    nb = nbr[pts]
    cols = np.zeros(width, dtype=np.int32)
    cols[:p] = pts
    cols[p:p * (1 + kmax)] = np.where(nb >= 0, nb, 0).ravel()
    nbr_c = np.where(nb >= 0, p + np.arange(p * kmax).reshape(p, kmax),
                     -1).astype(np.int32)
    idx = {x: i for i, x in enumerate(pts)}
    return cols, nbr_c, [(idx[a], idx[b]) for a, b in removed]


@dataclasses.dataclass
class SwapToken:
    """Pending result of ``IncrementalAPSP.evaluate_swap`` (commit to apply)."""

    removed: tuple[tuple[int, int], ...]
    added: tuple[tuple[int, int], ...]
    dist: np.ndarray  # full post-swap distance matrix (int32, sentinel = n)
    total: int
    diam: int
    mpl: float


class IncrementalAPSP:
    """Dense APSP state maintained under 2-edge swaps by delta evaluation.

    The evaluator keeps the current boolean adjacency, the int32 hop-distance
    matrix (sentinel ``n`` for unreachable) and the BFS-DAG parent-count
    matrix.  ``evaluate_swap`` prices a swap without mutating state:

    1. *Removals*: source ``s`` is affected by deleting edge (a, b) iff the
       edge is the sole DAG-parent edge of one endpoint (exact — if an
       endpoint keeps a parent, every vertex keeps a parent and all old
       distances stay achievable).  Distances are repaired by batched BFS
       from only the affected sources; unaffected rows (and, by symmetry,
       columns) are provably unchanged.
    2. *Additions*: the exact unweighted edge-insert formula
       ``d'(x, y) = min(d(x, y), d(x, u) + 1 + d(v, y), d(x, v) + 1 + d(u, y))``
       applied per added edge — vectorized O(n^2), no BFS.

    When the affected-source fraction exceeds ``full_rebuild_frac`` (or
    ``force_full`` is set) the evaluator falls back to a from-scratch batched
    BFS; ``n_delta`` / ``n_full`` count both paths for tests and benchmarks.

    A C kernel (``_fastpath``, compiled lazily when a system compiler
    exists) replaces the numpy BFS/patch math with queue-BFS at C speed;
    ``use_c=None`` auto-detects, ``use_c=False`` forces the numpy path.  The
    two paths are bit-identical (asserted by the property tests).

    Buffers may be caller-provided views (e.g. slices of a stacked replica
    tensor) — all updates are written in place.
    """

    def __init__(
        self,
        adj: np.ndarray,
        full_rebuild_frac: float = 0.9,
        force_full: bool = False,
        use_c: bool | None = None,
        dist_buf: np.ndarray | None = None,
        a32_buf: np.ndarray | None = None,
        npar_buf: np.ndarray | None = None,
    ):
        from . import _fastpath

        n = adj.shape[0]
        self.n = n
        self.sentinel = n
        self.full_rebuild_frac = full_rebuild_frac
        self.force_full = force_full
        # bool input is adopted as the live buffer (mutated in place — pass a
        # stacked-tensor slice to keep replicas in one array)
        self.adj = adj if adj.dtype == np.bool_ else adj.astype(bool)
        self.fast = None
        if use_c or use_c is None:
            lib = _fastpath.get_lib()
            if lib is not None:
                self.fast = _fastpath.FastEval(lib)
            elif use_c:
                raise RuntimeError("C fast path requested but unavailable")
        self.a32 = a32_buf if a32_buf is not None else np.empty((n, n), dtype=np.float32)
        self.a32[...] = self.adj
        # zero-init required: the C kernel epoch-stamps part of this buffer
        self._scratch = np.zeros(8 * n, dtype=np.int32)
        self._rem_buf = np.empty(4, dtype=np.int32)
        self._add_buf = np.empty(4, dtype=np.int32)
        self.nbr = self._build_nbr()
        self.dist = dist_buf if dist_buf is not None else np.empty((n, n), dtype=np.int32)
        self.npar = npar_buf if npar_buf is not None else np.empty((n, n), dtype=np.int16)
        if self.fast is not None:
            self.fast.apsp_rows(self.nbr, self.dist, self._scratch)
            self.fast.parent_counts(self.nbr, self.dist, self.npar)
        else:
            self.dist[...] = _bfs_rows(self.a32, np.arange(n), n)
            self.npar[...] = _parent_counts(self.adj, self.dist, self.nbr)
        self.total = int(self.dist.sum(dtype=np.int64))
        self.diam = int(self.dist.max())
        self.n_delta = 0
        self.n_full = 0

    def _build_nbr(self, kmax: int | None = None) -> np.ndarray:
        """Padded (n, kmax) neighbour table for the C kernel (pad -1)."""
        return _nbr_table(self.adj, kmax)

    def _refresh_nbr_rows(self, verts) -> None:
        for u in sorted(set(verts)):
            ws = np.nonzero(self.adj[u])[0]
            if len(ws) > self.nbr.shape[1]:
                self.nbr = self._build_nbr(kmax=int(self.adj.sum(1).max()))
                return
            self.nbr[u, :] = -1
            self.nbr[u, : len(ws)] = ws

    # -- public state ------------------------------------------------------
    @property
    def connected(self) -> bool:
        return self.diam < self.sentinel

    def mpl(self) -> float:
        if not self.connected:
            return float("inf")
        return self.total / (self.n * (self.n - 1))

    def diameter(self) -> float:
        return float(self.diam) if self.connected else float("inf")

    def as_float_dist(self) -> np.ndarray:
        """Distance matrix in the ``apsp`` convention (float, inf sentinel)."""
        out = self.dist.astype(float)
        out[self.dist >= self.sentinel] = np.inf
        return out

    # -- swap evaluation ---------------------------------------------------
    # (a32 is None on SymmetricAPSP's C path, which shares these helpers)
    def _apply_edges(self, removed, added) -> None:
        for u, v in removed:
            self.adj[u, v] = self.adj[v, u] = False
        for u, v in added:
            self.adj[u, v] = self.adj[v, u] = True
        if self.a32 is not None:
            for u, v in removed:
                self.a32[u, v] = self.a32[v, u] = 0.0
            for u, v in added:
                self.a32[u, v] = self.a32[v, u] = 1.0

    def _revert_edges(self, removed, added) -> None:
        for u, v in added:
            self.adj[u, v] = self.adj[v, u] = False
        for u, v in removed:
            self.adj[u, v] = self.adj[v, u] = True
        if self.a32 is not None:
            for u, v in added:
                self.a32[u, v] = self.a32[v, u] = 0.0
            for u, v in removed:
                self.a32[u, v] = self.a32[v, u] = 1.0

    def evaluate_swap(
        self,
        removed: list[tuple[int, int]],
        added: list[tuple[int, int]],
        want_diameter: bool = True,
    ) -> SwapToken:
        """Price the swap; returns a token (``commit`` applies it).

        Preconditions (asserted): removed edges exist and added edges do
        not.  The edge lists may be arbitrarily long and may share vertices
        (batched multi-edge changes — e.g. whole rotation orbits): the
        removal test counts lost parent edges per vertex exactly.  The
        2-out/2-in case takes the C fast path when compiled.  With
        ``want_diameter=False`` the C path may defer the diameter max-pass
        (token.diam == -1) — ``commit`` computes it lazily; hot loops that
        only need the MPL for accept/reject use this.
        """
        dist, n = self.dist, self.n
        assert all(self.adj[u, v] for u, v in removed)
        assert all(not self.adj[u, v] for u, v in added)

        # the C 2+2 fast path tests each removed edge independently (exact
        # only when they share no vertex); batched shapes take the numpy path
        if self.fast is not None and len(removed) == 2 and len(added) == 2 \
                and len({v for e in removed for v in e}) == 4:
            (self._rem_buf[0], self._rem_buf[1]), (self._rem_buf[2], self._rem_buf[3]) = removed
            (self._add_buf[0], self._add_buf[1]), (self._add_buf[2], self._add_buf[3]) = added
            new = np.empty((n, n), dtype=np.int32)
            # a disconnected base state invalidates the delta tests: force full
            force = self.force_full or not self.connected
            naff, total, diam = self.fast.eval_swap(
                self.nbr, dist, self.npar, self._rem_buf, self._add_buf,
                force, self.full_rebuild_frac, want_diameter, self.total,
                new, self._scratch)
            if naff < 0:
                self.n_full += 1
            else:
                self.n_delta += 1
            if diam == -1:
                mpl = total / (n * (n - 1))  # delta path proved connectivity
            else:
                mpl = total / (n * (n - 1)) if diam < self.sentinel else float("inf")
            return SwapToken(tuple(removed), tuple(added), new, total, diam, mpl)

        # exact removal-affected sources (batched lost-parent test); a
        # disconnected base forces the full path, matching the C branch so
        # the n_delta/n_full counters stay identical across kernels
        aff = _removal_affected(dist, self.npar, removed)
        n_aff = int(aff.sum())

        if self.force_full or not self.connected \
                or n_aff > self.full_rebuild_frac * n:
            self.n_full += 1
            self._apply_edges(removed, added)
            try:
                new = _bfs_rows(self.a32, np.arange(n), self.sentinel)
            finally:
                self._revert_edges(removed, added)
            return self._token(removed, added, new)

        self.n_delta += 1
        new = dist.copy()
        if n_aff:
            # repair on the graph minus removed edges (additions come after)
            for u, v in removed:
                self.a32[u, v] = self.a32[v, u] = 0.0
            try:
                rows = _bfs_rows(self.a32, np.nonzero(aff)[0], self.sentinel)
            finally:
                for u, v in removed:
                    self.a32[u, v] = self.a32[v, u] = 1.0
            new[aff, :] = rows
            new[:, aff] = rows.T
        for u, v in added:
            du = new[:, u]
            dv = new[:, v]
            via = np.minimum(du[:, None] + (dv[None, :] + np.int32(1)),
                             dv[:, None] + (du[None, :] + np.int32(1)))
            np.minimum(new, via, out=new)
        return self._token(removed, added, new)

    def _token(self, removed, added, new: np.ndarray) -> SwapToken:
        total = int(new.sum(dtype=np.int64))
        diam = int(new.max())
        mpl = total / (self.n * (self.n - 1)) if diam < self.sentinel else float("inf")
        return SwapToken(tuple(removed), tuple(added), new, total, diam, mpl)

    def commit(self, token: SwapToken) -> None:
        """Apply a previously evaluated swap to the maintained state."""
        self._apply_edges(token.removed, token.added)
        self.dist[...] = token.dist
        self.total = token.total
        self.diam = int(token.dist.max()) if token.diam < 0 else token.diam
        self._refresh_nbr_rows([x for e in (*token.removed, *token.added) for x in e])
        if self.fast is not None:
            self.fast.parent_counts(self.nbr, self.dist, self.npar)
        else:
            self.npar[...] = _parent_counts(self.adj, self.dist, self.nbr)

    def reset(self) -> None:
        """Re-derive all state from the (externally rewritten) adjacency."""
        self.a32[...] = self.adj
        self.nbr = self._build_nbr()
        if self.fast is not None:
            self.fast.apsp_rows(self.nbr, self.dist, self._scratch)
            self.fast.parent_counts(self.nbr, self.dist, self.npar)
        else:
            self.dist[...] = _bfs_rows(self.a32, np.arange(self.n), self.sentinel)
            self.npar[...] = _parent_counts(self.adj, self.dist, self.nbr)
        self.total = int(self.dist.sum(dtype=np.int64))
        self.diam = int(self.dist.max())

    def load_from(self, other: "IncrementalAPSP") -> None:
        """Copy another evaluator's state into this one (replica exchange)."""
        self.adj[...] = other.adj
        self.a32[...] = other.a32
        self.dist[...] = other.dist
        self.npar[...] = other.npar
        if self.nbr.shape == other.nbr.shape:
            self.nbr[...] = other.nbr
        else:
            self.nbr = other.nbr.copy()
        self.total = other.total
        self.diam = other.diam

    def verify(self) -> None:
        """Assert internal state equals a from-scratch recompute (tests)."""
        ref = apsp_hops(self.adj, self.sentinel)
        assert np.array_equal(self.dist, ref), "incremental dist diverged"
        assert self.total == int(ref.sum(dtype=np.int64))
        assert self.diam == int(ref.max())
        assert np.array_equal(self.npar, _parent_counts(self.adj, self.dist))


# --------------------------------------------------------------------------------
# Symmetry-aware incremental APSP (the orbit-level search engine's hot path)
# --------------------------------------------------------------------------------

class SymmetricAPSP:
    """Row-restricted incremental APSP for rotationally symmetric graphs.

    For a graph on ``n`` vertices invariant under rotation by ``shift``
    (``fold = n // shift`` symmetric copies), every distance follows from the
    rows of the ``shift`` representative sources ``0..shift-1``:

        d(x, y) = d(x mod shift, (y - (x - x mod shift)) mod n)

    so the evaluator maintains exactly those rows (int32, sentinel ``n``)
    plus their BFS-DAG parent counts, and prices *orbit-level* edge swaps —
    batched multi-edge removals and insertions whose edge sets are unions of
    rotation orbits, so the graph stays symmetric — by delta evaluation:

    1. removals: the exact batched lost-parent test (``_removal_affected``)
       selects the affected representative rows, which are repaired by BFS on
       the graph minus the removed orbits; unaffected rows are provably
       unchanged.
    2. insertions: a min-plus patch through the added-edge endpoints.  The
       post-removal graph is still symmetric, so the full rows of arbitrary
       endpoints are rotations of representative rows; a Floyd–Warshall
       closure over the <= 2 * n_added endpoints gives the exact new
       endpoint-to-endpoint distances, and one vectorized pass per
       representative row applies
       ``d'(r, y) = min(d(r, y), min_{p,q} d(r, p) + D(p, q) + d(q, y))``.

    ``total`` is the representative-row total: the full-matrix total is
    ``fold * total``, MPL = total / (shift * (n - 1)), and the row maxima
    realise the global diameter (every row is a rotation of a representative
    row).  ``n_delta`` / ``n_full`` count the two pricing paths.

    The BFS phases are priced by an interchangeable engine (all
    bit-identical, asserted by the property tests), selected by ``engine=``
    and resolved through the ``core.engines`` registry — the single place
    engine names are validated:

    - ``"c"`` — the ``_fastpath.eval_orbit_swap`` kernel: per-source queue
      BFS with cascade repair, compiled at first use.  Fastest when a system
      compiler exists.
    - ``"bitset"`` — word-packed frontier sweeps (``bitset_bfs_rows``):
      frontier/visited sets packed into uint64 words along the source
      dimension, advanced by word-parallel OR/AND-NOT gathers over the
      neighbour table.  This is the fast no-kernel path at N >= 8192 (and
      uses the C word-packed sweep for the BFS itself when the kernel
      happens to be available).
    - ``"pallas"`` — the same BFS as a Pallas TPU kernel
      (``kernels.bfs_sweep``, distance blocks in VMEM); interpret mode off
      a TPU.
    - ``"numpy"`` — the seed dense float32-matmul BFS (``_bfs_rows``); keeps
      an (n, n) float32 adjacency mirror, O(n^2) per BFS level.

    ``engine=None`` (or ``"auto"``) resolves to ``"c"`` when the kernel
    compiles and ``"bitset"`` otherwise (``REPRO_ENGINE`` overrides the
    auto choice); ``use_c`` is the legacy knob (``use_c=False`` forces
    ``"numpy"``, ``use_c=True`` requires ``"c"``) and is overridden by an
    explicit ``engine=``.
    """

    class _EngineNames:
        """Live view of the registered row-engine names (``engines.register``
        extends the registry after import, so a snapshot would go stale)."""

        def __get__(self, obj, objtype=None):
            return engines.ROWS_ENGINES

    ENGINES = _EngineNames()

    def __init__(
        self,
        adj: np.ndarray,
        shift: int,
        full_rebuild_frac: float = 0.9,
        force_full: bool = False,
        use_c: bool | None = None,
        engine: str | None = None,
    ):
        n = adj.shape[0]
        if shift < 1 or n % shift:
            raise ValueError(f"shift={shift} must be a positive divisor of n={n}")
        self.n = n
        self.s = shift
        self.fold = n // shift
        self.sentinel = n
        self.full_rebuild_frac = full_rebuild_frac
        self.force_full = force_full
        self.adj = adj if adj.dtype == np.bool_ else adj.astype(bool)
        if not np.array_equal(self.adj, np.roll(np.roll(self.adj, shift, 0), shift, 1)):
            raise ValueError(f"adjacency is not invariant under rotation by {shift}")
        # single validation/resolution point for engine names; the registry
        # probes the C toolchain only on paths that can use it (use_c=False /
        # engine="numpy" are explicit opt-outs and never trigger the
        # first-use compile attempt)
        eng = engines.resolve_rows(engine, use_c=use_c)
        self.engine = eng.name
        self._eng = eng
        # the orbit C kernel prices whole swaps without the generic numpy
        # delta logic below; every other engine plugs into it via rows_bfs
        self.fast = eng.fast_eval() if eng.has_orbit_kernel else None
        # the float32 adjacency mirror feeds only the dense-matmul BFS: for
        # the other engines it would be (n, n) of dead weight (256 MB at
        # N=8192), so it exists only when the engine asks for it
        self.a32 = None
        if eng.needs_dense_mirror:
            self.a32 = np.empty((n, n), dtype=np.float32)
            self.a32[...] = self.adj
        # zero-init required: the C kernel epoch-stamps part of this buffer
        self._scratch = np.zeros(8 * n, dtype=np.int32)
        self._work = np.empty(0, dtype=np.int32)
        self.nbr = self._build_nbr()
        self.dist = np.empty((shift, n), dtype=np.int32)
        self.npar = np.empty((shift, n), dtype=np.int16)
        if self.fast is not None:
            self.fast.apsp_rows(self.nbr, self.dist, self._scratch)
        else:
            self.dist[...] = self._rows_bfs(np.arange(shift))
        self._recount_parents()
        self.total = int(self.dist.sum(dtype=np.int64))
        self.diam = int(self.dist.max())
        self.n_delta = 0
        self.n_full = 0

    def _recount_parents(self) -> None:
        """Refresh ``npar`` from dist/nbr through the engine (C kernel when
        the engine has one — the numpy gather allocates an (s, n, k)
        temporary, heavy at N=8192)."""
        self._eng.parent_counts(self)

    def _rows_bfs(self, sources, removed=(), added=()) -> np.ndarray:
        """BFS rows from ``sources`` on the current graph with ``removed``
        edges deleted and ``added`` edges inserted (state reverted on exit),
        priced by the resolved engine's sweep."""
        touched = [x for e in (*removed, *added) for x in e] \
            if self._eng.uses_nbr else ()
        self._apply_edges(removed, added)
        if touched:
            self._refresh_nbr_rows(touched)
        try:
            return self._eng.rows_bfs(self, np.asarray(sources))
        finally:
            self._revert_edges(removed, added)
            if touched:
                self._refresh_nbr_rows(touched)

    _build_nbr = IncrementalAPSP._build_nbr
    _refresh_nbr_rows = IncrementalAPSP._refresh_nbr_rows
    _apply_edges = IncrementalAPSP._apply_edges
    _revert_edges = IncrementalAPSP._revert_edges

    # -- public state ------------------------------------------------------
    @property
    def connected(self) -> bool:
        return self.diam < self.sentinel

    def mpl(self) -> float:
        if not self.connected:
            return float("inf")
        return self.total / (self.s * (self.n - 1))

    def diameter(self) -> float:
        return float(self.diam) if self.connected else float("inf")

    # -- swap evaluation ---------------------------------------------------
    def _check_orbit_closed(self, edges, kind: str) -> None:
        n, s = self.n, self.s
        es = {(min(u, v), max(u, v)) for u, v in edges}
        for u, v in es:
            a, b = (u + s) % n, (v + s) % n
            if (min(a, b), max(a, b)) not in es:
                raise ValueError(
                    f"{kind} edge set is not closed under rotation by {s}: "
                    f"({u},{v}) rotates to ({a},{b})")

    def evaluate_swap(self, removed, added) -> SwapToken:
        """Price a batched orbit swap; returns a token (``commit`` applies it).

        ``removed`` / ``added`` are edge lists that must each be unions of
        rotation orbits (validated), with removed edges present and added
        edges absent.  Distances, total, diameter and MPL in the token are
        exact for the post-swap graph.
        """
        n, s = self.n, self.s
        self._check_orbit_closed(removed, "removed")
        self._check_orbit_closed(added, "added")
        assert all(self.adj[u, v] for u, v in removed)
        assert all(not self.adj[u, v] for u, v in added)

        # a disconnected base state invalidates the sentinel-coded parent
        # counts used by the delta tests: force the full rebuild (mirrors the
        # C kernel decision exactly so both paths stay bit-identical)
        force = self.force_full or not self.connected

        if self.fast is not None:
            new = np.empty((s, n), dtype=np.int32)
            nap = len({x for e in added for x in e})
            nrp = len({x for e in removed for x in e})
            need = nap * (n + nap + 2) + nrp
            if len(self._work) < need:
                self._work = np.empty(need, dtype=np.int32)
            naff, total, diam = self.fast.eval_orbit_swap(
                self.nbr, self.dist, self.npar, removed, added,
                force, self.full_rebuild_frac, new, self._scratch, self._work)
            if naff < 0:
                self.n_full += 1
            else:
                self.n_delta += 1
            mpl = total / (s * (n - 1)) if diam < self.sentinel else float("inf")
            return SwapToken(tuple(removed), tuple(added), new, total, diam, mpl)

        aff = _removal_affected(self.dist, self.npar, removed)
        n_aff = int(aff.sum())
        if force or n_aff > self.full_rebuild_frac * s:
            self.n_full += 1
            new = self._rows_bfs(np.arange(s), removed, added)
            return self._token(removed, added, new)

        self.n_delta += 1
        new = self.dist.copy()
        if n_aff:
            # repair on the graph minus removed orbits (still symmetric)
            new[aff, :] = self._rows_bfs(np.nonzero(aff)[0], removed)
        if added:
            self._insert_patch(new, added)
        return self._token(removed, added, new)

    def _insert_patch(self, new: np.ndarray, added) -> None:
        """Exact batched edge-insert patch on the representative rows.

        ``new`` holds the post-removal rows of a graph that is symmetric
        under rotation by ``self.s``; the full row of any added-edge endpoint
        is a rotation of a representative row, so the min-plus closure over
        the endpoints is computable without the other n - s rows.
        """
        n, s = self.n, self.s
        pts = sorted({x for e in added for x in e})
        m = len(pts)
        # rolled post-removal rows of the endpoints: crows[i, y] = d_rm(p_i, y)
        crows = np.empty((m, n), dtype=np.int32)
        for i, p in enumerate(pts):
            crows[i] = np.roll(new[p % s], p - p % s)
        # endpoint-to-endpoint closure with the added edges as weight-1 links
        w = crows[:, pts].copy()
        idx = {p: i for i, p in enumerate(pts)}
        for u, v in added:
            iu, iv = idx[u], idx[v]
            if w[iu, iv] > 1:
                w[iu, iv] = w[iv, iu] = 1
        for k in range(m):
            np.minimum(w, w[:, k : k + 1] + w[k : k + 1, :], out=w)
        # d'(r, y) = min(d_rm(r, y), min_q [min_p d_rm(r, p) + w(p, q)] + d_rm(q, y))
        a = new[:, pts]  # (s, m) — snapshot: broadcasting below reads `new`
        tmp = (a[:, :, None] + w[None, :, :]).min(axis=1)  # (s, m)
        for j in range(m):
            np.minimum(new, tmp[:, j : j + 1] + crows[j][None, :], out=new)

    def _token(self, removed, added, new: np.ndarray) -> SwapToken:
        total = int(new.sum(dtype=np.int64))
        diam = int(new.max())
        mpl = total / (self.s * (self.n - 1)) if diam < self.sentinel else float("inf")
        return SwapToken(tuple(removed), tuple(added), new, total, diam, mpl)

    def commit(self, token: SwapToken) -> None:
        """Apply a previously evaluated orbit swap to the maintained state."""
        self._apply_edges(token.removed, token.added)
        self.dist[...] = token.dist
        self.total = token.total
        self.diam = token.diam
        self._refresh_nbr_rows([x for e in (*token.removed, *token.added) for x in e])
        self._recount_parents()

    def verify(self) -> None:
        """Assert internal state equals a from-scratch recompute AND that the
        symmetry assumption actually holds for the full matrix (tests)."""
        assert np.array_equal(
            self.adj, np.roll(np.roll(self.adj, self.s, 0), self.s, 1)
        ), "adjacency lost its rotational symmetry"
        ref = apsp_hops(self.adj, self.sentinel)
        assert np.array_equal(self.dist, ref[: self.s]), "symmetric dist diverged"
        assert self.total == int(ref[: self.s].sum(dtype=np.int64))
        assert self.diam == int(ref[: self.s].max()) == int(ref.max())
        assert self.fold * self.total == int(ref.sum(dtype=np.int64))
        assert np.array_equal(self.npar, _parent_counts(self.adj, self.dist))


def mpl(g: Graph, dist: np.ndarray | None = None) -> float:
    """Mean path length over ordered distinct pairs (the paper's MPL)."""
    d = apsp(g) if dist is None else dist
    n = g.n
    off = ~np.eye(n, dtype=bool)
    vals = d[off]
    if not np.isfinite(vals).all():
        return float("inf")
    return float(vals.mean())


def eccentricities(g: Graph, dist: np.ndarray | None = None) -> np.ndarray:
    d = apsp(g) if dist is None else dist
    return d.max(axis=1)


def diameter(g: Graph, dist: np.ndarray | None = None) -> float:
    d = apsp(g) if dist is None else dist
    m = d.max()
    return float(m)


def girth(g: Graph) -> float:
    """Length of the shortest cycle (inf for forests). BFS from every vertex."""
    adj = g.adjacency_lists()
    best = np.inf
    for src in range(g.n):
        depth = [-1] * g.n
        parent = [-1] * g.n
        depth[src] = 0
        q = [src]
        while q:
            nq = []
            for u in q:
                for v in adj[u]:
                    if depth[v] == -1:
                        depth[v] = depth[u] + 1
                        parent[v] = u
                        nq.append(v)
                    elif v != parent[u]:
                        # cycle through src-ish: length bound
                        cyc = depth[u] + depth[v] + 1
                        if cyc < best:
                            best = cyc
            # early exit: any deeper layers can only give longer cycles
            if q and 2 * depth[q[0]] + 1 >= best:
                break
            q = nq
    return float(best)


# --------------------------------------------------------------------------------
# Bisection width
# --------------------------------------------------------------------------------

def _cut_size(adj: np.ndarray, mask: np.ndarray) -> int:
    return int(adj[np.ix_(mask, ~mask)].sum())


def bisection_width(
    g: Graph,
    exact_limit: int = 20,
    restarts: int = 24,
    seed: int = 0,
) -> int:
    """Minimum edge cut over balanced bipartitions (|A| = ceil(n/2)).

    Exact (exhaustive over subsets containing vertex 0) for n <= exact_limit;
    otherwise Kernighan–Lin refinement from spectral + random starts.  The
    heuristic returns an upper bound on the true BW; on the paper's structured
    graphs it reaches the published values (asserted in tests).
    """
    n = g.n
    adj = g.adjacency().astype(np.int64)
    half = n // 2
    if n <= exact_limit:
        import itertools

        best = np.inf
        others = list(range(1, n))
        for comb in itertools.combinations(others, half - 1):
            mask = np.zeros(n, dtype=bool)
            mask[0] = True
            mask[list(comb)] = True
            c = _cut_size(adj, mask)
            if c < best:
                best = c
        return int(best)

    rng = np.random.default_rng(seed)
    best = np.inf

    starts: list[np.ndarray] = []
    # spectral start: Fiedler vector median split
    try:
        deg = np.diag(adj.sum(1))
        lap = deg - adj
        w, v = np.linalg.eigh(lap)
        fied = v[:, 1]
        order = np.argsort(fied)
        mask = np.zeros(n, dtype=bool)
        mask[order[:half]] = True
        starts.append(mask)
    except np.linalg.LinAlgError:  # pragma: no cover
        pass
    for _ in range(restarts):
        perm = rng.permutation(n)
        mask = np.zeros(n, dtype=bool)
        mask[perm[:half]] = True
        starts.append(mask)

    for mask in starts:
        mask = _kernighan_lin(adj, mask.copy())
        c = _cut_size(adj, mask)
        if c < best:
            best = c
    return int(best)


def _kernighan_lin(adj: np.ndarray, mask: np.ndarray, max_passes: int = 12) -> np.ndarray:
    """Classic KL pass-based refinement of a balanced bipartition."""
    n = adj.shape[0]
    for _ in range(max_passes):
        # D[v] = external(v) - internal(v)
        ext = adj @ (~mask) if True else None
        a_side = np.where(mask)[0]
        b_side = np.where(~mask)[0]
        # gains for swapping pairs; do greedy sequence with locking
        locked = np.zeros(n, dtype=bool)
        cur = mask.copy()
        seq: list[tuple[int, int, int]] = []
        total = 0
        ext = adj @ (~cur).astype(np.int64)
        innr = adj @ cur.astype(np.int64)
        D = np.where(cur, ext - innr, innr - ext)  # benefit of moving v across
        for _step in range(min(len(a_side), len(b_side))):
            acand = [v for v in a_side if not locked[v]]
            bcand = [v for v in b_side if not locked[v]]
            if not acand or not bcand:
                break
            # best pair by D[a] + D[b] - 2 adj[a,b]; search top few by D to stay fast
            acand = sorted(acand, key=lambda v: -D[v])[:8]
            bcand = sorted(bcand, key=lambda v: -D[v])[:8]
            bg, ba, bb = -np.inf, -1, -1
            for va in acand:
                for vb in bcand:
                    gain = D[va] + D[vb] - 2 * adj[va, vb]
                    if gain > bg:
                        bg, ba, bb = gain, va, vb
            seq.append((int(bg), ba, bb))
            total += bg
            locked[ba] = locked[bb] = True
            # update D for unlocked vertices as if swapped
            for v in range(n):
                if locked[v]:
                    continue
                if cur[v]:  # same side as ba
                    D[v] += 2 * adj[v, ba] - 2 * adj[v, bb]
                else:
                    D[v] += 2 * adj[v, bb] - 2 * adj[v, ba]
        # find best prefix
        run, best_run, best_idx = 0, 0, -1
        for i, (gain, _, _) in enumerate(seq):
            run += gain
            if run > best_run:
                best_run, best_idx = run, i
        if best_run <= 0:
            break
        for i in range(best_idx + 1):
            _, va, vb = seq[i]
            mask[va] = False
            mask[vb] = True
    return mask


# --------------------------------------------------------------------------------
# Cerf et al. lower bounds (generalized Moore bounds)
# --------------------------------------------------------------------------------

def moore_bound_vertices(k: int, d: int) -> int:
    """Max vertices within distance d of any vertex in a k-regular graph."""
    if d == 0:
        return 1
    total = 1
    shell = k
    for _ in range(1, d + 1):
        total += shell
        shell *= k - 1
    return total


def diameter_lower_bound(n: int, k: int) -> int:
    d = 0
    while moore_bound_vertices(k, d) < n:
        d += 1
    return d


def mpl_lower_bound(n: int, k: int) -> float:
    """Cerf et al. (1974) lower bound on MPL of an (n,k) regular graph.

    From any root, at most k(k-1)^(i-1) vertices can sit at distance i; pack
    the other n-1 vertices greedily into the nearest shells.
    """
    remaining = n - 1
    i = 1
    shell = k
    ssum = 0.0
    while remaining > 0:
        take = min(shell, remaining)
        ssum += i * take
        remaining -= take
        shell *= k - 1
        i += 1
    return ssum / (n - 1)


def edge_betweenness_proxy(g: Graph, dist: np.ndarray | None = None) -> dict[tuple[int, int], float]:
    """Cheap congestion proxy: number of shortest-path pairs through each edge
    under single-shortest-path (lowest-next-hop) static routing.  The exact
    link loads for a given routing table live in routing.py; this proxy is
    routing-independent and used only for reporting."""
    from . import routing

    table = routing.RoutingTable.build(g)
    return table.link_loads()


# --------------------------------------------------------------------------------

class GraphStats:
    __slots__ = ("name", "n", "k", "diameter", "mpl", "bw", "girth", "d_lb", "mpl_lb")

    def __init__(self, name, n, k, diameter, mpl, bw, girth, d_lb, mpl_lb):
        self.name, self.n, self.k = name, n, k
        self.diameter, self.mpl, self.bw, self.girth = diameter, mpl, bw, girth
        self.d_lb, self.mpl_lb = d_lb, mpl_lb

    def row(self) -> str:
        return (
            f"{self.name:>24s}  N={self.n:<4d} k={self.k:<3d} D={self.diameter:<4.0f} "
            f"MPL={self.mpl:<7.4f} BW={self.bw:<4d} girth={self.girth:<3.0f} "
            f"D_lb={self.d_lb} MPL_lb={self.mpl_lb:.4f}"
        )


def stats(g: Graph, bw_restarts: int = 24, seed: int = 0) -> GraphStats:
    d = apsp(g)
    # irregular graphs (e.g. cluster-hub compositions) report max degree;
    # the lower bounds below stay valid since they are monotone in k
    k = g.degree() if g.is_regular() else int(g.degrees().max())
    return GraphStats(
        name=g.name,
        n=g.n,
        k=k,
        diameter=diameter(g, d),
        mpl=mpl(g, d),
        bw=bisection_width(g, restarts=bw_restarts, seed=seed),
        girth=girth(g),
        d_lb=diameter_lower_bound(g.n, k),
        mpl_lb=mpl_lower_bound(g.n, k),
    )
