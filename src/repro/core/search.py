"""Topology discovery: the paper's Algorithm 1 (SA + edge swap) and the
symmetry-restricted searches.

Three search tiers, matching Section 3.1 of the paper:

1. ``exhaustive_search`` — tiny (N,k): enumerate ring+chord graphs (optionally
   girth-constrained) and keep the min-MPL one.  Stands in for
   snarkhunter/genreg, whose role is exactness on small instances.
2. ``sa_search`` — the paper's Algorithm 1: simulated annealing over
   non-ring edge swaps of a random Hamiltonian regular graph, exponential
   cooling ``gamma = exp(log(T_end/T_start)/n_iter)``.  Rebuilt as a
   **parallel-replica engine with incremental MPL evaluation**: R
   independent annealing replicas (stacked state, per-replica PRNG streams,
   periodic best-replica exchange into the worst chain; replica 0 is a
   protected reference chain, so best-of-R is never worse than a
   single-replica run at the same seed) price every 2-edge swap through
   ``metrics.IncrementalAPSP`` — BFS repair only from sources whose
   shortest-path DAG actually broke, exact O(n^2) patching for inserted
   edges, full recompute only as a guarded fallback.
3. ``circulant_search`` / ``symmetric_sa_search`` — the rotational-symmetry
   restricted walks used for the large graphs (252/256/264 and now up to
   16384 vertices): circulant offset-set hillclimb priced by an implicit
   np.roll BFS (no graph materialisation per candidate; a jitted JAX batch
   sweep prices whole candidate batches at n >= 4096), plus orbit-level SA
   that can warm-start from the best circulant (``large_search``).  The
   orbit SA prices each orbit swap through ``metrics.SymmetricAPSP`` —
   batched multi-edge delta updates from only the n/fold representative
   sources — instead of a dense BFS per proposal, with the pricing backend
   resolved through the pluggable ``core.engines`` registry (C queue BFS,
   word-packed bitset sweep at N >= 8192, the Pallas VMEM device sweep, or
   the dense matmul baseline).  ``large_search(replicas=R)`` adds the
   device-sharded replica polish: lockstep chains priced in one
   ``shard_map`` dispatch per iteration.

Every function takes an explicit ``seed`` and is bit-reproducible (the
optional C kernel and the pure-python fallback consume identical pre-drawn
random streams, so they follow the same trajectory).  ``find_optimal`` is
the paper-facing driver that picks the tier by size and returns the best
graph found within budget, together with the Cerf bounds so callers can
report the optimality gap.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
from collections.abc import Iterable

import numpy as np

from .. import obs
from . import engines, metrics
from .graphs import Graph, circulant, from_edges, random_hamiltonian_regular, ring

__all__ = [
    "SearchResult",
    "sa_search",
    "exhaustive_search",
    "circulant_search",
    "symmetric_sa_search",
    "large_search",
    "find_optimal",
    "sa_objective_search",
    "KNOWN_OPTIMAL_MPL",
]

# Published MPL values for optimal graphs (paper TABLE 1/2) — used as search
# targets and test ground truth.
KNOWN_OPTIMAL_MPL = {
    (16, 3): 2.20,
    (16, 4): 1.75,
    (32, 3): 2.94,
    (32, 4): 2.35,
    (20, 4): 1.95,
    (30, 5): 1.97,
    (36, 5): 2.14,
}


@dataclasses.dataclass
class SearchResult:
    graph: Graph
    mpl: float
    diameter: float
    mpl_lb: float
    d_lb: int
    iterations: int
    accepted: int
    history: list[float]  # best-so-far MPL trace (sparse)
    replicas: int = 1
    evals_delta: int = 0  # incremental evaluations (delta path)
    evals_full: int = 0  # full-recompute fallbacks
    device_dispatches: int = 0  # shard_map pricing dispatches (device tiers)
    offsets: tuple[int, ...] | None = None  # circulant offsets, if applicable
    compound_steps: int = 0  # multi-orbit proposals priced (moves_per_step > 1)
    objective_value: float | None = None  # non-MPL objective score (e.g.
    # synthesized collective-schedule seconds for objective="collective-time")

    @property
    def mpl_gap(self) -> float:
        return self.mpl - self.mpl_lb

    @property
    def d_gap(self) -> float:
        return self.diameter - self.d_lb


def _mpl_fast(adj: np.ndarray, n_sources: int | None = None) -> tuple[float, float]:
    """(MPL, diameter) from a boolean adjacency matrix via frontier BFS.

    Uses float32 matmuls (BLAS) for the frontier expansion.  If ``n_sources``
    is given, BFS runs only from vertices ``0..n_sources-1`` — valid for
    graphs whose automorphism group acts with those vertices as orbit
    representatives (e.g. rotationally symmetric graphs with period
    ``n_sources``); MPL/diameter over those rows equal the global values.
    """
    n = adj.shape[0]
    s = n_sources or n
    a32 = adj.astype(np.float32)
    reach = np.zeros((s, n), dtype=bool)
    reach[np.arange(s), np.arange(s)] = True
    frontier = reach.astype(np.float32)
    total = 0.0
    d = 0
    while True:
        nxt = (frontier @ a32) > 0
        frontier_b = nxt & ~reach
        if not frontier_b.any():
            break
        d += 1
        total += d * frontier_b.sum()
        reach |= frontier_b
        frontier = frontier_b.astype(np.float32)
    if not reach.all():
        return float("inf"), float("inf")
    return total / (s * (n - 1)), float(d)


def _graph_mpl_d(g: Graph) -> tuple[float, float]:
    return _mpl_fast(g.adjacency())


# --------------------------------------------------------------------------------
# Tier 1: exhaustive (tiny graphs)
# --------------------------------------------------------------------------------

def exhaustive_search(
    n: int,
    k: int,
    girth_min: int = 3,
    limit: int = 2_000_000,
) -> SearchResult:
    """Exhaustive search over ring + chord-set graphs for tiny (n, k).

    We enumerate Hamiltonian k-regular graphs (ring + (k-2)-regular chord
    graph).  For k=3 the chords are a perfect matching — tractable up to
    n≈16.  A ``girth_min`` constraint prunes, mirroring the paper's use of
    girth to cut the (32,3) space from 1e13 to 1e5.
    """
    if k != 3:
        raise NotImplementedError("exhaustive tier implemented for k=3 (matching chords)")
    ring_edges = [(i, (i + 1) % n) for i in range(n)]
    base = from_edges(n, ring_edges)
    best: tuple[float, float, Graph] | None = None
    count = 0

    verts = list(range(n))

    def matchings(avail: list[int]):
        if not avail:
            yield []
            return
        u = avail[0]
        for j in range(1, len(avail)):
            v = avail[j]
            if (v - u) % n in (1, n - 1):
                continue  # ring edge
            rest = avail[1:j] + avail[j + 1 :]
            for m in matchings(rest):
                yield [(u, v)] + m

    for chords in matchings(verts):
        count += 1
        if count > limit:
            break
        g = from_edges(n, ring_edges + chords, f"({n},{k})-cand")
        if girth_min > 3 and metrics.girth(g) < girth_min:
            continue
        mp, dia = _graph_mpl_d(g)
        if best is None or (mp, dia) < (best[0], best[1]):
            best = (mp, dia, g.with_name(f"({n},{k})-Optimal"))
    assert best is not None
    mp, dia, g = best
    return SearchResult(
        graph=g,
        mpl=mp,
        diameter=dia,
        mpl_lb=metrics.mpl_lower_bound(n, k),
        d_lb=metrics.diameter_lower_bound(n, k),
        iterations=count,
        accepted=count,
        history=[mp],
    )


# --------------------------------------------------------------------------------
# Tier 2: the paper's Algorithm 1 — SA with edge swap
# --------------------------------------------------------------------------------

def _edge_swap(adj: np.ndarray, ring_mask: np.ndarray, rng: np.random.Generator):
    """Propose a 2-edge swap on non-ring edges, in place on a copy.

    Pick edges (a,b), (c,d) not on the ring, replace with (a,c),(b,d) or
    (a,d),(b,c) — preserves degrees.  Returns the new adjacency or None if the
    proposal is invalid (duplicate/self edge).
    """
    n = adj.shape[0]
    iu, ju = np.where(np.triu(adj & ~ring_mask))
    if len(iu) < 2:
        return None
    e1, e2 = rng.choice(len(iu), size=2, replace=False)
    a, b = int(iu[e1]), int(ju[e1])
    c, d = int(iu[e2]), int(ju[e2])
    if len({a, b, c, d}) != 4:
        return None
    if rng.integers(2):
        p1, p2 = (a, c), (b, d)
    else:
        p1, p2 = (a, d), (b, c)
    if adj[p1] or adj[p2]:
        return None
    out = adj.copy()
    out[a, b] = out[b, a] = False
    out[c, d] = out[d, c] = False
    out[p1] = out[p1[::-1]] = True
    out[p2] = out[p2[::-1]] = True
    return out


class _Replica:
    """One annealing chain: incremental-APSP state + chord list + best."""

    __slots__ = ("ev", "chords", "best_adj", "cur_total", "cur_diam",
                 "best_total", "best_diam", "t", "rng",
                 "hist_iters", "hist_totals", "hist_io", "stats", "newdist")

    def __init__(self, adj: np.ndarray, ring_mask: np.ndarray,
                 t_start: float, rng: np.random.Generator, n_iter: int):
        n = adj.shape[0]
        self.ev = metrics.IncrementalAPSP(adj)
        self.chords = _chord_array(adj, ring_mask)
        self.best_adj = adj.copy()
        self.cur_total = self.best_total = self.ev.total
        self.cur_diam = self.best_diam = self.ev.diam
        self.t = t_start
        self.rng = rng
        cap = max(n_iter, 1)
        self.hist_iters = np.empty(cap, dtype=np.int32)
        self.hist_totals = np.empty(cap, dtype=np.int64)
        self.hist_io = np.asarray([cap, 0], dtype=np.int32)
        self.stats = np.zeros(4, dtype=np.int64)  # accepted, delta, full, invalid
        self.newdist = np.empty((n, n), dtype=np.int32)

    def load_best_of(self, other: "_Replica", ring_mask: np.ndarray) -> None:
        """Replica exchange: adopt another chain's best state as current."""
        self.ev.adj[...] = other.best_adj
        self.ev.reset()
        self.chords = _chord_array(self.ev.adj, ring_mask)
        self.cur_total, self.cur_diam = self.ev.total, self.ev.diam


def _chord_array(adj: np.ndarray, ring_mask: np.ndarray) -> np.ndarray:
    iu, ju = np.nonzero(np.triu(adj & ~ring_mask))
    return np.ascontiguousarray(np.stack([iu, ju], axis=1).astype(np.int32))


def _sa_chunk_py(rep: _Replica, n: int, de1, de2, dorient, du,
                 gamma: float, full_frac: float, target_total: int,
                 iter_base: int, norm: float) -> int:
    """Pure-python mirror of the C ``sa_chunk`` (identical trajectory)."""
    ev = rep.ev
    done = 0
    for i in range(len(de1)):
        rep.t *= gamma
        done = i + 1
        e1, e2 = int(de1[i]), int(de2[i])
        if e1 == e2:
            rep.stats[3] += 1
            continue
        a, b = int(rep.chords[e1, 0]), int(rep.chords[e1, 1])
        c, d = int(rep.chords[e2, 0]), int(rep.chords[e2, 1])
        if a == c or a == d or b == c or b == d:
            rep.stats[3] += 1
            continue
        p1, p2 = ((a, c), (b, d)) if dorient[i] else ((a, d), (b, c))
        if ev.adj[p1] or ev.adj[p2]:
            rep.stats[3] += 1
            continue
        tok = ev.evaluate_swap([(a, b), (c, d)], [p1, p2], want_diameter=False)
        if tok.diam >= n:  # disconnected: dm = +inf, always rejected
            continue
        dm = (tok.total - rep.cur_total) / norm
        if not dm < 0.0:
            if not du[i] < math.exp(-dm / max(rep.t, 1e-12)):
                continue
        ev.commit(tok)
        rep.chords[e1] = p1
        rep.chords[e2] = p2
        rep.cur_total, rep.cur_diam = tok.total, ev.diam
        rep.stats[0] += 1
        if (rep.cur_total, rep.cur_diam) < (rep.best_total, rep.best_diam):
            rep.best_total, rep.best_diam = rep.cur_total, rep.cur_diam
            rep.best_adj[...] = ev.adj
            cnt = int(rep.hist_io[1])
            if cnt < int(rep.hist_io[0]):
                rep.hist_iters[cnt] = iter_base + i
                rep.hist_totals[cnt] = rep.cur_total
                rep.hist_io[1] = cnt + 1
            if 0 <= target_total and rep.best_total <= target_total:
                break
    return done


def _run_chunk(rep: _Replica, n: int, chunk: int, iter_base: int,
               gamma: float, full_frac: float, target_total: int,
               norm: float) -> int:
    """Draw this chunk's randomness from the replica stream and execute it
    (C kernel when compiled, python mirror otherwise — same trajectory)."""
    m_c = max(len(rep.chords), 1)
    ints = rep.rng.integers(0, [m_c, m_c, 2], size=(chunk, 3))
    de1 = np.ascontiguousarray(ints[:, 0], dtype=np.int32)
    de2 = np.ascontiguousarray(ints[:, 1], dtype=np.int32)
    dorient = np.ascontiguousarray(ints[:, 2], dtype=np.int32)
    du = rep.rng.random(chunk)
    if len(rep.chords) < 2:
        return chunk  # no swappable chords (k == 2): pure cooling
    ev = rep.ev
    if ev.fast is not None:
        out = ev.fast.sa_chunk(
            nbr=ev.nbr, dist=ev.dist, npar=None, adj=ev.adj,
            best_adj=rep.best_adj, chords=rep.chords,
            chunk_iters=chunk, iter_base=iter_base,
            de1=de1, de2=de2, dorient=dorient, du=du,
            t=rep.t, gamma=gamma, full_frac=full_frac,
            cur_total=rep.cur_total, cur_diam=rep.cur_diam,
            best_total=rep.best_total, best_diam=rep.best_diam,
            target_total=target_total,
            hist_iters=rep.hist_iters, hist_totals=rep.hist_totals,
            hist_io=rep.hist_io, newdist=rep.newdist,
            scratch=ev._scratch, stats=rep.stats)
        rep.t = out["t"]
        rep.cur_total, rep.cur_diam = out["cur_total"], out["cur_diam"]
        rep.best_total, rep.best_diam = out["best_total"], out["best_diam"]
        ev.a32[...] = ev.adj  # keep the numpy-path mirror coherent
        return out["done"]
    return _sa_chunk_py(rep, n, de1, de2, dorient, du, gamma, full_frac,
                        target_total, iter_base, norm)


def sa_search(
    n: int,
    k: int,
    seed: int = 0,
    n_iter: int = 4000,
    t_start: float = 0.1,
    t_end: float = 1e-4,
    target_mpl: float | None = None,
    start: Graph | None = None,
    replicas: int = 1,
    exchange_every: int = 400,
    full_rebuild_frac: float = 0.9,
) -> SearchResult:
    """Paper Algorithm 1, rebuilt: parallel-replica SA with incremental MPL.

    ``replicas`` independent chains anneal under the shared schedule, each on
    its own PRNG stream (``[seed, r]``); every ``exchange_every`` iterations
    the globally best state replaces the worst chain.  Replica 0 is never
    overwritten, so its trajectory is bit-identical to a ``replicas=1`` run
    with the same seed — best-of-R can only improve on it.

    Engine selection: swap pricing is ``metrics.IncrementalAPSP`` delta
    evaluation.  The C ``sa_chunk`` kernel runs the whole annealing inner
    loop when a system compiler exists; otherwise the pure-python mirror
    consumes the identical pre-drawn random streams, so both paths follow
    the same trajectory per seed (``REPRO_NO_C_KERNEL=1`` forces the
    fallback).  This tier keeps the dense (n, n) distance state — the
    word-packed bitset engine applies to the symmetry-restricted tiers
    (``symmetric_sa_search``/``large_search``), whose row-restricted state
    is what scales to N >= 8192.
    """
    ring_mask = ring(n).adjacency()
    gamma = math.exp(math.log(t_end / t_start) / n_iter) if n_iter else 1.0
    norm = n * (n - 1)
    lb = metrics.mpl_lower_bound(n, k)
    tgt = target_mpl if target_mpl is not None else lb
    target_total = math.floor((tgt + 1e-9) * norm + 1e-9)

    reps: list[_Replica] = []
    for r in range(replicas):
        # a generous retry cap: some (n, k, seed) streams need >500 pairing
        # draws (e.g. (30,5) seed [0,1]); extra tries only consume the stream
        # after the old cap would have errored, so existing trajectories are
        # untouched
        g0 = start or random_hamiltonian_regular(n, k, seed=[seed, r],
                                                 max_tries=20000)
        reps.append(_Replica(g0.adjacency(), ring_mask, t_start,
                             np.random.default_rng([seed, r]), n_iter))

    done = 0
    hit = min(rep.best_total for rep in reps) <= target_total
    while done < n_iter and not hit:
        chunk = min(exchange_every, n_iter - done)
        for rep in reps:
            _run_chunk(rep, n, chunk, done, gamma, full_rebuild_frac,
                       target_total, norm)
            if rep.best_total <= target_total:
                hit = True
                break
        done += chunk
        if hit or done >= n_iter:
            break
        if replicas > 1:
            gb = min(range(replicas),
                     key=lambda r: (reps[r].best_total, reps[r].best_diam, r))
            worst = max(range(1, replicas),
                        key=lambda r: (reps[r].cur_total, reps[r].cur_diam, -r))
            if (reps[gb].best_total, reps[gb].best_diam) < \
                    (reps[worst].cur_total, reps[worst].cur_diam):
                reps[worst].load_best_of(reps[gb], ring_mask)

    gb = min(range(replicas), key=lambda r: (reps[r].best_total, reps[r].best_diam, r))
    best = reps[gb]
    iu, ju = np.where(np.triu(best.best_adj))
    g = from_edges(n, zip(iu.tolist(), ju.tolist()), f"({n},{k})-Optimal-SA")

    # merged best-so-far trace across replicas (running global minimum)
    events = sorted(
        (int(it), int(tot))
        for rep in reps
        for it, tot in zip(rep.hist_iters[: int(rep.hist_io[1])],
                           rep.hist_totals[: int(rep.hist_io[1])])
    )
    history = []
    running = float("inf")
    for _, tot in events:
        if tot < running:
            running = tot
            history.append(tot / norm)

    return SearchResult(
        graph=g,
        mpl=best.best_total / norm,
        diameter=float(best.best_diam),
        mpl_lb=lb,
        d_lb=metrics.diameter_lower_bound(n, k),
        iterations=n_iter,
        accepted=int(sum(int(rep.stats[0]) for rep in reps)),
        history=history or [best.best_total / norm],
        replicas=replicas,
        evals_delta=int(sum(int(rep.stats[1]) + rep.ev.n_delta for rep in reps)),
        evals_full=int(sum(int(rep.stats[2]) + rep.ev.n_full for rep in reps)),
    )


def sa_objective_search(
    n: int,
    k: int,
    objective,
    seed: int = 0,
    n_iter: int = 4000,
    t_start: float = 0.1,
    t_end: float = 1e-4,
    start: Graph | None = None,
) -> Graph:
    """SA over edge swaps minimizing an arbitrary ``objective(Graph) -> float``.

    Used for reconstructions (e.g. pinning a graph that matches published
    invariants) and for the beyond-paper layout optimization.
    """
    rng = np.random.default_rng(seed)
    g0 = start or random_hamiltonian_regular(n, k, seed=seed)
    adj = g0.adjacency()
    ring_mask = ring(n).adjacency()
    gamma = math.exp(math.log(t_end / t_start) / n_iter)

    def to_graph(a):
        iu, ju = np.where(np.triu(a))
        return from_edges(n, zip(iu.tolist(), ju.tolist()), f"({n},{k})-obj")

    cur = objective(to_graph(adj))
    best_adj, best = adj.copy(), cur
    t = t_start
    for _ in range(n_iter):
        prop = _edge_swap(adj, ring_mask, rng)
        t *= gamma
        if prop is None:
            continue
        val = objective(to_graph(prop))
        dv = val - cur
        if dv < 0 or rng.random() < math.exp(-dv / max(t, 1e-12)):
            adj, cur = prop, val
            if cur < best:
                best_adj, best = adj.copy(), cur
                if best <= 0:
                    break
    return to_graph(best_adj)


# --------------------------------------------------------------------------------
# Tier 3: rotational-symmetry (circulant) search for large graphs
# --------------------------------------------------------------------------------

def _circulant_profile(n: int, offsets) -> tuple[float, float]:
    """(MPL, diameter) of C_n(offsets) via implicit np.roll BFS from vertex 0.

    Vertex-transitivity means one BFS gives the global MPL/diameter; working
    on the offset list directly (no Graph/edge-list materialisation) makes a
    candidate evaluation O(D * k * n) vector ops — thousands of candidates
    per second at n = 1024.
    """
    shifts = sorted({s % n for s in offsets} - {0})
    shifts = list({sh for s in shifts for sh in (s, n - s)})
    reach = np.zeros(n, dtype=bool)
    reach[0] = True
    frontier = reach.copy()
    total = 0
    count = 1
    d = 0
    while count < n:
        nxt = np.zeros(n, dtype=bool)
        for s in shifts:
            nxt |= np.roll(frontier, s)
        newf = nxt & ~reach
        c = int(newf.sum())
        if c == 0:
            return float("inf"), float("inf")
        d += 1
        total += d * c
        count += c
        reach |= newf
        frontier = newf
    return total / (n - 1), float(d)


# --- JAX batched circulant pricing -------------------------------------------
# The jitted batched twin of ``_circulant_profile`` lives in
# ``engines.jax_circulant`` (registry name "jax"); ``_profile_batch`` below
# is the thin dispatch the hillclimb consumes — values are bit-identical to
# the sequential pricer, so the trajectory never depends on the engine.


def _profile_batch(n: int, offset_lists, engine: str) -> "Iterable[tuple[float, float]]":
    return engines.jax_circulant.profile_batch(
        n, offset_lists, engine, _circulant_profile)


def circulant_search(
    n: int,
    k: int,
    seed: int = 0,
    n_iter: int = 300,
    include_ring: bool = True,
    engine: str = "auto",
) -> SearchResult:
    """Random-restart hillclimb over circulant offset sets.

    Circulants are Hamiltonian (offset 1 in the set) with full rotational
    symmetry — the subspace the paper searches for 252/256/264-vertex graphs.
    Candidates are priced by ``_circulant_profile`` (implicit BFS on the
    offset list, no graph construction), so 512/1024-vertex searches finish
    in seconds.

    ``engine`` selects the candidate pricer (resolved and validated by the
    ``core.engines`` registry): ``"numpy"`` prices candidates one at a
    time; ``"jax"`` batches each position sweep through a jitted packed
    frontier sweep (``engines.jax_circulant``) — the accelerator path for
    N >= 8192 offset batches.  ``"auto"`` picks ``"jax"`` when jax imports
    and n >= 4096, ``"numpy"`` otherwise.  The pricers return identical
    values and candidates are accepted in the same order, so the trajectory
    (and the result) is bit-identical across engines at a given seed.
    """
    engine = engines.resolve_circulant(engine, n)
    rng = np.random.default_rng(seed)
    half = k // 2
    has_anti = k % 2 == 1  # odd degree needs the antipodal offset n/2
    if has_anti and n % 2:
        raise ValueError("odd k needs even n")

    def full_offsets(offsets) -> list[int]:
        offs = ([1] if include_ring else []) + sorted(offsets)
        if has_anti:
            offs = offs + [n // 2]
        return offs

    def mpl_of(offsets) -> tuple[float, float]:
        offs = full_offsets(offsets)
        if len(set(offs)) != len(offs):
            return float("inf"), float("inf")
        return _circulant_profile(n, offs)

    with obs.span("repro.hillclimb"):
        n_free = half - (1 if include_ring else 0)
        lo, hi = 2, n // 2 - (1 if has_anti else 0)
        pool = list(range(lo, hi))
        if n_free > len(pool):
            raise ValueError(f"degree {k} too large for circulant on {n} vertices")
        best_offs: list[int] | None = None
        best = (float("inf"), float("inf"))
        history: list[float] = []
        it = 0
        # values the accept loop consumed, and rows the pricer priced for them
        examined = priced_rows = 0
        restarts = max(1, n_iter // 50)
        for _ in range(restarts):
            with obs.span("repro.hillclimb.start"):
                offs = sorted(rng.choice(pool, size=n_free, replace=False).tolist()) if n_free else []
                cur = mpl_of(offs)
            improved = True
            while improved and it < n_iter:
                improved = False
                for pos in range(len(offs)):
                    # exhaustive sweep of the position when affordable, else a
                    # random subsample (the paper's large-space regime)
                    with obs.span("repro.hillclimb.propose"):
                        cands = pool if len(pool) * len(offs) <= n_iter else \
                            rng.permutation(pool)[: min(32, len(pool))]
                        cands = [int(c) for c in cands]
                    # price the unexamined tail against the current offsets in
                    # one batch; an acceptance mid-sweep restarts the tail
                    # against the new base — exactly the sequential semantics,
                    # so numpy and jax pricing follow the same trajectory
                    i = 0
                    while i < len(cands):
                        tail = cands[i:]
                        # one eligibility pass drives both the batch and its
                        # consumption, so the vals iterator cannot desync:
                        # trials[j] is None for skipped candidates (already in
                        # offs, or duplicate full offsets — inf, never accepted)
                        with obs.span("repro.hillclimb.propose"):
                            trials = []
                            for c in tail:
                                t = None if c in offs else \
                                    sorted(offs[:pos] + [c] + offs[pos + 1 :])
                                if t is not None:
                                    fo = full_offsets(t)
                                    if len(set(fo)) != len(fo):
                                        t = None
                                trials.append(t)
                            vals = iter(_profile_batch(
                                n, [full_offsets(t) for t in trials if t is not None],
                                engine))
                        adv = len(tail)
                        used = 0
                        for j, trial in enumerate(trials):
                            it += 1
                            if trial is None:
                                continue
                            val = next(vals)
                            used += 1
                            if val < cur:
                                offs, cur = trial, val
                                improved = True
                                adv = j + 1
                                break
                        i += adv
                        examined += used
                        priced_rows += engines.jax_circulant.rows_priced(
                            engine, used)
                if cur < best:
                    best, best_offs = cur, list(offs)
                    history.append(best[0])
            if cur < best:
                best, best_offs = cur, list(offs)
                history.append(best[0])
        obs.mark("repro.hillclimb.tally", examined=examined,
                 priced_rows=priced_rows)
        with obs.span("repro.hillclimb.finish"):
            offs = full_offsets(best_offs or [])
            g = circulant(n, offs, f"({n},{k})-Suboptimal")
        return SearchResult(
            graph=g,
            mpl=best[0],
            diameter=best[1],
            mpl_lb=metrics.mpl_lower_bound(n, k),
            d_lb=metrics.diameter_lower_bound(n, k),
            iterations=it,
            accepted=it,
            history=history,
            offsets=tuple(offs),
        )


# --------------------------------------------------------------------------------
# Tier 3b: rotationally-symmetric SA (the paper's large-scale method)
# --------------------------------------------------------------------------------

def _orbit(n: int, s: int, u: int, v: int) -> frozenset[tuple[int, int]]:
    """Edge orbit of (u,v) under rotation by s (n/s-fold symmetry)."""
    out = set()
    t = 0
    while t < n:
        a, b = (u + t) % n, (v + t) % n
        out.add((min(a, b), max(a, b)))
        t += s
    return frozenset(out)


# compound-move gate: moves_per_step > 1 arms multi-orbit proposals once the
# single-move accept rate over a _COMPOUND_WINDOW-proposal window drops
# below _COMPOUND_RATE (the near-convergence collapse the ROADMAP names)
_COMPOUND_WINDOW = 50
_COMPOUND_RATE = 0.05


def _draw_orbit_swap(rng, work_list, work_chords, ring_edges, n, s, fold):
    """Draw one 2-orbit swap against ``(work_list, work_chords)``.

    Returns ``(i1, i2, no1, no2, new_edges, remaining)`` or None for an
    invalid draw.  Consumes the PRNG exactly like the classic inline
    single-move proposal, so the ``moves_per_step=1`` trajectory is
    bit-identical to the historical one.
    """
    i1, i2 = rng.choice(len(work_list), size=2, replace=False)
    o1, o2 = work_list[i1], work_list[i2]
    (u1, v1) = next(iter(o1))
    (u2, v2) = next(iter(o2))
    # orbit-level swap with a random relative rotation of the second orbit
    tshift = int(rng.integers(fold)) * s
    if rng.integers(2):
        na, nb = (u1, (v2 + tshift) % n), ((u2 + tshift) % n, v1)
    else:
        na, nb = (u1, (u2 + tshift) % n), (v1, (v2 + tshift) % n)
    if na[0] == na[1] or nb[0] == nb[1]:
        return None
    no1, no2 = _orbit(n, s, *na), _orbit(n, s, *nb)
    # orbit sizes must be conserved so degrees are conserved
    if len(no1) + len(no2) != len(o1) + len(o2):
        return None
    remaining = work_chords - set(o1) - set(o2)
    new_edges = set(no1) | set(no2)
    if len(new_edges) != len(no1) + len(no2):
        return None
    if new_edges & (remaining | ring_edges):
        return None
    return int(i1), int(i2), no1, no2, new_edges, remaining


def _symmetric_random_start(
    n: int, k: int, s: int, rng: np.random.Generator, max_tries: int = 4000
) -> set[frozenset[tuple[int, int]]] | None:
    """Random set of chord orbits making ring+chords k-regular, symmetric
    under rotation by s.  Returns the set of orbits or None."""
    fold = n // s
    for _ in range(max_tries):
        deg = np.full(n, 2)  # ring
        orbits: set[frozenset[tuple[int, int]]] = set()
        used: set[tuple[int, int]] = {(i, (i + 1) % n) for i in range(n - 1)} | {(0, n - 1)}
        fail = False
        guard = 0
        while (deg < k).any():
            guard += 1
            if guard > 50 * n:
                fail = True
                break
            us = np.where(deg < k)[0]
            u = int(rng.choice(us))
            v = int(rng.integers(n))
            if v == u:
                continue
            orb = _orbit(n, s, u, v)
            if any(e in used for e in orb):
                continue
            # degree increment per vertex from this orbit
            dd = np.zeros(n, dtype=np.int64)
            for a, b in orb:
                dd[a] += 1
                dd[b] += 1
            if ((deg + dd) > k).any():
                continue
            orbits.add(orb)
            used |= set(orb)
            deg += dd
        if not fail and (deg == k).all():
            return orbits
    return None


def _circulant_orbits(n: int, s: int, offsets) -> set[frozenset[tuple[int, int]]]:
    """Chord-edge orbits (under rotation by s) of circulant C_n(offsets).

    Excludes the ring offset 1 — a circulant is invariant under every
    rotation, so its chords decompose into orbits of the coarser rotation-by-s
    subgroup, giving ``symmetric_sa_search`` a warm start.
    """
    orbits: set[frozenset[tuple[int, int]]] = set()
    for o in sorted({x % n for x in offsets} - {0}):
        if o in (1, n - 1):
            continue
        for u in range(s):
            orbits.add(_orbit(n, s, u, (u + o) % n))
    return orbits


def symmetric_sa_search(
    n: int,
    k: int,
    seed: int = 0,
    n_iter: int = 3000,
    fold: int = 4,
    t_start: float = 0.05,
    t_end: float = 1e-4,
    target_mpl: float | None = None,
    start_orbits: set[frozenset[tuple[int, int]]] | None = None,
    start_offsets: tuple[int, ...] | None = None,
    incremental: bool = True,
    engine: str | None = None,
    moves_per_step: int = 1,
) -> SearchResult:
    """SA over *orbit-level* edge swaps of graphs with ``fold``-fold
    rotational symmetry (paper: 'random iteration of Hamiltonian graphs with
    rotational symmetry', used for the 252/256/264-vertex graphs).

    The graph stays invariant under rotation by s = n/fold throughout, so the
    search space shrinks by ~fold× and every accepted design is symmetric —
    the paper's engineering-feasibility requirement.  ``start_offsets`` (a
    circulant offset list, e.g. from ``known_optimal.KNOWN_CIRCULANT_OFFSETS``)
    warm-starts the walk from that circulant's chord orbits; ``start_orbits``
    passes an explicit orbit set instead (mutually exclusive).

    With ``incremental=True`` (the default) proposals are priced by
    ``metrics.SymmetricAPSP`` — distances delta-updated from only the
    ``n/fold`` representative sources, batched over the whole orbit swap —
    which is what makes the N >= 2048 polish tier run in seconds.
    ``incremental=False`` keeps the seed dense-BFS pricing
    (``_mpl_fast`` from ``s`` sources per proposal); both paths consume the
    PRNG identically and the evaluator is exact, so the two trajectories are
    bit-identical per seed (asserted in tests and measured by the
    ``polish_*`` rows of ``benchmarks/bench_search.py``).

    ``engine`` picks the ``SymmetricAPSP`` backend (only meaningful with
    ``incremental=True``): ``"c"`` queue-BFS kernel, ``"bitset"``
    word-packed frontier sweeps (the fast no-compiler path, sized for
    N >= 8192), ``"pallas"`` the same BFS as a Pallas TPU kernel
    (interpret mode off a TPU), ``"numpy"`` dense matmul BFS, or
    ``None``/``"auto"`` — C kernel when it compiles, bitset otherwise.
    All engines are bit-identical, so ``engine`` never changes the result —
    only the wall time (see docs/ARCHITECTURE.md for the selection matrix).

    ``moves_per_step > 1`` arms compound proposals: once the single-move
    accept rate collapses near convergence (below ``_COMPOUND_RATE`` over a
    ``_COMPOUND_WINDOW``-proposal window), each step samples up to
    ``moves_per_step`` 2-orbit swaps against a working copy of the orbit
    set and prices the merged multi-orbit change in one batched
    ``evaluate_swap`` — escaping the local basins single swaps cannot.
    The default (1) leaves the classic trajectory untouched (asserted by
    the trajectory tests); compound steps consume extra PRNG draws only
    after the rate gate opens, so runs remain bit-reproducible per seed.
    """
    # the registry is the single validation point — check engine= even when
    # incremental=False (where it is unused), so a typo'd engine= never
    # silently runs the dense pricer
    engines.check_engine(engine)
    if moves_per_step < 1:
        raise ValueError(f"moves_per_step={moves_per_step} must be >= 1")
    fold_i = int(fold)
    if fold_i != fold or fold_i < 1 or n % fold_i:
        raise ValueError(
            f"fold={fold!r} must be a positive integer divisor of n={n}: a "
            "non-divisor fold would make the rotation orbits irregular")
    fold = fold_i
    s = n // fold
    if start_offsets is not None:
        if start_orbits is not None:
            raise ValueError("pass either start_orbits or start_offsets, not both")
        start_orbits = _circulant_orbits(n, s, start_offsets)
    rng = np.random.default_rng(seed)
    orbits = set(start_orbits) if start_orbits is not None else \
        _symmetric_random_start(n, k, s, rng)
    if orbits is None:
        raise RuntimeError(f"no symmetric start found for ({n},{k}) fold={fold}")
    ring_edges = {(i, (i + 1) % n) for i in range(n - 1)} | {(0, n - 1)}

    def adj_of(orbs) -> np.ndarray:
        a = np.zeros((n, n), dtype=bool)
        for i, j in ring_edges:
            a[i, j] = a[j, i] = True
        for orb in orbs:
            for i, j in orb:
                a[i, j] = a[j, i] = True
        return a

    gamma = math.exp(math.log(t_end / t_start) / n_iter)
    adj = adj_of(orbits)
    ev = metrics.SymmetricAPSP(adj, shift=s, engine=engine) if incremental else None
    if ev is not None:
        cur_mpl, cur_d = ev.mpl(), ev.diameter()
    else:
        cur_mpl, cur_d = _mpl_fast(adj, n_sources=s)
    best_orbits, best_mpl, best_d = set(orbits), cur_mpl, cur_d
    lb = metrics.mpl_lower_bound(n, k)
    tgt = target_mpl if target_mpl is not None else lb
    t = t_start
    accepted = 0
    history = [best_mpl]
    orb_list = list(orbits)
    # incremental chord-edge set (excludes ring edges)
    chord_edges: set[tuple[int, int]] = set()
    for orb in orb_list:
        chord_edges |= set(orb)

    win_n = win_acc = 0
    compound_on = False
    compound_steps = 0
    for _ in range(n_iter):
        t *= gamma
        if len(orb_list) < 2:
            break
        # draw up to nmoves 2-orbit swaps against a working copy of the
        # orbit state; nmoves == 1 reproduces the classic proposal exactly
        nmoves = moves_per_step if compound_on else 1
        work_list, work_chords = orb_list, chord_edges
        got = 0
        for _m in range(nmoves):
            if len(work_list) < 2:
                break
            mv = _draw_orbit_swap(rng, work_list, work_chords, ring_edges,
                                  n, s, fold)
            if mv is None:
                continue
            i1, i2, no1, no2, new_edges, remaining = mv
            work_list = [o for idx, o in enumerate(work_list)
                         if idx not in (i1, i2)] + [no1, no2]
            work_chords = remaining | new_edges
            got += 1
        if got == 0:
            continue
        if got > 1:
            compound_steps += 1
        # edges in both states are removed-then-re-added: cancel them (set
        # differences of orbit-closed sets stay orbit-closed)
        removed = sorted(chord_edges - work_chords)
        added = sorted(work_chords - chord_edges)
        if ev is not None:
            tok = ev.evaluate_swap(removed, added)
            new_mpl = tok.mpl
            new_d = float(tok.diam) if tok.diam < n else float("inf")
        else:
            # mutate adjacency in place on a copy restricted to changed entries
            a2 = adj.copy()
            for i, j in removed:
                a2[i, j] = a2[j, i] = False
            for i, j in added:
                a2[i, j] = a2[j, i] = True
            new_mpl, new_d = _mpl_fast(a2, n_sources=s)
        win_n += 1
        dm = new_mpl - cur_mpl
        if dm < 0 or rng.random() < math.exp(-dm / max(t, 1e-12)):
            orb_list, cur_mpl, cur_d = work_list, new_mpl, new_d
            chord_edges = work_chords
            if ev is not None:
                ev.commit(tok)
            else:
                adj = a2
            accepted += 1
            win_acc += 1
            if (cur_mpl, cur_d) < (best_mpl, best_d):
                best_orbits, best_mpl, best_d = set(orb_list), cur_mpl, cur_d
                history.append(best_mpl)
                if best_mpl <= tgt + 1e-9:
                    break
        if moves_per_step > 1 and win_n >= _COMPOUND_WINDOW:
            # the gate is adaptive both ways: compound moves arm when the
            # single-move accept rate collapses and disarm if it recovers
            compound_on = win_acc < _COMPOUND_RATE * win_n
            win_n = win_acc = 0

    edges = set(ring_edges)
    for orb in best_orbits:
        edges |= set(orb)
    g = from_edges(n, edges, f"({n},{k})-Suboptimal")
    return SearchResult(
        graph=g,
        mpl=best_mpl,
        diameter=best_d,
        mpl_lb=lb,
        d_lb=metrics.diameter_lower_bound(n, k),
        iterations=n_iter,
        accepted=accepted,
        history=history,
        evals_delta=ev.n_delta if ev is not None else 0,
        evals_full=ev.n_full if ev is not None else 0,
        compound_steps=compound_steps,
    )


# --------------------------------------------------------------------------------
# Tier 3c: device-sharded replica polish (shard_map over the replica axis)
# --------------------------------------------------------------------------------

def _nbr_of_edges(n: int, edges) -> np.ndarray:
    """Padded (n, kmax) neighbour table (pad -1, kmax the largest degree)
    of the graph on n vertices with the undirected ``edges``: each row's
    neighbours ascending, exactly ``metrics._nbr_table`` of its adjacency,
    built without one."""
    e = np.fromiter(itertools.chain.from_iterable(edges), dtype=np.int64)
    a, b = e[0::2], e[1::2]
    # both directions as one sorted, deduplicated key: row-major (u, v)
    u, v = np.divmod(np.unique(np.concatenate([a * n + b, b * n + a])), n)
    deg = np.bincount(u, minlength=n)
    nbr = np.full((n, max(1, int(deg.max()))), -1, dtype=np.int32)
    nbr[u, np.arange(len(u)) - np.repeat(np.cumsum(deg) - deg, deg)] = v
    return nbr


class _PolishChain:
    """One replica of the device-priced orbit polish: host-side orbit state
    plus the padded neighbour table the device sweep prices from.  Its
    representative-row distance state and best snapshot are row r of the
    polish's two (R, s, n) device arrays, not fields here.  No field is
    written in place: a move replaces ``orb_list``, ``chord_edges`` and
    ``nbr``, so chains may start from shared ones."""

    __slots__ = ("rng", "orb_list", "chord_edges", "nbr",
                 "cur_mpl", "cur_d", "best_orbits", "best_mpl", "best_d", "t")

    def __init__(self, rng, orb_list, chord_edges, nbr, t_start):
        self.rng = rng
        self.orb_list = orb_list
        self.chord_edges = chord_edges
        self.nbr = nbr
        self.t = t_start
        self.cur_mpl = self.cur_d = float("inf")
        self.best_orbits = set(self.orb_list)
        self.best_mpl = self.best_d = float("inf")

    def trial_nbr(self, removed, added) -> np.ndarray:
        """Neighbour table of the proposal graph: the rows of the touched
        endpoints rebuilt from their current rows (degrees are conserved by
        the orbit-size check, so kmax never grows)."""
        out = self.nbr.copy()
        gone = set(removed) | {(v, u) for u, v in removed}
        for u in sorted({x for e in (*removed, *added) for x in e}):
            ws = sorted([w for w in out[u].tolist() if w >= 0 and (u, w) not in gone]
                        + [b if a == u else a for a, b in added if u in (a, b)])
            out[u, :] = -1
            out[u, : len(ws)] = ws
        return out

    def commit(self, work_list, work_chords, nbr, mpl, d):
        self.nbr = nbr
        self.orb_list, self.chord_edges = work_list, work_chords
        self.cur_mpl, self.cur_d = mpl, d


def _resync_check(base, nbrs: np.ndarray, n: int, use_pallas: bool) -> None:
    """Drift guard for the delta-priced polish: re-sweep every chain's
    current graph (``nbrs``, (R, n, kmax)) from scratch in one dispatch and
    assert the maintained incremental distance state ``base`` ((R, s, n))
    matches bit-for-bit.  The comparison runs where the state lives (a host
    state is uploaded); one flag per replica comes back.  Raises
    ``AssertionError`` on any divergence."""
    from .engines import pallas_sweep

    r, s, _ = base.shape
    with obs.span("repro.polish.resync"):
        _, _, state = pallas_sweep.sharded_delta_state(
            base, nbrs.astype(np.int32, copy=False), [np.arange(s)] * r,
            [None] * r, n, use_pallas=use_pallas)
        for r, same in enumerate(pallas_sweep.states_equal(state, base)):
            if not same:
                raise AssertionError(
                    f"delta pricing drift: replica {r} incremental distance "
                    f"state diverged from the full re-sweep")


def _replica_polish(
    n: int,
    k: int,
    seed: int,
    n_iter: int,
    fold: int,
    start_orbits,
    engine: str | None,
    replicas: int,
    exchange_every: int = 50,
    t_start: float = 0.05,
    t_end: float = 1e-4,
    proposal_batch: int = 1,
    resync_every: int = 64,
    full_rebuild_frac: float = 0.9,
) -> SearchResult:
    """Parallel-replica orbit polish with device-batched pricing.

    ``replicas`` lockstep annealing chains share the circulant warm start,
    each on its own PRNG stream (``[seed, r]``, replica 0 protected — the
    ``sa_search`` exchange semantics).  Every iteration each chain draws
    ``proposal_batch`` orbit swaps; all R*M proposals are then priced in
    **one** device dispatch — a ``shard_map`` over the replica mesh axis, so
    each device prices its replicas' proposals locally (the Pallas kernels
    when the resolved engine is the device sweep, their jnp twins otherwise)
    and only per-proposal (total, max) scalars come home.

    The dispatch is the incremental-APSP twin ``sharded_delta_state``:
    each chain's representative-row distances stay on the device.  Once
    all proposals are drawn, one gather pulls the columns the lost-parent
    test reads (each removed endpoint and its neighbours) from each
    proposal's chain state; the test, run on the host on those compact
    blocks, marks the rows a removal touches, and the device re-sweeps
    only those rows on the post-removal graph before min-plus patching the
    added edges back in — the ``SymmetricAPSP`` algorithm, vectorized over
    proposals.  An accepted proposal's post-swap rows are selected on the
    device; no whole state crosses to the host.  Proposals whose affected
    set exceeds ``full_rebuild_frac`` of the rows (or whose base is
    disconnected) fall back to a full re-sweep expressed in the same
    vocabulary.  Every ``resync_every`` iterations (and at the end) a full
    re-sweep asserts the incremental state has not drifted.  Pricing is
    exact integer hop counts, so every accept decision is the one a
    from-scratch BFS of the proposal graph would give.

    Each chain holds its graph as its orbit list, its chord-edge set and
    one (n, kmax) neighbour table; the chains start from the warm start's
    shared ones.  A proposal's table rebuilds only the rows of the swapped
    edges' endpoints, and the exchange rebuilds a whole table from the
    best chain's orbits.

    Batched proposals are accepted greedily in lockstep order: once a
    chain accepts, the rest of its batch was priced against a stale base
    and is discarded (no RNG is consumed for discarded proposals), so
    ``proposal_batch=1`` reproduces the unbatched trajectory exactly.

    Every ``exchange_every`` iterations the globally best state replaces the
    worst non-protected chain, exactly like ``sa_search``.

    The chains' current rows and best snapshots are
    two (R, s, n) device arrays split over the replica mesh, each chain's
    rows on the device that prices its proposals.  After each dispatch one
    program (``pallas_sweep.place_states``) takes every chain's accepted
    post-swap rows into both, on the device where they were priced; the
    exchange's copy of one chain's best rows into another chain is the
    only state that crosses between devices, and only where the two
    chains live on different ones.

    Under a profiler the call records ``repro.polish.place`` spans (the
    placement program and the exchange's copy) and the
    ``repro.polish.tally`` mark: the column gathers (``column_pulls``)
    with the bytes they pulled (``column_bytes``), the placement calls
    (``place_calls``, one per ``place`` span), and the chain states copied
    between devices (``state_moves``) with their bytes
    (``state_move_bytes``).
    """
    from .engines import pallas_sweep

    if proposal_batch < 1:
        raise ValueError(f"proposal_batch must be >= 1, got {proposal_batch}")
    with obs.span("repro.polish", iterations=n_iter):
        use_pallas = engines.resolve_rows(engine).device_sweep
        with obs.span("repro.polish.setup"):
            s = n // fold
            gamma = math.exp(math.log(t_end / t_start) / n_iter)
            ring_edges = {(i, (i + 1) % n) for i in range(n - 1)} | {(0, n - 1)}

            def nbr_of(chords) -> np.ndarray:
                return _nbr_of_edges(n, itertools.chain(ring_edges, chords))

            # all chains start from the warm start's orbits, chords and
            # table, shared: a move replaces them, never writes them
            start = sorted(start_orbits, key=sorted)
            chords0 = {e for orb in start for e in orb}
            nbr0 = nbr_of(chords0)
            chains = [_PolishChain(np.random.default_rng([seed, r]), start,
                                   chords0, nbr0, t_start)
                      for r in range(replicas)]
            norm = s * (n - 1)
            dispatches = 1
            # the warm start is priced once on each replica device (the same
            # pricing, so the same rows); it seeds cur/best
            nd = pallas_sweep.replica_shards(replicas)
            tot0, mx0, st0 = pallas_sweep.sharded_delta_state(
                np.zeros((nd, s, n), dtype=np.int32), np.stack([nbr0] * nd),
                [np.arange(s)] * nd, [None] * nd, n, use_pallas=use_pallas)
            base = best_rows = pallas_sweep.spread_states(st0, replicas)
            pallas_sweep.prepare_states(base, proposal_batch)
            mpl0 = tot0[0] / norm if mx0[0] < n else float("inf")
            d0 = float(mx0[0]) if mx0[0] < n else float("inf")
            for ch in chains:
                ch.cur_mpl = ch.best_mpl = mpl0
                ch.cur_d = ch.best_d = d0

            mprop = proposal_batch
            bsz = replicas * mprop
            accepted = 0
            evals_delta = evals_full = 0
            history = [mpl0]
            global_best = (mpl0, d0)
            nbr_stack = np.empty((bsz,) + nbr0.shape, dtype=np.int32)
            empty = np.empty(0, dtype=np.int64)
            # the lost-parent test's columns: two swapped orbits remove at
            # most 2 * fold edges, so 4 * fold endpoints, each with kmax
            # neighbours; one gather shape per configuration
            cols = np.zeros((bsz, 4 * fold * (1 + nbr0.shape[1])),
                            dtype=np.int32)
            column_pulls = column_bytes = 0
            place_calls = state_moves = state_move_bytes = 0
        for it in range(n_iter):
            proposals: list = [None] * bsz
            srcs: list = [empty] * bsz
            patches: list = [None] * bsz
            with obs.span("repro.polish.propose"):
                drawn = []  # proposals, tested once their columns are in
                for r, ch in enumerate(chains):
                    ch.t *= gamma
                    for m in range(mprop):
                        slot = r * mprop + m
                        nbr_stack[slot] = ch.nbr  # idle slots price the unchanged graph
                        if len(ch.orb_list) < 2:
                            continue
                        mv = _draw_orbit_swap(ch.rng, ch.orb_list, ch.chord_edges,
                                              ring_edges, n, s, fold)
                        if mv is None:
                            continue
                        i1, i2, no1, no2, new_edges, remaining = mv
                        work_list = [o for idx, o in enumerate(ch.orb_list)
                                     if idx not in (i1, i2)] + [no1, no2]
                        work_chords = remaining | new_edges
                        removed = sorted(ch.chord_edges - work_chords)
                        added = sorted(work_chords - ch.chord_edges)
                        drawn.append((slot, ch, removed, added, work_list,
                                      work_chords))
                if drawn:
                    with obs.span("repro.polish.columns"):
                        cols[:] = 0  # idle slots gather column 0, unread
                        compact = {}
                        for slot, ch, removed, *_ in drawn:
                            cols[slot], nbr_c, removed_c = metrics._removal_columns(
                                ch.nbr, removed, cols.shape[1])
                            compact[slot] = (nbr_c, removed_c)
                        block = pallas_sweep.state_columns(base, cols)
                        column_pulls += 1
                        column_bytes += block.nbytes
                for slot, ch, removed, added, work_list, work_chords in drawn:
                    aff = metrics._removal_affected_nbr(block[slot], *compact[slot])
                    full = (ch.cur_d == float("inf")
                            or int(aff.sum()) > full_rebuild_frac * s)
                    if full:
                        nbr_stack[slot] = ch.trial_nbr(removed, added)
                        srcs[slot] = np.arange(s)
                        evals_full += 1
                    else:
                        # re-sweep only the affected rows on the post-removal
                        # graph; the added edges come back as a min-plus patch
                        nbr_stack[slot] = ch.trial_nbr(removed, ())
                        srcs[slot] = np.nonzero(aff)[0]
                        patches[slot] = added
                        evals_delta += 1
                    proposals[slot] = (removed, added, work_list, work_chords)
            if drawn:
                totals, maxima, states = pallas_sweep.sharded_delta_state(
                    base, nbr_stack, srcs, patches, n, use_pallas=use_pallas)
                dispatches += 1
                take = np.full(replicas, -1, dtype=np.int32)
                better = np.zeros(replicas, dtype=bool)
                with obs.span("repro.polish.accept"):
                    for r, ch in enumerate(chains):
                        committed = False
                        for m in range(mprop):
                            slot = r * mprop + m
                            if proposals[slot] is None or committed:
                                continue  # discarded batch slots consume no RNG
                            new_mpl = (totals[slot] / norm if maxima[slot] < n
                                       else float("inf"))
                            new_d = (float(maxima[slot]) if maxima[slot] < n
                                     else float("inf"))
                            dm = new_mpl - ch.cur_mpl
                            if not (dm < 0
                                    or ch.rng.random() < math.exp(-dm / max(ch.t, 1e-12))):
                                continue
                            removed, added, work_list, work_chords = proposals[slot]
                            ch.commit(work_list, work_chords,
                                      ch.trial_nbr(removed, added), new_mpl, new_d)
                            take[r] = m
                            committed = True
                            accepted += 1
                            if (ch.cur_mpl, ch.cur_d) < (ch.best_mpl, ch.best_d):
                                ch.best_orbits = set(ch.orb_list)
                                ch.best_mpl, ch.best_d = ch.cur_mpl, ch.cur_d
                                better[r] = True
                                if (ch.best_mpl, ch.best_d) < global_best:
                                    global_best = (ch.best_mpl, ch.best_d)
                                    history.append(ch.best_mpl)
                if (take >= 0).any():
                    with obs.span("repro.polish.place"):
                        base, best_rows = pallas_sweep.place_states(
                            base, best_rows, states, take, better)
                    place_calls += 1
                if replicas > 1 and (it + 1) % exchange_every == 0 and it + 1 < n_iter:
                    with obs.span("repro.polish.exchange"):
                        gb = min(range(replicas),
                                 key=lambda r: (chains[r].best_mpl, chains[r].best_d, r))
                        worst = max(range(1, replicas),
                                    key=lambda r: (chains[r].cur_mpl, chains[r].cur_d, -r))
                        if (chains[gb].best_mpl, chains[gb].best_d) < \
                                (chains[worst].cur_mpl, chains[worst].cur_d):
                            ch = chains[worst]
                            ch.orb_list = sorted(chains[gb].best_orbits, key=sorted)
                            ch.chord_edges = {e for orb in ch.orb_list for e in orb}
                            ch.nbr = nbr_of(ch.chord_edges)
                            ch.cur_mpl, ch.cur_d = chains[gb].best_mpl, chains[gb].best_d
                            with obs.span("repro.polish.place"):
                                base, moved = pallas_sweep.copy_state(
                                    base, best_rows, gb, worst)
                            place_calls += 1
                            state_moves += moved > 0
                            state_move_bytes += moved
            if it + 1 == n_iter or (resync_every and (it + 1) % resync_every == 0):
                _resync_check(base, np.stack([ch.nbr for ch in chains]), n,
                              use_pallas)
                dispatches += 1

        obs.mark("repro.polish.tally", column_pulls=column_pulls,
                 column_bytes=column_bytes, place_calls=place_calls,
                 state_moves=state_moves, state_move_bytes=state_move_bytes)
        with obs.span("repro.polish.finish"):
            gb = min(range(replicas),
                     key=lambda r: (chains[r].best_mpl, chains[r].best_d, r))
            best = chains[gb]
            edges = set(ring_edges)
            for orb in best.best_orbits:
                edges |= set(orb)
            g = from_edges(n, edges, f"({n},{k})-Suboptimal")
        return SearchResult(
            graph=g,
            mpl=best.best_mpl,
            diameter=best.best_d,
            mpl_lb=metrics.mpl_lower_bound(n, k),
            d_lb=metrics.diameter_lower_bound(n, k),
            iterations=n_iter,
            accepted=accepted,
            history=history,
            replicas=replicas,
            evals_delta=evals_delta,
            evals_full=evals_full,
            device_dispatches=dispatches,
        )


# --------------------------------------------------------------------------------
# Drivers
# --------------------------------------------------------------------------------

def large_search(
    n: int,
    k: int,
    seed: int = 0,
    budget: int | None = None,
    fold: int = 4,
    polish: bool = True,
    engine: str | None = None,
    replicas: int = 1,
    exchange_every: int = 50,
    proposal_batch: int = 1,
    resync_every: int = 64,
    polish_iters: int | None = None,
) -> SearchResult:
    """Large-N tier: fast circulant hillclimb, then orbit-level SA polish
    warm-started from the best circulant (when ``fold`` divides ``n``).

    Returns whichever of the two stages found the lower (MPL, diameter).
    A pinned offset set in ``known_optimal.KNOWN_CIRCULANT_OFFSETS`` skips
    the hillclimb entirely (seed 0 reproduces the pinning run).  With
    ``replicas=1`` (default) the polish stage prices orbit swaps through
    ``metrics.SymmetricAPSP`` (delta updates from the n/fold representative
    sources), which keeps it practical up to N=16384 — pinned offsets exist
    for 2048..16384 at degrees 4/6/8.

    ``replicas > 1`` switches the polish to the **device-sharded replica
    tier** (``_replica_polish``): R lockstep annealing chains (replica 0
    protected, best-into-worst exchange every ``exchange_every`` iterations
    — the ``sa_search`` semantics) whose proposals are priced in one
    ``shard_map`` dispatch per iteration, each device sweeping its replicas'
    packed-frontier BFS locally — the Pallas VMEM kernel when
    ``engine="pallas"``, its jitted jnp twin otherwise.  The dispatch prices
    **incrementally** (affected-rows-only re-sweep plus min-plus patch, the
    device twin of ``SymmetricAPSP``) with a periodic full-sweep drift
    guard every ``resync_every`` iterations.  ``proposal_batch`` prices M
    candidate swaps per
    chain per dispatch (accepted greedily in lockstep order) to amortize
    dispatch overhead; ``polish_iters`` overrides the polish iteration count
    derived from ``budget`` (it applies to the single-replica symmetric
    polish too).

    ``engine`` is forwarded to the polish stage (and through it to the
    ``core.engines`` registry, which validates it): ``None``/``"auto"``
    resolves to the C queue BFS kernel when one compiles and to the
    word-packed ``"bitset"`` sweep otherwise; every engine is bit-identical,
    so the choice affects wall time only.  The hillclimb stage independently
    auto-selects its candidate pricer (``circulant_search``'s jax batch
    sweep at n >= 4096).
    """
    from .known_optimal import KNOWN_CIRCULANT_OFFSETS

    # validate engine= before the circulant stage spends its budget
    engines.check_engine(engine)

    pinned = KNOWN_CIRCULANT_OFFSETS.get((n, k)) if seed == 0 else None
    if pinned is not None:
        mpl_c, d_c = _circulant_profile(n, pinned)
        res_c = SearchResult(
            graph=circulant(n, pinned, f"({n},{k})-Suboptimal"),
            mpl=mpl_c, diameter=d_c,
            mpl_lb=metrics.mpl_lower_bound(n, k),
            d_lb=metrics.diameter_lower_bound(n, k),
            iterations=0, accepted=0, history=[mpl_c], offsets=tuple(pinned))
    else:
        res_c = circulant_search(n, k, seed=seed, n_iter=budget or 400)
    if not polish or n % fold or res_c.offsets is None:
        return res_c
    n_polish = (polish_iters if polish_iters is not None
                else max(200, (budget or 400) * 2))
    # polish errors (device, compile, bad arguments) propagate: returning the
    # unpolished circulant here would hide a broken device path
    orbits = _circulant_orbits(n, n // fold, res_c.offsets)
    if replicas > 1:
        res_s = _replica_polish(
            n, k, seed=seed, n_iter=n_polish,
            fold=fold, start_orbits=orbits, engine=engine,
            replicas=replicas, exchange_every=exchange_every,
            proposal_batch=proposal_batch,
            resync_every=resync_every)
    else:
        res_s = symmetric_sa_search(
            n, k, seed=seed, n_iter=n_polish,
            fold=fold, start_orbits=orbits, engine=engine)
    if (res_s.mpl, res_s.diameter) < (res_c.mpl, res_c.diameter):
        return res_s
    # the circulant wins: keep the polish's work counters, so a caller can
    # still see that (and how much) the polish ran
    return dataclasses.replace(
        res_c, evals_delta=res_s.evals_delta, evals_full=res_s.evals_full,
        device_dispatches=res_s.device_dispatches)


def find_optimal(
    n: int,
    k: int,
    seed: int = 0,
    budget: int | None = None,
    method: str | None = None,
    replicas: int | None = None,
) -> Graph:
    """Deprecated shim: the paper-facing driver, now a thin delegate to the
    declarative ``repro.core.specs.search`` dispatch.

    method: 'exhaustive' | 'sa' | 'circulant' | 'symmetric' | 'large' |
    None (auto).  The strategy registry reproduces every branch of the old
    if-ladder byte-identically per seed — the auto policy (pinned edge lists
    from ``known_optimal`` instantly; n <= 64 → parallel-replica SA; larger
    → ``large_search``) now lives in ``specs.resolve_strategy``, and new
    tiers are registrations instead of new branches here.
    """
    import warnings

    warnings.warn(
        "find_optimal is deprecated: use repro.api.search(SearchSpec(n, k, "
        "strategy=..., budget=..., seed=...)) — auto strategy reproduces "
        "find_optimal's tier policy exactly",
        DeprecationWarning, stacklevel=2)
    from . import specs  # lazy: specs imports this module

    return specs.search(specs.SearchSpec(
        n=n, k=k, seed=seed, budget=budget, strategy=method or "auto",
        replicas=replicas)).graph
