"""Plain reference for the search jobs the benchmark times.

It imports nothing of ``repro``.  A search job is deterministic per seed, so
the reference replays the job from its spec and seed: the same random draws
and accept rules as the searched tiers (the circulant hillclimb, then the
replica orbit polish), with every candidate priced by a plain BFS written
here — a numpy BFS on the implicit circulant for the hillclimb, and a dense
0/1 matrix BFS on the device for the polish's orbit-swapped graphs.  Pricing
is exact integer hop counting, so the replay must reproduce the program's
trajectory and result bit for bit: MPL, diameter, accepted count, best-MPL
history and the edge set of the returned graph.

``precision="float32"`` is the control: the same replay with each hop total
turned into an MPL in float32 instead of float64, which breaks the exactness
the configuration states and must come out as not correct.

``graph_checks`` recounts the returned graph on its own: degrees, the
Hamiltonian ring, invariance under rotation by ``s = n / fold`` and the MPL
and diameter from the ``s`` representative sources.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

INF = float("inf")


@dataclass
class Expected:
    """What a job must return: the fields the harness compares."""

    mpl: float
    diameter: float
    accepted: int
    history: list
    edges: frozenset
    offsets: tuple | None = None


def _to_mpl(total: int, norm: int, precision: str) -> float:
    if precision == "float64":
        return total / norm
    if precision == "float32":
        return float(np.float32(total) / np.float32(norm))
    raise ValueError(f"unknown precision {precision!r}")


# --------------------------------------------------------------------------
# Circulant pricing and the hillclimb
# --------------------------------------------------------------------------

def circulant_hops(n: int, offsets) -> tuple[int, int] | None:
    """(total hops from vertex 0, eccentricity) of C_n(offsets), or None
    when the graph is disconnected.  Vertex-transitive, so one source
    gives the whole graph's MPL and diameter."""
    steps = sorted({o % n for o in offsets} | {(-o) % n for o in offsets}
                   - {0})
    steps = np.asarray(steps, dtype=np.int64)
    dist = np.full(n, -1, dtype=np.int64)
    dist[0] = 0
    frontier = np.array([0], dtype=np.int64)
    total, level = 0, 0
    while frontier.size:
        nxt = np.unique((frontier[:, None] + steps[None, :]) % n)
        nxt = nxt[dist[nxt] < 0]
        if not nxt.size:
            break
        level += 1
        dist[nxt] = level
        total += level * int(nxt.size)
        frontier = nxt
    if (dist < 0).any():
        return None
    return total, level


def hillclimb(n: int, k: int, seed: int, n_iter: int,
              precision: str = "float64", include_ring: bool = True) -> dict:
    """Replay of the random-restart circulant offset hillclimb."""
    rng = np.random.default_rng(seed)
    half = k // 2
    has_anti = k % 2 == 1
    if has_anti and n % 2:
        raise ValueError("odd k needs even n")

    def full_offsets(offsets) -> list[int]:
        offs = ([1] if include_ring else []) + sorted(offsets)
        if has_anti:
            offs = offs + [n // 2]
        return offs

    def price(offsets) -> tuple[float, float]:
        fo = full_offsets(offsets)
        if len(set(fo)) != len(fo):
            return INF, INF
        hops = circulant_hops(n, fo)
        if hops is None:
            return INF, INF
        return _to_mpl(hops[0], n - 1, precision), float(hops[1])

    n_free = half - (1 if include_ring else 0)
    lo, hi = 2, n // 2 - (1 if has_anti else 0)
    pool = list(range(lo, hi))
    best_offs = None
    best = (INF, INF)
    history: list[float] = []
    it = 0
    for _ in range(max(1, n_iter // 50)):
        offs = (sorted(rng.choice(pool, size=n_free, replace=False).tolist())
                if n_free else [])
        cur = price(offs)
        improved = True
        while improved and it < n_iter:
            improved = False
            for pos in range(len(offs)):
                cands = (pool if len(pool) * len(offs) <= n_iter else
                         rng.permutation(pool)[: min(32, len(pool))])
                cands = [int(c) for c in cands]
                i = 0
                while i < len(cands):
                    tail = cands[i:]
                    adv = len(tail)
                    for j, c in enumerate(tail):
                        it += 1
                        if c in offs:
                            continue
                        trial = sorted(offs[:pos] + [c] + offs[pos + 1:])
                        fo = full_offsets(trial)
                        if len(set(fo)) != len(fo):
                            continue
                        val = price(trial)
                        if val < cur:
                            offs, cur = trial, val
                            improved = True
                            adv = j + 1
                            break
                    i += adv
            if cur < best:
                best, best_offs = cur, list(offs)
                history.append(best[0])
        if cur < best:
            best, best_offs = cur, list(offs)
            history.append(best[0])
    return {"mpl": best[0], "diameter": best[1], "iterations": it,
            "history": history, "offsets": tuple(full_offsets(best_offs or []))}


def circulant_edges(n: int, offsets) -> frozenset:
    out = set()
    for o in sorted({x % n for x in offsets} - {0}):
        for i in range(n):
            j = (i + o) % n
            out.add((min(i, j), max(i, j)))
    return frozenset(out)


# --------------------------------------------------------------------------
# Dense BFS on the device, for the orbit-swapped graphs
# --------------------------------------------------------------------------

class DenseBFS:
    """Hop counts from sources ``0..s-1`` of an n-vertex graph given as an
    edge list: a level-synchronous BFS whose frontier expansion is a
    (s, n) x (n, n) product of 0/1 matrices.  bfloat16 holds 0 and 1
    exactly and float32 accumulation counts at most n neighbours exactly,
    so the level sets are exact.  Returns per-level counts; the host sums
    them in int64."""

    def __init__(self, n: int, s: int, device=None):
        import jax
        import jax.numpy as jnp

        self.n, self.s = n, s
        self._device = device
        self._jax = jax

        def levels(u, v):
            a = jnp.zeros((n, n), jnp.bfloat16)
            a = a.at[u, v].set(1).at[v, u].set(1)
            f0 = jnp.arange(n)[None, :] == jnp.arange(s)[:, None]
            counts0 = jnp.zeros(n + 1, jnp.int32)

            def body(st):
                d, f, seen, counts = st
                reach = jnp.dot(f.astype(jnp.bfloat16), a,
                                preferred_element_type=jnp.float32) > 0
                new = reach & ~seen
                d = d + 1
                return d, new, seen | new, counts.at[d].set(
                    new.sum(dtype=jnp.int32))

            _, _, seen, counts = jax.lax.while_loop(
                lambda st: st[1].any(), body,
                (jnp.int32(0), f0, f0, counts0))
            return counts, seen.all()

        self._fn = jax.jit(levels)

    def submit(self, edges: np.ndarray):
        """Start one graph's BFS; ``edges`` is an (m, 2) int array."""
        e = np.ascontiguousarray(edges, dtype=np.int32)
        u, v = e[:, 0], e[:, 1]
        if self._device is not None:
            u = self._jax.device_put(u, self._device)
            v = self._jax.device_put(v, self._device)
        return self._fn(u, v)

    @staticmethod
    def result(handle) -> tuple[int, int] | None:
        """(total hops, eccentricity) or None when some pair is unreached."""
        counts, connected = handle
        counts = np.asarray(counts).astype(np.int64)
        if not bool(connected):
            return None
        levels = np.nonzero(counts)[0]
        ecc = int(levels.max()) if levels.size else 0
        return int((np.arange(counts.size, dtype=np.int64) * counts).sum()), ecc


# --------------------------------------------------------------------------
# The replica orbit polish
# --------------------------------------------------------------------------

def _orbit(n: int, s: int, u: int, v: int) -> frozenset:
    out = set()
    t = 0
    while t < n:
        a, b = (u + t) % n, (v + t) % n
        out.add((min(a, b), max(a, b)))
        t += s
    return frozenset(out)


def _circulant_orbits(n: int, s: int, offsets) -> set:
    orbits = set()
    for o in sorted({x % n for x in offsets} - {0}):
        if o in (1, n - 1):
            continue
        for u in range(s):
            orbits.add(_orbit(n, s, u, (u + o) % n))
    return orbits


def _draw_orbit_swap(rng, work_list, work_chords, ring_edges, n, s, fold):
    i1, i2 = rng.choice(len(work_list), size=2, replace=False)
    o1, o2 = work_list[i1], work_list[i2]
    (u1, v1) = next(iter(o1))
    (u2, v2) = next(iter(o2))
    tshift = int(rng.integers(fold)) * s
    if rng.integers(2):
        na, nb = (u1, (v2 + tshift) % n), ((u2 + tshift) % n, v1)
    else:
        na, nb = (u1, (u2 + tshift) % n), (v1, (v2 + tshift) % n)
    if na[0] == na[1] or nb[0] == nb[1]:
        return None
    no1, no2 = _orbit(n, s, *na), _orbit(n, s, *nb)
    if len(no1) + len(no2) != len(o1) + len(o2):
        return None
    remaining = work_chords - set(o1) - set(o2)
    new_edges = set(no1) | set(no2)
    if len(new_edges) != len(no1) + len(no2):
        return None
    if new_edges & (remaining | ring_edges):
        return None
    return int(i1), int(i2), no1, no2, new_edges, remaining


class _Chain:
    def __init__(self, rng, orb_list, t):
        self.rng = rng
        self.orb_list = list(orb_list)
        self.chord_edges = {e for orb in orb_list for e in orb}
        self.t = t
        self.cur = (INF, INF)
        self.best = (INF, INF)
        self.best_orbits = set(self.orb_list)


def polish(n: int, k: int, seed: int, n_iter: int, fold: int, start_offsets,
           replicas: int, proposal_batch: int, bfs: DenseBFS,
           precision: str = "float64", exchange_every: int = 50,
           t_start: float = 0.05, t_end: float = 1e-4) -> dict:
    """Replay of the lockstep replica orbit polish from a circulant."""
    s = n // fold
    gamma = math.exp(math.log(t_end / t_start) / n_iter)
    ring_edges = {(i, (i + 1) % n) for i in range(n - 1)} | {(0, n - 1)}
    ring = np.asarray(sorted(ring_edges), dtype=np.int32)
    norm = s * (n - 1)

    def submit(chord_edges):
        chords = np.asarray(list(chord_edges), dtype=np.int32).reshape(-1, 2)
        return bfs.submit(np.concatenate([ring, chords]))

    def value(handle) -> tuple[float, float]:
        hops = bfs.result(handle)
        if hops is None:
            return INF, INF
        return _to_mpl(hops[0], norm, precision), float(hops[1])

    start = sorted(_circulant_orbits(n, s, start_offsets), key=sorted)
    chains = [_Chain(np.random.default_rng([seed, r]), start, t_start)
              for r in range(replicas)]
    v0 = value(submit(chains[0].chord_edges))
    for ch in chains:
        ch.cur = ch.best = v0
    accepted = 0
    history = [v0[0]]
    global_best = v0
    for it in range(n_iter):
        proposals = []
        for ch in chains:
            ch.t *= gamma
            row = []
            for _ in range(proposal_batch):
                mv = (None if len(ch.orb_list) < 2 else
                      _draw_orbit_swap(ch.rng, ch.orb_list, ch.chord_edges,
                                       ring_edges, n, s, fold))
                if mv is None:
                    row.append(None)
                    continue
                i1, i2, no1, no2, new_edges, remaining = mv
                work_list = [o for idx, o in enumerate(ch.orb_list)
                             if idx not in (i1, i2)] + [no1, no2]
                work_chords = remaining | new_edges
                row.append((work_list, work_chords, submit(work_chords)))
            proposals.append(row)
        if not any(p is not None for row in proposals for p in row):
            continue
        for ch, row in zip(chains, proposals):
            for p in row:
                if p is None:
                    continue
                work_list, work_chords, handle = p
                new = value(handle)
                dm = new[0] - ch.cur[0]
                if not (dm < 0 or ch.rng.random() < math.exp(
                        -dm / max(ch.t, 1e-12))):
                    continue
                ch.orb_list, ch.chord_edges = work_list, work_chords
                ch.cur = new
                accepted += 1
                if ch.cur < ch.best:
                    ch.best_orbits = set(ch.orb_list)
                    ch.best = ch.cur
                    if ch.best < global_best:
                        global_best = ch.best
                        history.append(ch.best[0])
                break  # the rest of this chain's batch is discarded
        if replicas > 1 and (it + 1) % exchange_every == 0 and it + 1 < n_iter:
            gb = min(range(replicas), key=lambda r: (*chains[r].best, r))
            worst = max(range(1, replicas), key=lambda r: (*chains[r].cur, -r))
            if chains[gb].best < chains[worst].cur:
                ch = chains[worst]
                ch.orb_list = sorted(chains[gb].best_orbits, key=sorted)
                ch.chord_edges = {e for orb in ch.orb_list for e in orb}
                ch.cur = chains[gb].best
    gb = min(range(replicas), key=lambda r: (*chains[r].best, r))
    best = chains[gb]
    edges = set(ring_edges)
    for orb in best.best_orbits:
        edges |= set(orb)
    return {"mpl": best.best[0], "diameter": best.best[1],
            "accepted": accepted, "history": history,
            "edges": frozenset(edges)}


# --------------------------------------------------------------------------
# One job: hillclimb, then (optionally) the polish
# --------------------------------------------------------------------------

def replay_large(n: int, k: int, seed: int, budget: int, fold: int,
                 polish_stage: bool, replicas: int, proposal_batch: int,
                 polish_iters: int | None, bfs: DenseBFS | None,
                 precision: str = "float64") -> Expected:
    """Replay of ``strategy="large"`` for a non-zero seed (seed 0 takes
    pinned offsets, a different job that the benchmark never sends)."""
    if seed == 0:
        raise ValueError("seed 0 takes the pinned offsets; jobs use seeds != 0")
    hc = hillclimb(n, k, seed, budget or 400, precision)
    circ = Expected(mpl=hc["mpl"], diameter=hc["diameter"],
                    accepted=hc["iterations"], history=hc["history"],
                    edges=circulant_edges(n, hc["offsets"]),
                    offsets=hc["offsets"])
    if not polish_stage or n % fold:
        return circ
    if replicas <= 1:
        raise ValueError("the reference replays the replica polish only "
                         "(replicas > 1)")
    n_polish = (polish_iters if polish_iters is not None
                else max(200, (budget or 400) * 2))
    po = polish(n, k, seed, n_polish, fold, hc["offsets"], replicas,
                proposal_batch, bfs, precision)
    if (po["mpl"], po["diameter"]) < (hc["mpl"], hc["diameter"]):
        return Expected(mpl=po["mpl"], diameter=po["diameter"],
                        accepted=po["accepted"], history=po["history"],
                        edges=po["edges"])
    return circ


# --------------------------------------------------------------------------
# Checks on the returned graph alone
# --------------------------------------------------------------------------

def graph_checks(n: int, k: int, fold: int, edges, bfs: DenseBFS) -> dict:
    """Structure faults and the recounted (MPL, diameter) of one graph."""
    e = np.asarray(sorted(edges), dtype=np.int64).reshape(-1, 2)
    s = n // fold
    faults = []
    deg = np.bincount(e.ravel(), minlength=n)
    if not (deg == k).all():
        faults.append(f"{int((deg != k).sum())} vertices of degree != {k}")
    have = {(int(a), int(b)) for a, b in e}
    if len(have) != len(e) or (e[:, 0] == e[:, 1]).any():
        faults.append("duplicate edges or self-loops")
    ring = {(min(i, (i + 1) % n), max(i, (i + 1) % n)) for i in range(n)}
    if not ring <= have:
        faults.append(f"{len(ring - have)} ring edges missing")
    rot = np.sort((e + s) % n, axis=1)
    if {(int(a), int(b)) for a, b in rot} != have:
        faults.append(f"not invariant under rotation by {s}")
    hops = bfs.result(bfs.submit(e))
    mpl = INF if hops is None else hops[0] / (s * (n - 1))
    diameter = INF if hops is None else float(hops[1])
    return {"faults": faults, "mpl": mpl, "diameter": diameter}
