"""Topology-derived collective schedules executed in JAX.

The simulator (core.collectives) *predicts* schedule cost on a graph; this
module *runs* the same schedules on real devices with ``shard_map`` +
``lax.ppermute``.  The bridge to the paper: the rank order of a ring schedule
is a Hamiltonian cycle of the physical graph (core.hamiltonian), and the mesh
device order comes from the MPL/QAP layout (core.layout) — so every ppermute
step below is a 1-hop transfer on the optimized topology.

All functions run INSIDE shard_map (they take ``axis_name``).  Wrappers that
build the shard_map for a flat mesh axis are provided for tests/examples.

  ring_reduce_scatter / ring_allgather / ring_allreduce
      bandwidth-optimal ring schedules (2(n-1)/n · bytes on the wire)
  recursive_doubling_allreduce
      latency-optimal for small payloads (log n rounds)
  flood_bcast
      BFS flooding along *actual graph edges* (eccentricity rounds, all
      transfers 1 hop) — the topology-aware broadcast from core.collectives
  int8_ring_allreduce
      gradient compression: per-chunk absmax int8 quantization around the
      same ring schedule — ~4x fewer wire bytes, quantization error bounded
      by tests (beyond-paper distributed-optimization trick)
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..core.graphs import Graph
from ..core import collectives as C

__all__ = [
    "ring_perm",
    "ring_reduce_scatter",
    "ring_allgather",
    "ring_allreduce",
    "recursive_doubling_allreduce",
    "int8_ring_allreduce",
    "flood_bcast",
    "run_on_axis",
]


def ring_perm(n: int, order: Sequence[int] | None = None, reverse: bool = False):
    """ppermute pairs for one ring step over a device order (Hamiltonian)."""
    order = list(order) if order is not None else list(range(n))
    pairs = []
    for i in range(n):
        src = order[i]
        dst = order[(i + 1) % n]
        pairs.append((dst, src) if reverse else (src, dst))
    return pairs


def _my_ring_index(axis_name: str, order: Sequence[int] | None, n: int) -> jax.Array:
    rank = jax.lax.axis_index(axis_name)
    if order is None:
        return rank
    inv = np.argsort(np.asarray(order))  # physical rank -> ring position
    return jnp.asarray(inv)[rank]


def ring_reduce_scatter(x: jax.Array, axis_name: str,
                        order: Sequence[int] | None = None) -> jax.Array:
    """Per-device input x (same shape everywhere) -> my 1/n reduced chunk.

    x's leading dim must be divisible by n.  Returns chunk of shape
    (x.shape[0] // n, ...), the fully-reduced chunk this rank owns.
    """
    n = jax.lax.axis_size(axis_name)
    assert x.shape[0] % n == 0
    chunks = x.reshape(n, x.shape[0] // n, *x.shape[1:])
    pos = _my_ring_index(axis_name, order, n)
    perm = ring_perm(n, order)

    # start by forwarding my partial of chunk (pos-1); at step s the incoming
    # partial is for chunk (pos-s-2), to which I add my contribution; after
    # n-1 steps I hold the fully reduced chunk `pos`
    acc = jnp.take(chunks, (pos - 1) % n, axis=0)
    for s in range(n - 1):
        recv = jax.lax.ppermute(acc, axis_name, perm)
        own_idx = (pos - s - 2) % n
        acc = recv + jnp.take(chunks, own_idx, axis=0)
    return acc  # fully reduced chunk `pos`


def ring_allgather(x: jax.Array, axis_name: str,
                   order: Sequence[int] | None = None) -> jax.Array:
    """Per-device chunk -> concatenation of all chunks (ring, n-1 steps)."""
    n = jax.lax.axis_size(axis_name)
    pos = _my_ring_index(axis_name, order, n)
    perm = ring_perm(n, order)
    out = jnp.zeros((n, *x.shape), x.dtype)
    cur = x
    idx = pos
    out = out.at[idx].set(cur)
    for _ in range(n - 1):
        cur = jax.lax.ppermute(cur, axis_name, perm)
        idx = (idx - 1) % n
        out = out.at[idx].set(cur)
    return out.reshape(n * x.shape[0], *x.shape[1:])


def ring_allreduce(x: jax.Array, axis_name: str,
                   order: Sequence[int] | None = None) -> jax.Array:
    """Bandwidth-optimal ring allreduce; x identical-shaped on all ranks."""
    n = jax.lax.axis_size(axis_name)
    lead = x.shape[0] if x.ndim else 1
    pad = (-lead) % n
    xp = jnp.pad(x.reshape(lead, -1), ((0, pad), (0, 0))) if x.ndim else x.reshape(1, 1)
    chunk = ring_reduce_scatter(xp, axis_name, order)
    full = ring_allgather(chunk, axis_name, order)
    full = full[:lead] if pad else full
    return full.reshape(x.shape)


def recursive_doubling_allreduce(x: jax.Array, axis_name: str) -> jax.Array:
    """log2(n) rounds of XOR-partner exchange (latency-optimal, small msgs)."""
    n = jax.lax.axis_size(axis_name)
    assert n & (n - 1) == 0, "recursive doubling needs power-of-two axis"
    mask = 1
    while mask < n:
        perm = [(i, i ^ mask) for i in range(n)]
        x = x + jax.lax.ppermute(x, axis_name, perm)
        mask <<= 1
    return x


def int8_ring_allreduce(x: jax.Array, axis_name: str,
                        order: Sequence[int] | None = None) -> jax.Array:
    """Ring allreduce with int8-quantized payloads (per-hop requantization).

    Wire bytes ~ x.nbytes/4 + scales.  Quantization error per hop is bounded
    by scale/254; after n-1 hops relative error stays ~1e-2 for n<=32 (tested).
    """
    n = jax.lax.axis_size(axis_name)
    lead = x.shape[0]
    pad = (-lead) % n
    xp = jnp.pad(x.reshape(lead, -1).astype(jnp.float32), ((0, pad), (0, 0)))
    chunks = xp.reshape(n, xp.shape[0] // n, -1)
    pos = _my_ring_index(axis_name, order, n)
    perm = ring_perm(n, order)

    def q(v):
        scale = jnp.maximum(jnp.max(jnp.abs(v)), 1e-12) / 127.0
        return jnp.clip(jnp.round(v / scale), -127, 127).astype(jnp.int8), scale

    def dq(qv, scale):
        return qv.astype(jnp.float32) * scale

    acc = jnp.take(chunks, (pos - 1) % n, axis=0)
    for s in range(n - 1):
        qv, scale = q(acc)
        qv_r = jax.lax.ppermute(qv, axis_name, perm)
        scale_r = jax.lax.ppermute(scale, axis_name, perm)
        own_idx = (pos - s - 2) % n
        acc = dq(qv_r, scale_r) + jnp.take(chunks, own_idx, axis=0)
    # allgather phase, also int8
    qv, scale = q(acc)
    out = jnp.zeros((n, *acc.shape), jnp.float32)
    idx = pos
    out = out.at[idx].set(acc)
    cur_q, cur_s = qv, scale
    for _ in range(n - 1):
        cur_q = jax.lax.ppermute(cur_q, axis_name, perm)
        cur_s = jax.lax.ppermute(cur_s, axis_name, perm)
        idx = (idx - 1) % n
        out = out.at[idx].set(dq(cur_q, cur_s))
    flat = out.reshape(xp.shape[0], -1)
    flat = flat[:lead] if pad else flat
    return flat.reshape(x.shape).astype(x.dtype)


def flood_bcast(x: jax.Array, axis_name: str, g: Graph, root: int = 0) -> jax.Array:
    """BFS-flood broadcast along graph edges (all transfers 1 hop).

    Devices other than root contribute zeros; after ecc(root) rounds every
    rank holds root's value.  Rounds come from core.collectives.bcast_flood.
    """
    n = jax.lax.axis_size(axis_name)
    assert g.n == n
    sched = C.bcast_flood(n, 0.0, g, root=root)
    rank = jax.lax.axis_index(axis_name)
    have = (rank == root)
    val = jnp.where(have, x, jnp.zeros_like(x))
    for rnd in sched.rounds:
        # ppermute needs unique sources; a node feeding several neighbours in
        # one simulator round (one port per neighbour on real hardware) is
        # decomposed into sub-permutes by per-source ordinal.
        by_src: dict[int, list[int]] = {}
        subrounds: list[list[tuple[int, int]]] = []
        for t in rnd:
            k = len(by_src.setdefault(t.src, []))
            by_src[t.src].append(t.dst)
            while len(subrounds) <= k:
                subrounds.append([])
            subrounds[k].append((t.src, t.dst))
        for perm in subrounds:
            recv = jax.lax.ppermute(val, axis_name, perm)
            dsts = jnp.asarray([d for _, d in perm])
            is_dst = jnp.any(dsts == rank)
            val = jnp.where(is_dst & ~have, recv, val)
            have = have | is_dst
    return val


# ------------------------------------------------------------------------------
# shard_map wrapper for tests/examples
# ------------------------------------------------------------------------------

def run_on_axis(fn, mesh: Mesh, axis: str, *args):
    """Test/demo harness: args have leading dim == axis size (per-device
    inputs); fn runs per device on the slice; outputs are stacked back along
    the leading axis (so an allreduce returns n identical rows)."""

    def inner(*xs):
        out = fn(*[x[0] for x in xs], axis_name=axis)
        return out[None]

    wrapped = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=tuple(P(axis) for _ in args),
        out_specs=P(axis),
        check_vma=False,
    )
    return wrapped(*args)
