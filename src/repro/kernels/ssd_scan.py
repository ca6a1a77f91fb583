"""Mamba2 SSD intra-chunk kernel for TPU (Pallas).

The SSD chunked algorithm splits into (a) a quadratic *intra-chunk* term —
two (chunk × chunk)·(chunk × p) matmuls plus a decay-masked score matrix —
and (b) a cheap linear *inter-chunk* state scan.  (a) is the compute hot spot
(MXU-friendly), so it is the kernel; (b) stays in jnp (``ops.ssd_scan``).

Grid = (batch·heads, n_chunks); every grid cell computes, entirely in VMEM:
    cs      = cumsum(dt · A)                       (1, Q)
    scores  = (C B^T) ⊙ tril(exp(cs_i − cs_j))     (Q, Q)
    y_intra = (scores ⊙ dt_j) X                    (Q, p)
    state   = X^T (B ⊙ dt ⊙ exp(cs_Q − cs))        (p, n)   [chunk summary]

Block shapes: Q=chunk (default 256), p=headdim (64), n=d_state (64/128) — the
(Q,Q) fp32 score tile is 256 KB, well inside VMEM; all matmul dims are
multiples of the 128-lane MXU for the production configs.

Validated in interpret mode against ``ref.ssd_scan_ref`` (sequential
recurrence) through ``ops.ssd_scan``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["ssd_intra_chunk"]


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, st_ref, *, chunk):
    x = x_ref[0].astype(jnp.float32)    # (Q, p)
    dt = dt_ref[...].astype(jnp.float32)  # (1, Q)
    a = a_ref[0, 0].astype(jnp.float32)   # scalar
    B = b_ref[0].astype(jnp.float32)    # (Q, n)
    C = c_ref[0].astype(jnp.float32)    # (Q, n)

    dtq = dt.reshape(chunk, 1)          # (Q, 1)
    dA = dtq * a                        # (Q, 1), negative
    cs = jnp.cumsum(dA, axis=0)         # (Q, 1)

    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (Q, Q)
    decay = cs - cs.reshape(1, chunk)   # cs_i - cs_j
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(ii >= jj, jnp.exp(decay), 0.0)
    w = scores * L * dtq.reshape(1, chunk)  # weight for source position j
    y_ref[0] = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32).astype(y_ref.dtype)

    seg_end = cs[chunk - 1]
    bw = B * (jnp.exp(seg_end - cs) * dtq)  # (Q, n)
    st = jax.lax.dot_general(x, bw, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (p, n)
    st_ref[0, 0] = st.astype(st_ref.dtype)


def ssd_intra_chunk(
    x: jax.Array,   # (bh, s, p)
    dt: jax.Array,  # (bh, s)
    A: jax.Array,   # (bh, 1)
    B: jax.Array,   # (bh, s, n)
    C: jax.Array,   # (bh, s, n)
    chunk: int,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """Returns (y_intra (bh, s, p) fp32, states (bh, nc, p, n) fp32)."""
    bh, s, p = x.shape
    n = B.shape[-1]
    assert s % chunk == 0
    nc = s // chunk
    kernel = functools.partial(_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda i, z: (i, z, 0)),
            pl.BlockSpec((1, chunk), lambda i, z: (i, z)),
            pl.BlockSpec((1, 1), lambda i, z: (i, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, z: (i, z, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, z: (i, z, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda i, z: (i, z, 0)),
            pl.BlockSpec((1, 1, p, n), lambda i, z: (i, z, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, p), jnp.float32),
            jax.ShapeDtypeStruct((bh, nc, p, n), jnp.float32),
        ],
        interpret=interpret,
    )(x, dt, A, B, C)
