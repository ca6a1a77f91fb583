"""FlashAttention forward kernel for TPU (Pallas, explicit BlockSpec tiling).

TPU-native design (not a CUDA port):
  * grid = (batch, heads, q_blocks, kv_blocks) — TPU executes the grid
    sequentially minor-to-major, so the online-softmax carry lives in VMEM
    scratch across the innermost (kv) dimension; no atomics, no shared-memory
    banking tricks.
  * BlockSpec index maps implement GQA *in the memory system*: the K/V block
    for head ``h`` is fetched from KV-head ``h // rep``, so grouped KV is
    never materialized at full head count in HBM.
  * block shapes default to (128, 128)×(128, head_dim): multiples of the MXU
    tile (128) and the fp32 VMEM tile (8, 128).  VMEM footprint per step =
    q_blk·hd + 2·kv_blk·hd + q_blk·kv_blk (fp32 scores) + carries ≈ 0.4 MB at
    the defaults — far under the ~16 MB/core budget, leaving room for
    double-buffered prefetch.
  * causal masking is done with ``broadcasted_iota`` against absolute
    positions (``q_offset`` supports prefill continuation); fully-masked
    blocks still execute (predication keeps the pipeline simple) — the
    measured cost is the empty-block matmul, acceptable at block 128.

Validated in interpret mode against ``ref.flash_attention_ref`` over
shape/dtype sweeps (tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_fwd"]

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *, scale, causal,
            q_offset, blk_q, blk_k, n_kv_blocks):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0, 0].astype(jnp.float32) * scale  # (blk_q, hd)
    k = k_ref[0, 0].astype(jnp.float32)          # (blk_k, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (blk_q, blk_k)
    if causal:
        qpos = q_offset + iq * blk_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = ik * blk_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(qpos >= kpos, s, NEG_INF)

    m_prev = m_sc[...]
    l_prev = l_sc[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + p.sum(axis=1, keepdims=True)
    acc_sc[...] = acc_sc[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_sc[...] = m_new
    l_sc[...] = l_new

    @pl.when(ik == n_kv_blocks - 1)
    def _finalize():
        o_ref[0, 0] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_fwd(
    q: jax.Array,  # (b, h, sq, hd)
    k: jax.Array,  # (b, kv, skv, hd)
    v: jax.Array,  # (b, kv, skv, hd)
    causal: bool = True,
    q_offset: int = 0,
    blk_q: int = 128,
    blk_k: int = 128,
    scale: float | None = None,  # pass the UNPADDED hd**-0.5 when hd is padded
    *,
    interpret: bool,
) -> jax.Array:
    b, h, sq, hd = q.shape
    _, kvh, skv, _ = k.shape
    rep = h // kvh
    blk_q = min(blk_q, sq)
    blk_k = min(blk_k, skv)
    assert sq % blk_q == 0 and skv % blk_k == 0, (sq, blk_q, skv, blk_k)
    nq, nk = sq // blk_q, skv // blk_k
    grid = (b, h, nq, nk)

    kernel = functools.partial(
        _kernel, scale=scale if scale is not None else hd ** -0.5,
        causal=causal, q_offset=q_offset,
        blk_q=blk_q, blk_k=blk_k, n_kv_blocks=nk)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, hd), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, blk_k, hd), lambda ib, ih, iq, ik, rep=rep: (ib, ih // rep, ik, 0)),
            pl.BlockSpec((1, 1, blk_k, hd), lambda ib, ih, iq, ik, rep=rep: (ib, ih // rep, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, hd), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
