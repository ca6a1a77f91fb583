"""jit'd dispatch wrappers around the Pallas kernels.

Model code calls these; they handle layout (b,s,h,hd)<->(b,h,s,hd), head-dim
padding to the 128-lane MXU (kimi: 112 -> 128), and the inter-chunk state
scan that completes the SSD algorithm around the intra-chunk kernel.

Interpret mode comes from the one platform resolver the topology kernels
use, ``repro.core.engines.pallas_sweep.get_interpret``: compiled on a TPU
backend, Pallas interpret mode elsewhere, unless ``REPRO_PALLAS_INTERPRET``
says otherwise.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.engines.pallas_sweep import get_interpret
from . import flash_attention as _fa
from . import ssd_scan as _ssd

__all__ = ["flash_attention", "ssd_scan"]


def _pad_hd(x: jax.Array, mult: int = 128) -> tuple[jax.Array, int]:
    hd = x.shape[-1]
    pad = (-hd) % mult
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x, hd


def flash_attention(
    q: jax.Array,  # (b, sq, h, hd)
    k: jax.Array,  # (b, skv, kv, hd)
    v: jax.Array,  # (b, skv, kv, hd)
    causal: bool = True,
    q_offset: int = 0,
    blk_q: int = 128,
    blk_k: int = 128,
) -> jax.Array:
    """Flash attention with GQA; returns (b, sq, h, hd)."""
    hd = q.shape[-1]
    qt, _ = _pad_hd(q.transpose(0, 2, 1, 3))
    kt, _ = _pad_hd(k.transpose(0, 2, 1, 3))
    vt, _ = _pad_hd(v.transpose(0, 2, 1, 3))
    # padding the contraction dim with zeros leaves logits unchanged; padded
    # output channels are sliced away below
    o = _fa.flash_attention_fwd(qt, kt, vt, causal=causal, q_offset=q_offset,
                                blk_q=blk_q, blk_k=blk_k, scale=hd ** -0.5,
                                interpret=get_interpret())
    return o[..., :hd].transpose(0, 2, 1, 3)


def ssd_scan(
    x: jax.Array,   # (b, s, h, p)
    dt: jax.Array,  # (b, s, h)
    A: jax.Array,   # (h,)
    B: jax.Array,   # (b, s, h, n)
    C: jax.Array,   # (b, s, h, n)
    chunk: int,
    init_state: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Full SSD: Pallas intra-chunk kernel + jnp inter-chunk state scan.

    Returns (y (b,s,h,p) fp32, final_state (b,h,p,n) fp32) — same contract as
    ``models.ssm.ssd_chunked_ref``.
    """
    b, s_orig, h, p = x.shape
    n = B.shape[-1]
    pad = (-s_orig) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    s = x.shape[1]
    nc = s // chunk

    # (b, s, h, ...) -> (b*h, s, ...)
    xr = x.transpose(0, 2, 1, 3).reshape(b * h, s, p)
    dtr = dt.transpose(0, 2, 1).reshape(b * h, s).astype(jnp.float32)
    Ar = jnp.broadcast_to(A.astype(jnp.float32)[None, :], (b, h)).reshape(b * h, 1)
    Br = B.transpose(0, 2, 1, 3).reshape(b * h, s, n)
    Cr = C.transpose(0, 2, 1, 3).reshape(b * h, s, n)

    y_intra, states = _ssd.ssd_intra_chunk(xr, dtr, Ar, Br, Cr, chunk, interpret=get_interpret())

    # inter-chunk state scan (linear, cheap) + cross-chunk output term
    dA = (dtr * Ar).reshape(b * h, nc, chunk)
    cs = jnp.cumsum(dA, axis=-1)                      # (bh, nc, Q)
    seg_end = cs[..., -1]                             # (bh, nc)

    def scan_body(H, inp):
        st, dec = inp
        H_in = H
        return H * jnp.exp(dec)[:, None, None] + st, H_in

    H0 = (jnp.zeros((b * h, p, n), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32).reshape(b * h, p, n))
    H_final, H_ins = jax.lax.scan(
        scan_body, H0, (states.transpose(1, 0, 2, 3), seg_end.T))
    H_ins = H_ins.transpose(1, 0, 2, 3)               # (bh, nc, p, n)

    Crc = Cr.reshape(b * h, nc, chunk, n)
    y_inter = jnp.einsum("gzqn,gzpn,gzq->gzqp", Crc, H_ins, jnp.exp(cs))
    y = y_intra.reshape(b * h, nc, chunk, p) + y_inter
    y = y.reshape(b * h, s, p).reshape(b, h, s, p).transpose(0, 2, 1, 3)
    return y[:, :s_orig], H_final.reshape(b, h, p, n)
