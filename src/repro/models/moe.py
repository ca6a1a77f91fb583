"""Mixture-of-Experts FFN: capacity-based token dispatch under ``shard_map``.

Two sharding modes, selected per architecture (``MoECfg.mode``):

  * ``ep`` (kimi-k2: 384 experts, d_ff_expert=2048): experts shard over the
    'model' axis; tokens are dispatched into a per-chip (E, C, d) buffer and
    exchanged with ``lax.all_to_all`` so each chip runs only its E/16 local
    experts, then a second all_to_all returns expert outputs.  This is the
    GShard/Switch schedule with *sort-free* position assignment (cumulative
    one-hot replaced by an argsort + segment-rank, O(Tk log Tk) instead of
    O(T·E) memory).

  * ``tp`` (grok-1: 8 experts, d_ff_expert=32768): E < model-axis size, so
    experts cannot shard; instead every chip holds a d_ff shard of *every*
    expert (Megatron-style TP inside the expert) and the only collective is
    the output psum over 'model'.  No all_to_all.

Dense dispatch einsums ((T, E, C) one-hot tensors) are deliberately avoided:
at E=384, C≈1.7k they are ~10^13 elements.  The scatter/gather formulation
keeps the footprint at (E, C, d) per chip, and microbatching (config) keeps C
small.

Token dropping: assignments ranked beyond capacity get combine-weight zero
(standard capacity-factor semantics); the router aux loss (Switch-style
load-balancing) discourages imbalance.  Everything is differentiable.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ArchConfig, MoECfg
from .common import Initializer
from .sharding import ShardingRules

__all__ = ["init_moe_ffn", "moe_logical_axes", "moe_ffn"]


def init_moe_ffn(ini: Initializer, n_layers: int, cfg: ArchConfig) -> dict:
    m = cfg.moe
    d, fe, E = cfg.d_model, m.d_ff_expert, m.n_experts
    p = {
        "router": ini.normal((n_layers, d, E), stddev=0.02),
        "w1": ini.normal((n_layers, E, d, fe)),
        "w3": ini.normal((n_layers, E, d, fe)),
        "w2": ini.normal((n_layers, E, fe, d)),
    }
    if m.n_shared_experts:
        fs = fe * m.n_shared_experts
        p["shared"] = {
            "w1": ini.normal((n_layers, d, fs)),
            "w3": ini.normal((n_layers, d, fs)),
            "w2": ini.normal((n_layers, fs, d)),
        }
    return p


def moe_logical_axes(cfg: ArchConfig) -> dict:
    m = cfg.moe
    if m.mode == "ep":
        w = {
            "w1": (None, "w_expert", "w_exp_in", "w_exp_fe"),
            "w3": (None, "w_expert", "w_exp_in", "w_exp_fe"),
            "w2": (None, "w_expert", "w_exp_fe", "w_exp_in"),
        }
    else:  # tp
        w = {
            "w1": (None, None, "w_embed", "w_ff"),
            "w3": (None, None, "w_embed", "w_ff"),
            "w2": (None, None, "w_ff", "w_embed"),
        }
    axes = {"router": (None, None, None), **w}
    if m.n_shared_experts:
        axes["shared"] = {
            "w1": (None, "w_embed", "w_ff"),
            "w3": (None, "w_embed", "w_ff"),
            "w2": (None, "w_ff", "w_embed"),
        }
    return axes


# ------------------------------------------------------------------------------
# Local (per-shard) dispatch + expert compute
# ------------------------------------------------------------------------------

def _capacity(t_loc: int, m: MoECfg) -> int:
    c = int(t_loc * m.top_k * m.capacity_factor / m.n_experts)
    c = max(c, m.min_capacity)
    return (c + 3) // 4 * 4


def _positions_in_expert(e_flat: jax.Array, n_experts: int) -> jax.Array:
    """Rank of each assignment within its expert (stable arrival order).

    argsort groups assignments by expert; rank-in-segment is recovered with a
    cumulative-max over segment starts — O(A log A), no (T, E) cumsum.
    """
    a = e_flat.shape[0]
    order = jnp.argsort(e_flat, stable=True)
    sorted_e = e_flat[order]
    idx = jnp.arange(a, dtype=jnp.int32)
    is_start = jnp.concatenate([jnp.ones((1,), jnp.bool_), sorted_e[1:] != sorted_e[:-1]])
    seg_start = jax.lax.cummax(jnp.where(is_start, idx, 0))
    rank_sorted = idx - seg_start
    pos = jnp.zeros((a,), jnp.int32).at[order].set(rank_sorted)
    return pos


def _moe_shard(
    x: jax.Array,  # (bl, s, d) local tokens
    router_w: jax.Array,  # (d, E)
    w1: jax.Array,  # ep: (E_loc, d, fe) | tp: (E, d, fe_loc)
    w3: jax.Array,
    w2: jax.Array,  # ep: (E_loc, fe, d) | tp: (E, fe_loc, d)
    m: MoECfg,
    model_axis: str | None,
    fsdp_axis: str | None,
    fe_axis: str | None = None,
    pmean_axes: tuple[str, ...] = (),
) -> tuple[jax.Array, jax.Array]:
    """Per-shard MoE body (runs inside shard_map; axes None => single device).

    Weight layouts (ep mode):
      * fsdp_axis set: d_model dim ZeRO-3-sharded, gathered per call — right
        for training, where gather bytes amortize over many tokens;
      * fe_axis set (weight-stationary): the expert hidden dim is sharded and
        NEVER gathered; the partial w2 output is psum'd over fe_axis — right
        for decode, where tokens are few and weights dominate wire bytes.
    """
    bl, s, d = x.shape
    t = bl * s
    E, k = m.n_experts, m.top_k
    xf = x.reshape(t, d)

    if fsdp_axis is not None:  # ZeRO-3: re-materialize the FSDP'd weight dim
        gather = functools.partial(jax.lax.all_gather, axis_name=fsdp_axis, tiled=True)
        w1 = gather(w1, axis=1)
        w3 = gather(w3, axis=1)
        w2 = gather(w2, axis=2)

    # --- routing (fp32) -------------------------------------------------------
    logits = (xf.astype(jnp.float32) @ router_w.astype(jnp.float32))  # (t, E)
    gates = jax.nn.softmax(logits, axis=-1)
    gate_k, eids = jax.lax.top_k(gates, k)  # (t, k)
    gate_k = gate_k / jnp.maximum(gate_k.sum(-1, keepdims=True), 1e-9)

    # Switch aux loss: E * Σ_e fraction_tokens_e · mean_gate_e
    assign_frac = jnp.mean(
        (jax.nn.one_hot(eids, E, dtype=jnp.float32)).sum(1), axis=0)
    aux = E * jnp.sum(assign_frac / k * jnp.mean(gates, axis=0))
    if pmean_axes:
        aux = jax.lax.pmean(aux, pmean_axes)

    # --- dispatch -------------------------------------------------------------
    C = _capacity(t, m)
    e_flat = eids.reshape(-1).astype(jnp.int32)  # (t*k,)
    pos = _positions_in_expert(e_flat, E)
    keep = (pos < C).astype(xf.dtype)
    pos_c = jnp.minimum(pos, C - 1)
    tok_idx = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    buf = jnp.zeros((E, C, d), xf.dtype)
    buf = buf.at[e_flat, pos_c].add(xf[tok_idx] * keep[:, None])

    # --- expert compute -------------------------------------------------------
    if m.mode == "ep":
        if model_axis is not None:
            # (E, C, d) -> (E_loc, C * n_model, d): each chip keeps its experts
            buf = jax.lax.all_to_all(buf, model_axis, split_axis=0, concat_axis=1, tiled=True)
        h = jnp.einsum("ecd,edf->ecf", buf, w1) * jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w3))
        out = jnp.einsum("ecf,efd->ecd", h, w2)
        if fe_axis is not None:  # weight-stationary: combine fe partial sums
            out = jax.lax.psum(out, fe_axis)
        if model_axis is not None:
            out = jax.lax.all_to_all(out, model_axis, split_axis=1, concat_axis=0, tiled=True)
    else:  # tp: full E on-chip, fe sharded; single psum combines partial d
        h = jnp.einsum("ecd,edf->ecf", buf, w1) * jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w3))
        out = jnp.einsum("ecf,efd->ecd", h, w2)
        if model_axis is not None:
            out = jax.lax.psum(out, model_axis)

    # --- combine --------------------------------------------------------------
    y_flat = out[e_flat, pos_c] * (gate_k.reshape(-1, 1).astype(out.dtype) * keep[:, None])
    y = jnp.zeros((t, d), out.dtype).at[tok_idx].add(y_flat)
    return y.reshape(bl, s, d).astype(x.dtype), aux


# ------------------------------------------------------------------------------
# Public entry: shard_map wrapper
# ------------------------------------------------------------------------------

def moe_ffn(
    p: dict,  # one layer's slice: router (d,E), w1/w3/w2, [shared]
    x: jax.Array,  # (b, s, d) global
    cfg: ArchConfig,
    rules: ShardingRules,
) -> tuple[jax.Array, jax.Array]:
    """MoE FFN for one layer. Returns (y, aux_loss)."""
    m = cfg.moe
    mesh = rules.mesh
    if mesh is None:
        y, aux = _moe_shard(x, p["router"], p["w1"], p["w3"], p["w2"], m, None, None)
    else:
        batch_axes = rules.axes_for("batch")
        model_axes = rules.axes_for("heads")
        model_axis = model_axes[0] if model_axes else None
        fsdp_axes = rules.axes_for("w_embed")
        fsdp_axis = fsdp_axes[0] if fsdp_axes else None
        if m.mode == "ep":
            fe_axes = rules.axes_for("w_exp_fe")
            fe_axis = fe_axes[0] if fe_axes else None
            in_axes = rules.axes_for("w_exp_in")
            ep_fsdp = in_axes[0] if in_axes else None
            if fe_axis is not None:
                ep_fsdp = None  # weight-stationary: nothing to gather
            w_spec = (
                P(model_axis, ep_fsdp, fe_axis),
                P(model_axis, ep_fsdp, fe_axis),
                P(model_axis, fe_axis, ep_fsdp),
            )
        else:
            w_spec = (
                P(None, fsdp_axis, model_axis),
                P(None, fsdp_axis, model_axis),
                P(None, model_axis, fsdp_axis),
            )
        b_entry = batch_axes if batch_axes else None
        # EP: also shard the sequence dim over the model axis so each chip
        # dispatches a distinct token slice (otherwise dispatch and expert
        # compute replicate model_size-fold).  Decode (s=1) falls back to
        # replicated dispatch — negligible at one token.
        model_size = mesh.shape[model_axis] if model_axis else 1
        seq_entry = model_axis if (m.mode == "ep" and model_axis
                                   and x.shape[1] % model_size == 0) else None
        if m.mode == "ep":
            fn = functools.partial(_moe_shard, m=m, model_axis=model_axis,
                                   fsdp_axis=ep_fsdp, fe_axis=fe_axis,
                                   pmean_axes=tuple(mesh.axis_names))
        else:
            fn = functools.partial(_moe_shard, m=m, model_axis=model_axis, fsdp_axis=fsdp_axis,
                                   pmean_axes=tuple(mesh.axis_names))
        y, aux = jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=(P(b_entry, seq_entry, None), P(None, None), *w_spec),
            out_specs=(P(b_entry, seq_entry, None), P()),
            check_vma=False,
        )(x, p["router"], p["w1"], p["w3"], p["w2"])
    if m.n_shared_experts:
        from .common import swiglu

        sh = p["shared"]
        y = y + swiglu(x, sh["w1"], sh["w3"], sh["w2"], rules)
    return rules.shard(y, "batch", "seq", "embed"), aux
