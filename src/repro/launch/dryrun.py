import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST stay the first statements in this module — jax locks
the device count at first init, and the production meshes need 512 host
placeholder devices.  Do not set that flag anywhere global (smoke tests and
benches must see 1 device).

Per cell this:
  1. builds the production mesh ((16,16) or (2,16,16));
  2. builds the model + the full train_step (grads + optimizer) or serve_step;
  3. ``jax.jit(...).lower(*ShapeDtypeStructs).compile()``;
  4. records memory_analysis (proves it fits), cost_analysis (FLOPs/bytes for
     the roofline) and the collective-op wire bytes parsed from the
     partitioned HLO.

Results stream to JSON (``--out``); benchmarks/roofline.py consumes them.

Usage:
    python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k
    python -m repro.launch.dryrun --all --multi-pod --out results/dryrun.json
"""

import argparse
import json
import re
import sys
import time
import traceback
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ARCH_IDS, SHAPES, get_config
from ..models.zoo import build_model
from ..optim import make_optimizer
from ..train.trainer import make_train_step
from . import specs as S
from .mesh import make_production_mesh

# v5e-ish hardware constants (assignment spec)
PEAK_FLOPS = 197e12  # bf16 / chip
HBM_BW = 819e9       # B/s / chip
LINK_BW = 50e9       # B/s / link
HBM_PER_CHIP = 16 * 2 ** 30

_COLL_RE = re.compile(
    r"=\s*(?P<rtype>\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
    "pred": 1, "c64": 8, "c128": 16,
}


def _result_bytes(rtype: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(rtype):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo: str) -> dict:
    """Wire bytes per chip, per collective kind, from partitioned HLO.

    Shapes in post-SPMD HLO are per-partition.  Ring-schedule wire cost per
    chip:  all-reduce 2·b·(g-1)/g;  all-gather b·(g-1)/g (b = result bytes);
    reduce-scatter b·(g-1) (b = result = operand/g);  all-to-all b·(g-1)/g;
    collective-permute b.
    """
    out: dict[str, float] = {}
    count: dict[str, int] = {}
    for line in hlo.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group("op")
        b = _result_bytes(m.group("rtype"))
        g = None
        gm = _GROUPS_RE.search(line)
        if gm:
            g = len(gm.group(1).split(","))
        else:
            gm2 = _GROUPS_IOTA_RE.search(line)
            if gm2:
                g = int(gm2.group(2))
        g = g or 2
        if op == "all-reduce":
            wire = 2.0 * b * (g - 1) / g
        elif op == "all-gather":
            wire = b * (g - 1) / g
        elif op == "reduce-scatter":
            wire = b * (g - 1)
        elif op == "all-to-all":
            wire = b * (g - 1) / g
        else:  # collective-permute
            wire = float(b)
        out[op] = out.get(op, 0.0) + wire
        count[op] = count.get(op, 0) + 1
    out["total"] = sum(out.values())
    out["counts"] = count
    return out


def _units(cfg) -> int:
    """Extrapolation unit count: identical-cost repeated blocks."""
    if cfg.family == "hybrid":
        return cfg.n_layers // cfg.shared_attn_every  # stages
    return cfg.n_layers


def _with_units(cfg, u: int):
    """Measurement variant with ``u`` units, unrolled, single microbatch."""
    import dataclasses

    # keep the configured microbatching (the accumulation scan is unrolled in
    # measurement mode, so per-microbatch costs are counted correctly)
    kw = dict(unroll_layers=True)
    if cfg.family == "hybrid":
        kw["n_layers"] = u * cfg.shared_attn_every
    elif cfg.family == "encdec":
        kw["n_layers"] = u
        kw["enc_layers"] = u
    else:
        kw["n_layers"] = u
    return dataclasses.replace(cfg, **kw)


def _lower_one(cfg, shape, mesh, donate: bool):
    """Build + lower one step function; returns (lowered, kind)."""
    model = build_model(cfg, mesh=mesh)
    if shape.kind == "train":
        opt = make_optimizer(cfg.optimizer)
        step_fn = make_train_step(model, opt, microbatches=cfg.microbatches)
        st_shapes, st_shard = S.train_state_specs(model, opt, cfg.optimizer)
        in_specs = model.input_specs(shape)
        b_shard = S.batch_shardings(model, in_specs)
        jitted = jax.jit(step_fn, in_shardings=(st_shard, b_shard),
                         donate_argnums=(0,) if donate else ())
        return jitted.lower(st_shapes, in_specs)
    if shape.kind == "prefill":
        pshapes = S.param_shapes(model)
        p_shard = S.param_shardings(model, pshapes)
        in_specs = model.input_specs(shape)
        b_shard = S.batch_shardings(model, in_specs)

        def prefill_fn(params, batch):
            return model.prefill(params, batch, shape.seq_len)

        jitted = jax.jit(prefill_fn, in_shardings=(p_shard, b_shard))
        return jitted.lower(pshapes, in_specs)
    # decode
    (pshapes, tok, cache_shapes), (p_shard, t_shard, c_shard) = S.serve_specs(model, shape)

    def serve_step(params, tokens, cache):
        return model.decode_step(params, tokens, cache)

    jitted = jax.jit(serve_step, in_shardings=(p_shard, t_shard, c_shard),
                     donate_argnums=(2,) if donate else ())
    return jitted.lower(pshapes, tok, cache_shapes)


def _measure(cfg, shape, mesh) -> dict:
    """Roofline terms by 2-point unrolled extrapolation over layer units."""
    u_full = _units(cfg)
    res = {}
    for u in (1, 2):
        lo = _lower_one(_with_units(cfg, u), shape, mesh, donate=False)
        co = lo.compile()
        ca = co.cost_analysis() or {}
        coll = collective_bytes(co.as_text())
        res[u] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": coll,
        }

    def extrap(f1: float, f2: float) -> float:
        body = f2 - f1
        return f1 + max(body, 0.0) * (u_full - 1)

    flops = extrap(res[1]["flops"], res[2]["flops"])
    byts = extrap(res[1]["bytes"], res[2]["bytes"])
    coll_total = extrap(res[1]["coll"].get("total", 0.0), res[2]["coll"].get("total", 0.0))
    per_kind = {}
    kinds = set(res[1]["coll"]) | set(res[2]["coll"])
    for k in sorted(kinds - {"total", "counts"}):
        per_kind[k] = extrap(res[1]["coll"].get(k, 0.0), res[2]["coll"].get(k, 0.0))
    return {
        "hlo_flops_per_chip": flops,
        "hlo_bytes_per_chip": byts,
        "collective_wire_bytes_per_chip": coll_total,
        "collectives": per_kind,
        "units": u_full,
        "raw_1_2": {str(k): v for k, v in res.items()},
    }


def lower_cell(arch: str, shape_name: str, multi_pod: bool = False,
               donate: bool = True, extra_tag: str = "", cfg=None,
               skip_measure: bool = False) -> dict:
    cfg = cfg or get_config(arch)
    shape = SHAPES[shape_name]
    if shape_name == "long_500k" and not cfg.long_context_ok:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skipped", "reason": "pure full-attention arch (assignment rule)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = build_model(cfg, mesh=mesh)
    t0 = time.time()

    if shape.kind == "train":
        opt = make_optimizer(cfg.optimizer)
        step_fn = make_train_step(model, opt, microbatches=cfg.microbatches)
        st_shapes, st_shard = S.train_state_specs(model, opt, cfg.optimizer)
        in_specs = model.input_specs(shape)
        b_shard = S.batch_shardings(model, in_specs)
        jitted = jax.jit(step_fn, in_shardings=(st_shard, b_shard),
                         donate_argnums=(0,) if donate else ())
        lowered = jitted.lower(st_shapes, in_specs)
    elif shape.kind == "prefill":
        pshapes = S.param_shapes(model)
        p_shard = S.param_shardings(model, pshapes)
        in_specs = model.input_specs(shape)
        b_shard = S.batch_shardings(model, in_specs)

        def prefill_fn(params, batch):
            return model.prefill(params, batch, shape.seq_len)

        jitted = jax.jit(prefill_fn, in_shardings=(p_shard, b_shard))
        lowered = jitted.lower(pshapes, in_specs)
    else:  # decode
        (pshapes, tok, cache_shapes), (p_shard, t_shard, c_shard) = S.serve_specs(model, shape)

        def serve_step(params, tokens, cache):
            return model.decode_step(params, tokens, cache)

        jitted = jax.jit(serve_step, in_shardings=(p_shard, t_shard, c_shard),
                         donate_argnums=(2,) if donate else ())
        lowered = jitted.lower(pshapes, tok, cache_shapes)

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    n_chips = int(np.prod(list(mesh.shape.values())))

    res = {
        "arch": arch,
        "shape": shape_name,
        "kind": shape.kind,
        "multi_pod": multi_pod,
        "tag": extra_tag,
        "status": "ok",
        "n_chips": n_chips,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "peak_bytes": int(ma.peak_memory_in_bytes),
            "alias_bytes": ma.alias_size_in_bytes,
            "generated_code_bytes": ma.generated_code_size_in_bytes,
        },
    }
    if not skip_measure:
        # while-loop bodies are cost-counted once by XLA; measure with 1- and
        # 2-unit fully-unrolled variants and extrapolate linearly (exact for
        # identical repeated blocks; embed/logits/optimizer land in the
        # intercept).  cost_analysis is per-partition (per chip) under SPMD.
        meas = _measure(cfg, shape, mesh)
        res.update({k: meas[k] for k in
                    ("hlo_flops_per_chip", "hlo_bytes_per_chip",
                     "collective_wire_bytes_per_chip", "collectives", "units")})
        res["roofline"] = {
            "compute_s": meas["hlo_flops_per_chip"] / PEAK_FLOPS,
            "memory_s": meas["hlo_bytes_per_chip"] / HBM_BW,
            "collective_s": meas["collective_wire_bytes_per_chip"] / LINK_BW,
        }
        dom = max(res["roofline"], key=res["roofline"].get)
        res["roofline"]["dominant"] = dom
    return res


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", choices=ARCH_IDS)
    p.add_argument("--shape", choices=list(SHAPES))
    p.add_argument("--all", action="store_true", help="every (arch x shape) cell")
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--both-meshes", action="store_true")
    p.add_argument("--out", default=None, help="JSON output path (appends records)")
    p.add_argument("--no-donate", action="store_true")
    args = p.parse_args(argv)

    cells = []
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                cells.append((a, s, mp))

    records = []
    if args.out and os.path.exists(args.out):
        with open(args.out) as f:
            records = json.load(f)
    done = {(r["arch"], r["shape"], r["multi_pod"]) for r in records if r.get("status") == "ok"}

    failures = 0
    for a, s, mp in cells:
        if (a, s, mp) in done:
            print(f"[skip cached] {a} {s} multi_pod={mp}")
            continue
        print(f"=== {a} x {s} (multi_pod={mp}) ===", flush=True)
        try:
            # roofline table is single-pod only (assignment): multi-pod pass
            # proves the 'pod' axis shards, no measurement variants needed
            r = lower_cell(a, s, multi_pod=mp, donate=not args.no_donate,
                           skip_measure=mp)
        except Exception as e:
            traceback.print_exc()
            r = {"arch": a, "shape": s, "multi_pod": mp, "status": "error",
                 "error": f"{type(e).__name__}: {e}"}
            failures += 1
        if r.get("status") == "ok":
            mm = r["memory"]
            # peak_memory_in_bytes includes live arguments (verified: peak ~=
            # args + temps across cells), so it is the HBM high-water mark
            fits = mm["peak_bytes"] <= HBM_PER_CHIP
            r["fits_hbm"] = bool(fits)
            line = (f"  lower {r['lower_s']}s compile {r['compile_s']}s | "
                    f"args {mm['argument_bytes']/2**30:.2f} GiB peak {mm['peak_bytes']/2**30:.2f} GiB "
                    f"fits={r['fits_hbm']}")
            if "roofline" in r:
                rl = r["roofline"]
                line += (f" | flops/chip {r['hlo_flops_per_chip']:.3g}"
                         f" | coll {r['collective_wire_bytes_per_chip']/2**20:.1f} MiB | "
                         f"roofline c/m/x = {rl['compute_s']*1e3:.2f}/{rl['memory_s']*1e3:.2f}/"
                         f"{rl['collective_s']*1e3:.2f} ms -> {rl['dominant']}")
            print(line, flush=True)
        elif r.get("status") == "skipped":
            print(f"  skipped: {r['reason']}")
        records = [x for x in records if not (x["arch"] == a and x["shape"] == s
                                              and x["multi_pod"] == mp)]
        records.append(r)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(records, f, indent=1)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
