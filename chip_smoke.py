"""Smoke run of the device search path on a TPU, through ``repro.api``.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips: the sharded parts only

One chip runs two phases:

* ``polish`` — the replica polish at n = 8192, k = 8, fold = 8 (4 chains of
  (1024, 8192) int32 state, 2 proposals each, 32 iterations) with the Pallas
  sweep and patch kernels compiled for the chip.  The same spec priced by
  the jnp twins must give the identical trajectory, the returned graph
  re-certified by the independent host BFS of ``repro.core.certify`` must
  have the same MPL and diameter, and the polish must have dispatched to
  the device.
* ``circulant`` — the circulant hillclimb at n = 8192, k = 6 priced by the
  jitted JAX sweep must follow the numpy pricer's trajectory.

``--four-chips`` runs only what exists across chips: the same polish spec
with its replica axis sharded over four chips against the same spec on a
one-device mesh, and the ``comm.jaxcoll`` ring and recursive-doubling
allreduce on a four-device mesh against ``jax.lax.psum``.

Everything runs in this one process.  Earlier lines report the device,
wall seconds per phase (compiles included) and the compared values; the
last line of stdout is ``{"ok": true, "device": {...}}``.  Without a TPU,
or on any mismatch or error, the script exits non-zero and prints no such
line.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
sys.path.insert(0, SRC)

# the real size: one (n, k) the certified table pins, fold 8
POLISH = dict(n=8192, k=8, fold=8, replicas=4, proposal_batch=2,
              polish_iters=32)
CIRCULANT = dict(n=8192, k=6, seed=1)
ALLREDUCE_SHAPE = (1024, 1024)  # int32 per device: exact sums to compare


def polish_spec(engine, n, k, fold, replicas, proposal_batch, polish_iters):
    from repro.api import SearchSpec

    return SearchSpec.make(n, k, seed=0, strategy="large", fold=fold,
                           replicas=replicas, proposal_batch=proposal_batch,
                           polish_iters=polish_iters, engine=engine)


def _trajectory(res) -> dict:
    return {"mpl": res.mpl, "diameter": res.diameter,
            "accepted": res.accepted, "history": list(res.history)}


def _same(what: str, got, want) -> None:
    if got != want:
        raise AssertionError(f"{what} differs: {got!r} != {want!r}")


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def phase_polish(interpret: bool = False, **size) -> dict:
    """Pallas replica polish vs the jnp twins, and vs an independent
    recount of the returned graph."""
    from repro import api
    from repro.core import certify
    from repro.core.engines import pallas_sweep

    size = {**POLISH, **size}
    res, t_pallas = _timed(lambda: api.search(polish_spec("pallas", **size)))
    _same("Pallas interpret mode", pallas_sweep.get_interpret(), interpret)
    if res.device_dispatches <= 0:
        raise AssertionError("the polish made no device dispatch")
    twin, t_twin = _timed(lambda: api.search(polish_spec(None, **size)))
    _same("polish trajectory (pallas vs jnp twin)", _trajectory(res),
          _trajectory(twin))
    _same("polish graph (pallas vs jnp twin)", res.graph.edges,
          twin.graph.edges)
    cert, t_cert = _timed(lambda: certify.certify(res.graph))
    # integer totals over s or n sources: equal up to the final division
    if not math.isclose(cert.mpl, res.mpl, rel_tol=1e-12):
        raise AssertionError(f"certified MPL {cert.mpl!r} != {res.mpl!r}")
    _same("certified diameter", float(cert.diameter), res.diameter)
    return {"mpl": res.mpl, "diameter": res.diameter,
            "accepted": res.accepted, "history_len": len(res.history),
            "device_dispatches": res.device_dispatches,
            "evals_delta": res.evals_delta, "evals_full": res.evals_full,
            "certified_total_hops": cert.total_hops,
            "seconds_pallas": t_pallas, "seconds_jnp_twin": t_twin,
            "seconds_certify": t_cert}


def phase_circulant(**size) -> dict:
    """Circulant hillclimb: jitted JAX pricer vs the numpy pricer."""
    from repro.core import search

    size = {**CIRCULANT, **size}
    a, t_jax = _timed(lambda: search.circulant_search(engine="jax", **size))
    b, t_np = _timed(lambda: search.circulant_search(engine="numpy", **size))
    for f in ("offsets", "mpl", "diameter", "iterations", "history"):
        _same(f"circulant {f} (jax vs numpy)", getattr(a, f), getattr(b, f))
    return {"offsets": list(a.offsets), "mpl": a.mpl, "diameter": a.diameter,
            "iterations": a.iterations, "seconds_jax": t_jax,
            "seconds_numpy": t_np}


def phase_four_chips(**size) -> dict:
    """Replica polish sharded over four devices vs one device, and the
    jaxcoll allreduce schedules vs ``psum`` on a four-device mesh."""
    import jax
    import numpy as np

    from repro import api
    from repro.comm import jaxcoll
    from repro.core.engines import pallas_sweep
    from repro.launch.mesh import make_test_mesh

    devs = jax.devices()
    if len(devs) < 4:
        raise RuntimeError(f"four devices needed, found {len(devs)}")
    size = {**POLISH, **size}
    spec = polish_spec("pallas", **size)
    sharded, t_sharded = _timed(lambda: api.search(spec))
    with pallas_sweep.replica_devices(devs[:1]):
        single, t_single = _timed(lambda: api.search(spec))
    _same("polish trajectory (4 devices vs 1)", _trajectory(sharded),
          _trajectory(single))
    _same("polish graph (4 devices vs 1)", sharded.graph.edges,
          single.graph.edges)

    mesh = make_test_mesh((4,), ("x",))
    x = np.random.default_rng(0).integers(
        -1000, 1000, size=(4, *ALLREDUCE_SHAPE), dtype=np.int32)
    want = np.asarray(jaxcoll.run_on_axis(
        lambda v, axis_name: jax.lax.psum(v, axis_name), mesh, "x", x))
    for fn in (jaxcoll.ring_allreduce, jaxcoll.recursive_doubling_allreduce):
        got = np.asarray(jaxcoll.run_on_axis(fn, mesh, "x", x))
        if not np.array_equal(got, want):
            raise AssertionError(f"{fn.__name__} differs from psum")
    return {"mpl": sharded.mpl, "accepted": sharded.accepted,
            "device_dispatches": sharded.device_dispatches,
            "seconds_4_devices": t_sharded, "seconds_1_device": t_single,
            "allreduce_shape": list(x.shape)}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the four-chip phase")
    args = p.parse_args(argv)
    if not os.path.isfile(os.path.join(SRC, "repro", "api.py")):
        print(f"chip_smoke: no repro package at {SRC}", file=sys.stderr)
        return 1

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"device: {device}", flush=True)
    if device["platform"] != "tpu":
        print("chip_smoke: no TPU found; nothing was run", file=sys.stderr)
        return 1
    from repro import compile_cache

    print(f"compile cache: {compile_cache.enable()}", flush=True)
    phases = ([("four_chips", phase_four_chips)] if args.four_chips else
              [("polish", phase_polish), ("circulant", phase_circulant)])
    for name, phase in phases:
        out, secs = _timed(phase)
        print(f"phase {name}: ok in {secs:.1f} s {json.dumps(out)}",
              flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
