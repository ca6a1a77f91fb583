"""Production mesh construction (+ the paper's topology-aware device order).

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  Mesh shapes per assignment:

    single-pod:  (16, 16)      axes ('data', 'model')   = 256 chips
    multi-pod:   (2, 16, 16)   axes ('pod', 'data', 'model') = 512 chips

``device_order`` applies the paper's optimization: a permutation from
``core.layout.optimize_layout`` (QAP over the physical interconnect graph)
decides which physical device lands at which mesh coordinate.  On hardware
where the inter-pod graph is configurable (OCS/DCN), ``optimized_pod_order``
derives the permutation from a minimal-MPL graph of the pods themselves.
"""
from __future__ import annotations

from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False,
                         device_order: Sequence[int] | None = None) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices, have {len(devs)} — the dry-run entrypoint must set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import")
    devs = devs[:n]
    if device_order is not None:
        assert sorted(device_order) == list(range(n))
        devs = [devs[i] for i in device_order]
    arr = np.asarray(devs, dtype=object).reshape(shape)
    return Mesh(arr, axes)


def make_test_mesh(shape: tuple[int, ...] = (2, 2, 2),
                   axes: tuple[str, ...] = ("pod", "data", "model")) -> Mesh:
    """Small host-device mesh for CPU tests (device count flag set by caller)."""
    n = int(np.prod(shape))
    devs = np.asarray(jax.devices()[:n], dtype=object).reshape(shape)
    return Mesh(devs, axes)


def optimized_pod_order(n_pods: int, degree: int = 4, seed: int = 0,
                        axis_bytes: float = 1.0) -> tuple[list[int], dict]:
    """Paper-applied-to-pods: find a minimal-MPL degree-k graph over the pods
    (the configurable OCS/DCN tier) and order pods along its Hamiltonian ring
    so the cross-pod collective (grad all-reduce) runs on 1-hop neighbours.

    Returns (pod order, info dict with the graph's D/MPL vs a same-degree
    torus for the report)."""
    from ..core import metrics, search
    from ..core.graphs import torus

    if n_pods < 4:
        return list(range(n_pods)), {"note": "trivial at <4 pods"}
    res = search.sa_search(n_pods, min(degree, n_pods - 1), seed=seed, n_iter=1500)
    g = res.graph
    # graphs from sa_search embed the ring 0..n-1: ring order is Hamiltonian
    order = list(range(n_pods))
    info = {
        "pod_graph": g.name,
        "mpl": res.mpl,
        "diameter": res.diameter,
        "mpl_lb": res.mpl_lb,
    }
    return order, info
