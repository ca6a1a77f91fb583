"""Compile-only checks of the device search path for a TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a described ``v5e:2x2`` topology and raises what the chip's compiler
would raise (tiling, VMEM and SMEM limits) — which interpret-mode tests
cannot see.  Nothing runs, so these tests say nothing about results or
speed.  Sizes are the real ones of the polish tier: n = 8192, k = 8,
fold = 8 (s = 1024 representative rows), with the smallest and the largest
source-lane and patch-endpoint buckets the delta packers can produce.

The topology is described inside a module fixture, never at import, so that
only the test worker that runs this file loads the TPU library.
"""
import os

import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

N, K, FOLD = 8192, 8, 8
S = N // FOLD
# the largest delta buckets at this size: every one of the s rows swept
# (all 1024 lanes), and two added orbits of FOLD edges (4 * FOLD endpoints)
MAX_LANES = S
MAX_ENDPOINTS = 4 * FOLD
MAX_EDGES = 2 * FOLD


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means: no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("b,lanes", [(8, 32), (8, MAX_LANES)])
def test_sweep_kernel_compiles_for_v5e(one_chip, b, lanes):
    from repro.kernels import bfs_sweep

    fn = bfs_sweep._pallas_sweep(b, N, K, lanes, N, interpret=False)
    compiled = fn.lower(_sds((b, N, K), one_chip),
                        _sds((b, lanes), one_chip)).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("mmax", [1, MAX_ENDPOINTS])
def test_patch_kernel_compiles_for_v5e(one_chip, mmax):
    from repro.kernels import bfs_sweep

    fn = bfs_sweep._pallas_patch(8, S, N, mmax, interpret=False)
    compiled = fn.lower(_sds((8, S, N), one_chip), _sds((8, S, mmax), one_chip),
                        _sds((8, mmax, N), one_chip)).compile()
    assert _has_kernel(compiled)


def test_delta_step_compiles_for_v5e(topo):
    """The whole sharded delta dispatch — sweep kernel, merge, patch
    prologue and patch kernel — as one program for one chip."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.engines import pallas_sweep

    r, mprop = 4, 2
    b = r * mprop
    try:
        pallas_sweep.set_interpret(False)
        with pallas_sweep.replica_devices(topo.devices[:1]):
            rs = NamedSharding(pallas_sweep._mesh(r), P("r"))
            fn = pallas_sweep._sharded_delta_fn(
                r, mprop, N, K, S, MAX_LANES, MAX_ENDPOINTS, MAX_EDGES, N,
                use_pallas=True)
            args = [_sds((r, S, N), rs), _sds((b, N, K), rs),
                    _sds((b, MAX_LANES), rs)]
            args += [_sds((b, MAX_ENDPOINTS), rs)] * 3
            args += [_sds((b, MAX_ENDPOINTS), rs, jnp.bool_)]
            args += [_sds((b, MAX_EDGES), rs)] * 3
            compiled = fn.lower(*args).compile()
    finally:
        pallas_sweep.set_interpret(None)
    assert _has_kernel(compiled)


def test_jax_circulant_sweep_compiles_for_v5e(one_chip):
    from repro.core.engines import jax_circulant

    m = 6  # k = 6: three offsets and their negatives
    fn = jax_circulant._jax_sweep(N, m)
    compiled = fn.lower(_sds((jax_circulant.CHUNK, m), one_chip)).compile()
    assert compiled.memory_analysis().peak_memory_in_bytes > 0
