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


def _has_sweep_kernel(compiled) -> bool:
    """A Pallas custom call named after the jitted ``sweep``: the name the
    benchmark's sweep kernel time reads (``jit_per_shard/sweep.N``)."""
    return any(line.lstrip().startswith("%sweep.") and "tpu_custom_call" in line
               for line in compiled.as_text().splitlines())


@pytest.mark.parametrize("b,lanes", [(8, 32), (8, MAX_LANES)])
def test_sweep_kernel_compiles_for_v5e(one_chip, b, lanes):
    from repro.kernels import bfs_sweep

    fn = bfs_sweep._pallas_sweep(b, N, K, lanes, N, interpret=False)
    compiled = fn.lower(_sds((b, N, K), one_chip),
                        _sds((b, lanes), one_chip)).compile()
    assert _has_kernel(compiled)


# n, k, s: the polish cells' folds 8 and 4 (a 4-row tile of fold 4 is
# refused as unaligned: the kernel's tile repeats the orbit to 8 rows), and
# fold 2 at n = 16384, whose 32 MiB state the kernel asks VMEM room for
@pytest.mark.parametrize("n,k,s,lanes", [(N, K, S, 32), (N, K, S, MAX_LANES),
                                         (4096, 6, 1024, 512),
                                         (2 * N, K, N, 128)])
def test_orbit_sweep_kernel_compiles_for_v5e(one_chip, n, k, s, lanes):
    from repro.kernels import bfs_sweep

    fn = bfs_sweep._pallas_orbit_sweep(8, n, s, k, lanes, n, interpret=False)
    compiled = fn.lower(_sds((8, n, k), one_chip),
                        _sds((8, lanes), one_chip)).compile()
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
    assert _has_kernel(compiled) and _has_sweep_kernel(compiled)


def test_delta_step_compiles_for_v5e_at_fold_4(topo):
    """The whole delta dispatch at n = 4096, k = 6, fold 4 (the TPU v4 pod
    cell), whose orbit tiles hold each orbit twice.  The sweep is still
    the custom call named after the jitted ``sweep``, which the
    benchmark's kernel time reads."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.engines import pallas_sweep

    n, k, s, mmax, amax = 4096, 6, 1024, 16, 8
    r, mprop = 4, 2
    b = r * mprop
    try:
        pallas_sweep.set_interpret(False)
        with pallas_sweep.replica_devices(topo.devices[:1]):
            rs = NamedSharding(pallas_sweep._mesh(r), P("r"))
            fn = pallas_sweep._sharded_delta_fn(
                r, mprop, n, k, s, s, mmax, amax, n, use_pallas=True)
            args = [_sds((r, s, n), rs), _sds((b, n, k), rs),
                    _sds((b, s), rs)]
            args += [_sds((b, mmax), rs)] * 3
            args += [_sds((b, mmax), rs, jnp.bool_)]
            args += [_sds((b, amax), rs)] * 3
            compiled = fn.lower(*args).compile()
    finally:
        pallas_sweep.set_interpret(None)
    assert _has_sweep_kernel(compiled)


def test_jax_circulant_sweep_compiles_for_v5e(one_chip):
    from repro.core.engines import jax_circulant

    m = 6  # k = 6: three offsets and their negatives
    fn = jax_circulant._jax_sweep(N, m)
    compiled = fn.lower(_sds((jax_circulant.CHUNK, m), one_chip)).compile()
    assert compiled.memory_analysis().peak_memory_in_bytes > 0


COLLECTIVES = ("all-gather", "all-reduce", "collective-permute", "all-to-all",
               "reduce-scatter")


def test_replica_state_programs_compile_for_four_chips(topo):
    """Sixteen chains over the four chips of the described host (the
    ``polish4`` deployment): the delta dispatch and every chain-state
    program compile with the state split four chains a chip, and none
    holds a collective: each chip prices, places, probes and compares its
    own chains.  The exchange's one copy between chips is a
    ``jax.device_put`` outside every program."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.engines import pallas_sweep

    r, mprop = 16, 2
    b = r * mprop
    cols = 4 * FOLD * (1 + K)
    try:
        pallas_sweep.set_interpret(False)
        with pallas_sweep.replica_devices(topo.devices):
            assert pallas_sweep.replica_shards(r) == 4
            rs = NamedSharding(pallas_sweep._mesh(r), P("r"))
            state = _sds((r, S, N), rs)
            progs = pallas_sweep._state_programs(r)
            compiled = {
                "spread": progs["spread"].lower(_sds((4, S, N), rs)),
                "place": progs["place"].lower(
                    state, state, _sds((b, S, N), rs),
                    jax.ShapeDtypeStruct((r,), jnp.int32),
                    jax.ShapeDtypeStruct((r,), jnp.bool_)),
                "pick": progs["pick"].lower(
                    state, jax.ShapeDtypeStruct((), jnp.int32)),
                "put": progs["put"].lower(
                    state, _sds((4, S, N), rs),
                    jax.ShapeDtypeStruct((), jnp.int32)),
                "columns": progs["columns"].lower(
                    state, jax.ShapeDtypeStruct((b, cols), jnp.int32)),
                "equal": progs["equal"].lower(state, state),
            }
            fn = pallas_sweep._sharded_delta_fn(
                r, mprop, N, K, S, MAX_LANES, MAX_ENDPOINTS, MAX_EDGES, N,
                use_pallas=True)
            args = [state, _sds((b, N, K), rs), _sds((b, MAX_LANES), rs)]
            args += [_sds((b, MAX_ENDPOINTS), rs)] * 3
            args += [_sds((b, MAX_ENDPOINTS), rs, jnp.bool_)]
            args += [_sds((b, MAX_EDGES), rs)] * 3
            compiled["dispatch"] = fn.lower(*args)
            compiled = {k: v.compile() for k, v in compiled.items()}
    finally:
        pallas_sweep.set_interpret(None)
    assert _has_kernel(compiled["dispatch"])
    for name, c in compiled.items():
        text = c.as_text()
        assert not [op for op in COLLECTIVES if op in text], name
    # each chip's placement holds its four chains' rows, snapshots and
    # post-swap rows, never the sixteen
    chain = S * N * 4
    assert compiled["place"].memory_analysis().argument_size_in_bytes \
        < (4 + 4 + 8 + 1) * chain
