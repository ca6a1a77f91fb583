"""``engine="pallas"`` — the device BFS sweep (``kernels.bfs_sweep``).

The same level-synchronous BFS as the host bitset engine, run as a Pallas
TPU kernel: each grid cell keeps a (n, 128-source) distance block resident
in VMEM for the whole level loop and reads neighbour indices as scalars from
SMEM.  Whether the kernels run compiled or in Pallas interpret mode is
resolved in one place, ``_default_interpret``: compiled exactly when JAX's
default backend is a TPU, interpret mode elsewhere (the CPU test suite),
unless ``REPRO_PALLAS_INTERPRET`` says otherwise.  The model-zoo kernels in
``repro.kernels.ops`` use the same resolver.

``sharded_rows_totals`` and ``sharded_delta_state`` are the replica-polish
entry points: R stacked neighbour tables are priced in one ``shard_map``
over the replica axis, so each device sweeps its replicas' graphs locally
and only per-replica scalars come home.  The delta path's distance state
stays on the devices: ``stack_states``, ``take_slot``, ``state_columns``
and ``states_equal`` stack, select, probe and compare it there.
"""
from __future__ import annotations

import contextlib
import functools
import os

import numpy as np

from ... import obs
from .base import Engine

# None = unresolved: the first get_interpret() call resolves it from the
# REPRO_PALLAS_INTERPRET env override, falling back to the platform
_INTERPRET: bool | None = None
# devices the replica axis shards over; None = every local device
_DEVICES: tuple | None = None
_CACHE: dict = {}


def _default_interpret() -> bool:
    """Resolve the interpret default: ``REPRO_PALLAS_INTERPRET`` wins
    (1/true/on → interpret, 0/false/off → compiled), otherwise compiled
    mode exactly when JAX's default backend is a TPU — the only platform
    the kernels are written for."""
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env is not None and env.strip() != "":
        return env.strip().lower() not in ("0", "false", "no", "off")
    return _jax().default_backend() != "tpu"


def set_interpret(v: bool | None) -> None:
    """Force Pallas interpret mode on (True) or off (False); ``None``
    re-resolves the default (env override, then platform)."""
    global _INTERPRET
    _INTERPRET = v
    _CACHE.clear()


def get_interpret() -> bool:
    """Whether the Pallas kernels currently run in interpret mode (the
    benchmarks record this: interpret-mode timings measure interpreter
    overhead, not device performance)."""
    global _INTERPRET
    if _INTERPRET is None:
        _INTERPRET = _default_interpret()
    return _INTERPRET


@contextlib.contextmanager
def replica_devices(devices):
    """Shard the replica axis over ``devices`` instead of every local
    device inside the block — e.g. one device, to check that a multi-device
    run prices the same trajectory."""
    global _DEVICES
    prev, _DEVICES = _DEVICES, tuple(devices)
    try:
        yield
    finally:
        _DEVICES = prev


def _jax():
    if "jax" not in _CACHE:
        try:
            import jax

            _CACHE["jax"] = jax
        except Exception:  # pragma: no cover - jax is a hard dep in CI
            _CACHE["jax"] = None
    return _CACHE["jax"]


class PallasEngine(Engine):
    name = "pallas"
    device_sweep = True

    def available(self) -> bool:
        return _jax() is not None

    def why_unavailable(self) -> str:
        return "pallas engine requested but jax is unavailable"

    def rows_bfs(self, ev, sources: np.ndarray) -> np.ndarray:
        from ...kernels import bfs_sweep

        return bfs_sweep.bfs_rows(ev.nbr, sources, ev.sentinel)


# ------------------------------------------------------------------------------
# Replica-sharded batched pricing (large_search replica polish)
# ------------------------------------------------------------------------------

def _mesh(r: int):
    """1-D ``("r",)`` mesh over the largest divisor of ``r`` that fits the
    replica devices (one device on a single-chip host: same math, one
    shard)."""
    from jax.sharding import Mesh

    devs = _DEVICES or tuple(_jax().devices())
    nd = max(d for d in range(1, min(r, len(devs)) + 1) if r % d == 0)
    return Mesh(np.asarray(devs[:nd]), ("r",))


def _sharding(r: int, *spec):
    """``NamedSharding`` over ``_mesh(r)``: ``("r",)`` splits the leading
    replica axis, no spec replicates."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(_mesh(r), P(*spec))


def _sweep(use_pallas: bool, interpret: bool, n: int, kmax: int, lanes: int,
           sentinel: int):
    """(bs, n, kmax) tables, (bs, lanes) sources -> (bs, lanes, n) rows:
    the Pallas kernel or its vmapped jnp twin."""
    jax = _jax()
    from ...kernels import bfs_sweep

    if use_pallas:
        return lambda nb, src: bfs_sweep._pallas_sweep(
            nb.shape[0], n, kmax, lanes, sentinel, interpret)(nb, src)
    return jax.vmap(functools.partial(bfs_sweep.sweep_rows_ref,
                                      sentinel=sentinel))


def _sharded_fn(r: int, n: int, kmax: int, lanes: int, m: int,
                sentinel: int, use_pallas: bool):
    jax = _jax()
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    interpret = get_interpret()
    mesh = _mesh(r)
    key = ("sharded", r, n, kmax, lanes, m, sentinel, use_pallas, interpret,
           tuple(mesh.devices.flat))
    fn = _CACHE.get(key)
    if fn is not None:
        return fn
    sweep = _sweep(use_pallas, interpret, n, kmax, lanes, sentinel)

    def per_shard(nb, src):
        rows = sweep(nb, src)[:, :m, :]
        # per-source sums fit int32 only while n * sentinel <= 2^31 - 1
        # (n <= 46340 with sentinel == n — guarded in sharded_rows_totals);
        # the int64 grand total is finished on the host, where x64 is on
        return (rows.sum(2, dtype=jnp.int32), rows.max((1, 2)))

    fn = jax.jit(jax.shard_map(
        per_shard, mesh=mesh, in_specs=(P("r"), P("r")),
        out_specs=(P("r"), P("r")), check_vma=False))
    _CACHE[key] = fn
    return fn


def sharded_rows_totals(
    nbrs: np.ndarray,
    n_sources: int,
    sentinel: int,
    use_pallas: bool = True,
) -> tuple[np.ndarray, np.ndarray]:
    """Price R stacked graphs on the device mesh in one dispatch.

    ``nbrs`` is (R, n, kmax) padded neighbour tables; BFS runs from sources
    ``0..n_sources-1`` of every graph (the representative rows of the
    symmetric tier).  Returns (totals (R,) int64, maxima (R,) int32) of the
    (n_sources, n) distance rows — exactly what the polish accept rule needs,
    so only 2R scalars leave the devices.
    """
    from ...kernels import bfs_sweep

    r, n, kmax = nbrs.shape
    m = n_sources
    if n * sentinel > np.iinfo(np.int32).max:
        # the device reduction accumulates per-source row sums in int32
        # (jax x64 is off); one row sums to at most n * sentinel
        raise NotImplementedError(
            f"device pricing needs n * sentinel <= int32 max (n={n}, "
            f"sentinel={sentinel})")
    nb, src = bfs_sweep.pack_sweep(nbrs, [np.arange(m)] * r)
    rowsums, mx = _sharded_fn(r, n, kmax, src.shape[1], m, sentinel,
                              use_pallas)(nb, src)
    return np.asarray(rowsums).sum(1, dtype=np.int64), np.asarray(mx)


# ------------------------------------------------------------------------------
# Replica-sharded delta pricing (incremental APSP on the device path)
# ------------------------------------------------------------------------------

def _sharded_delta_fn(r: int, mprop: int, n: int, kmax: int, s: int,
                      lanes: int, mmax: int, amax: int, sentinel: int,
                      use_pallas: bool):
    jax = _jax()
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ...kernels import bfs_sweep

    interpret = get_interpret()
    mesh = _mesh(r)
    key = ("delta", r, mprop, n, kmax, s, lanes, mmax, amax, sentinel,
           use_pallas, interpret, tuple(mesh.devices.flat))
    fn = _CACHE.get(key)
    if fn is not None:
        return fn
    sweep = _sweep(use_pallas, interpret, n, kmax, lanes, sentinel)

    def per_shard(base, nb, src, crow_src, crow_shift, pts_idx, pmask, add_i,
                  add_j, add_w):
        # base is (r_sh, s, n); the proposal arrays are (r_sh * mprop, ...)
        # in replica-major order, so repeating base rows M times lines the
        # two batch layouts up within the shard
        bs = nb.shape[0]
        rows = sweep(nb, src)
        baseb = jnp.repeat(base, mprop, axis=0)
        # merge: re-swept rows replace their representative rows, idle lanes
        # (src == n, out of range) drop; unaffected rows are provably exact
        merged = jax.vmap(
            lambda bb, rw, ii: bb.at[ii].set(rw, mode="drop")
        )(baseb, rows, src)
        tmp, crows = jax.vmap(bfs_sweep.patch_prologue)(
            merged, crow_src, crow_shift, pts_idx, pmask, add_i, add_j, add_w)
        if use_pallas:
            out = bfs_sweep._pallas_patch(bs, s, n, mmax, interpret)(
                merged, tmp, crows)
        else:
            out = bfs_sweep.patch_apply_ref(merged, tmp, crows)
        # int32 row sums: n * sentinel <= 2^31 - 1 guarded by the caller
        return out.sum(2, dtype=jnp.int32), out.max((1, 2)), out

    fn = jax.jit(jax.shard_map(
        per_shard, mesh=mesh, in_specs=(P("r"),) * 10,
        out_specs=(P("r"), P("r"), P("r")), check_vma=False))
    _CACHE[key] = fn
    return fn


def sharded_delta_state(
    base: np.ndarray,
    nbrs: np.ndarray,
    sources_list,
    patches,
    sentinel: int,
    use_pallas: bool = True,
):
    """Price b = R*M proposal graphs *incrementally* in one device dispatch.

    The delta twin of ``sharded_rows_totals``: instead of re-sweeping every
    representative row of every proposal, each proposal re-sweeps only its
    ``sources_list[i]`` rows (the affected set from the host-side batched
    lost-parent test) on its ``nbrs[i]`` (n, kmax) table — the post-removal
    graph — merges them into its chain's ``base`` (R, s, n) rows, and applies
    the min-plus insert patch for ``patches[i]`` (the added edge list, or
    None).  Full-rebuild proposals are expressed in the same vocabulary:
    all rows affected, post-swap table, no patch.  Proposal i belongs to
    chain ``i // M`` (replica-major order, M = b // R proposals per chain).

    ``base`` may be a host array or a device array (``stack_states``); a
    device base is not copied back.  Returns ``(totals (b,) int64, maxima
    (b,) int32, state)`` where state is the (b, s, n) post-swap
    representative rows, a device array sharded over the replica axis
    (callers select the accepted proposals with ``take_slot``).  Exact
    integer hop counts: bit-identical to the full sweep, per the property
    tests.
    """
    from ...kernels import bfs_sweep

    r, s, n = base.shape
    b, _, kmax = nbrs.shape
    if b % r:
        raise ValueError(f"proposal batch {b} is not a multiple of replicas {r}")
    if n * sentinel > np.iinfo(np.int32).max:
        raise NotImplementedError(
            f"device pricing needs n * sentinel <= int32 max (n={n}, "
            f"sentinel={sentinel})")
    # src doubles as the merge scatter index: lane j of proposal i re-sweeps
    # representative row src[i, j]
    with obs.span("repro.dispatch.pack"):
        nb, src = bfs_sweep.pack_sweep(nbrs, sources_list)
        patch = bfs_sweep.pack_patch(patches, s)
    mmax, amax = patch[2].shape[1], patch[4].shape[1]
    # the call returns once the inputs are enqueued; the pull of the totals
    # waits for the upload and the whole program.  A host base is uploaded
    # and a device base left where it is, with one placement either way, so
    # both reach the same compiled program
    with obs.span("repro.dispatch.run"):
        base = _jax().device_put(base, _sharding(r, "r"))
        rowsums, mx, state = _sharded_delta_fn(
            r, b // r, n, kmax, s, src.shape[1], mmax, amax, sentinel,
            use_pallas)(base, nb, src, *patch)
        return (np.asarray(rowsums).sum(1, dtype=np.int64), np.asarray(mx),
                state)


# ------------------------------------------------------------------------------
# Device-resident chain state (the delta polish's distance rows)
# ------------------------------------------------------------------------------

def _helper(fn, **kw):
    """``jax.jit(fn)``, cached under ``fn``'s name: the program is named
    after it (never ``per_shard``, the dispatch's own name)."""
    key = ("helper", fn.__name__, tuple(sorted(kw.items())))
    out = _CACHE.get(key)
    if out is None:
        out = _CACHE[key] = _jax().jit(fn, **kw)
    return out


def stack_states(states, replicas: int):
    """Stack R (s, n) chain states into the (R, s, n) dispatch base on the
    device, sharded over the replica axis.  Device states are left where
    they are (host ones, as tests pass, are uploaded); nothing is pulled."""
    import jax.numpy as jnp

    def stack_states(xs):
        return jnp.stack(xs)

    sharding = _sharding(replicas, "r")
    xs = _jax().device_put(list(states), _sharding(replicas))
    return _helper(stack_states, out_shardings=sharding)(xs)


def take_slot(states, slot: int, replicas: int):
    """Proposal ``slot``'s (s, n) rows of a (b, s, n) device state, kept on
    the device and replicated over the replica mesh.  The slot is a traced
    argument, so one program serves every slot."""
    from jax import lax

    def take_slot(st, i):
        return lax.dynamic_index_in_dim(st, i, keepdims=False)

    row = _helper(take_slot)(states, np.int32(slot))
    return _jax().device_put(row, _sharding(replicas))


def state_columns(base, cols: np.ndarray) -> np.ndarray:
    """Gather columns of the chains' device state and pull them once.

    ``base`` is the (R, s, n) device state, ``cols`` (b, C) int32 vertex
    columns with proposal i reading chain ``i // (b // R)`` (replica-major,
    as the dispatch).  Returns the (b, s, C) int32 host array
    ``out[i] = base[i // M][:, cols[i]]``."""
    jax = _jax()

    def state_columns(st, cc):
        r, s, _ = st.shape
        b, c = cc.shape
        m = b // r
        g = jax.vmap(lambda x, ci: x[:, ci])(st, cc.reshape(r, m * c))
        return g.reshape(r, s, m, c).transpose(0, 2, 1, 3).reshape(b, s, c)

    return np.asarray(_helper(state_columns)(base, cols))


def states_equal(a, b) -> np.ndarray:
    """(R,) bool: whether each replica's (s, n) rows of two (R, s, n)
    device states agree bit for bit; only the flags leave the device."""
    import jax.numpy as jnp

    def states_equal(x, y):
        return jnp.all(x == y, axis=(1, 2))

    return np.asarray(_helper(states_equal)(a, b))
