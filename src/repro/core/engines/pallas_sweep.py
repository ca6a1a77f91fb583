"""``engine="pallas"`` — the device BFS sweep (``kernels.bfs_sweep``).

The same level-synchronous BFS as the host bitset engine, run as a Pallas
TPU kernel: each grid cell keeps the distance state of 128 sources resident
in VMEM for the whole level loop and reads neighbour indices as scalars from
SMEM.  The row kernel advances one vertex a step.  The delta dispatch's
graphs are invariant under rotation by s, and where their fold allows
(``bfs_sweep.sweep_fold``) the orbit kernel sweeps them, advancing a
vertex's whole orbit a step.  Whether the kernels run compiled or in Pallas interpret mode is
resolved in one place, ``_default_interpret``: compiled exactly when JAX's
default backend is a TPU, interpret mode elsewhere (the CPU test suite),
unless ``REPRO_PALLAS_INTERPRET`` says otherwise.  The model-zoo kernels in
``repro.kernels.ops`` use the same resolver.

``sharded_delta_state`` is the replica polish's one pricing entry point:
R*M stacked neighbour tables are priced in one ``shard_map`` over the
replica axis, so each device sweeps its replicas' graphs locally and only
per-proposal scalars come home.  The chains' distance state is one
(R, s, n) array split over the same replica axis, so each chain's rows
stay on the device that prices its proposals: ``spread_states``,
``place_states``, ``copy_state``, ``state_columns`` and ``states_equal``
start, update, exchange, probe and compare it there.
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
# Replica mesh (large_search replica polish)
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
           sentinel: int, s: int | None = None):
    """(bs, n, kmax) tables, (bs, lanes) sources -> (bs, lanes, n) rows:
    a Pallas kernel or the vmapped jnp twin.  Given ``s``, every table is
    invariant under rotation by ``s`` and every source is below it, and the
    orbit kernel sweeps where ``bfs_sweep.sweep_fold`` allows; the row
    kernel sweeps otherwise."""
    jax = _jax()
    from ...kernels import bfs_sweep

    if use_pallas and bfs_sweep.sweep_fold(n, s) > 1:
        return lambda nb, src: bfs_sweep._pallas_orbit_sweep(
            nb.shape[0], n, s, kmax, lanes, sentinel, interpret)(nb, src)
    if use_pallas:
        return lambda nb, src: bfs_sweep._pallas_sweep(
            nb.shape[0], n, kmax, lanes, sentinel, interpret)(nb, src)
    return jax.vmap(functools.partial(bfs_sweep.sweep_rows_ref,
                                      sentinel=sentinel))


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
    sweep = _sweep(use_pallas, interpret, n, kmax, lanes, sentinel, s)

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

    Instead of re-sweeping every representative row of every proposal,
    each proposal re-sweeps only its
    ``sources_list[i]`` rows (the affected set from the host-side batched
    lost-parent test) on its ``nbrs[i]`` (n, kmax) table — the post-removal
    graph — merges them into its chain's ``base`` (R, s, n) rows, and applies
    the min-plus insert patch for ``patches[i]`` (the added edge list, or
    None).  Full-rebuild proposals are expressed in the same vocabulary:
    all rows affected, post-swap table, no patch.  Proposal i belongs to
    chain ``i // M`` (replica-major order, M = b // R proposals per chain).

    ``base`` may be a host array or the chains' device state, split over
    the replica axis; a device base is not copied back.  Returns ``(totals
    (b,) int64, maxima (b,) int32, state)`` where state is the (b, s, n)
    post-swap representative rows, a device array split over the replica
    axis (callers select the accepted proposals with ``place_states``).  Exact
    integer hop counts: the totals and maxima are a from-scratch BFS's, per
    the property tests.

    Every table in ``nbrs`` must be invariant under rotation by s, as the
    patch's rolled endpoint rows assume too, and every source is a
    representative row below s: the orbit sweep kernel reads only the
    first s rows of a table.  The ``repro.dispatch.run`` span counts
    ``sweep_fold``: the fold the orbit kernel swept with, or 1 where the
    rows were swept otherwise.
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
    fold = bfs_sweep.sweep_fold(n, s) if use_pallas else 1
    with obs.span("repro.dispatch.run", sweep_fold=fold):
        base = _jax().device_put(base, _sharding(r, "r"))
        rowsums, mx, state = _sharded_delta_fn(
            r, b // r, n, kmax, s, src.shape[1], mmax, amax, sentinel,
            use_pallas)(base, nb, src, *patch)
        return (np.asarray(rowsums).sum(1, dtype=np.int64), np.asarray(mx),
                state)


# ------------------------------------------------------------------------------
# Device-resident chain state (the delta polish's distance rows)
# ------------------------------------------------------------------------------
#
# The chains' current rows and their best snapshots are two (R, s, n) int32
# arrays split over the replica axis, as the dispatch splits its proposals:
# chain r lives on the mesh device that prices its proposals.  Each program
# below is a ``shard_map`` over that mesh and runs on every device's own
# chains with no collective.  The one copy between devices is the
# exchange's, made by ``copy_state`` with ``jax.device_put``.

def replica_shards(r: int) -> int:
    """How many devices the replica axis of ``r`` chains is split over."""
    return _mesh(r).devices.size


def _state_programs(r: int) -> dict:
    """The chain-state programs for ``r`` chains, each jitted as a
    ``shard_map`` over ``_mesh(r)`` with every chain-indexed argument and
    result split over the replica axis, cached per mesh.  Each program is
    named after its body (never ``per_shard``, the dispatch's own name)."""
    jax = _jax()
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    mesh = _mesh(r)
    key = ("state", r, tuple(mesh.devices.flat))
    if key in _CACHE:
        return _CACHE[key]
    per = r // mesh.devices.size  # chains a device holds

    def spread_states(x):
        return jnp.broadcast_to(x, (per,) + x.shape[1:])

    def place_states(cur, snap, st, take, better):
        st = st.reshape((per, st.shape[0] // per) + st.shape[1:])
        new = cur
        for m in range(st.shape[1]):
            new = jnp.where((take == m)[:, None, None], st[:, m], new)
        return new, jnp.where(better[:, None, None], new, snap)

    def pick_state(x, i):
        # every device takes its own row nearest chain i; the one that
        # holds chain i takes chain i
        off = lax.axis_index("r") * per
        return lax.dynamic_index_in_dim(x, jnp.clip(i - off, 0, per - 1),
                                        keepdims=True)

    def put_state(x, row, j):
        hit = jnp.arange(per) == j - lax.axis_index("r") * per
        return jnp.where(hit[:, None, None], row, x)

    def state_columns(st, cc):
        b, c = cc.shape
        m = b // per
        g = jax.vmap(lambda x, ci: x[:, ci])(st, cc.reshape(per, m * c))
        return g.reshape(per, -1, m, c).transpose(0, 2, 1, 3).reshape(
            b, -1, c)

    def states_equal(x, y):
        return jnp.all(x == y, axis=(1, 2))

    split, whole = P("r"), P()

    def local(body, in_specs, out_specs):
        return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                     out_specs=out_specs, check_vma=False))

    progs = _CACHE[key] = {
        "spread": local(spread_states, (split,), split),
        "place": local(place_states, (split,) * 5, (split, split)),
        "pick": local(pick_state, (split, whole), split),
        "put": local(put_state, (split, split, whole), split),
        "columns": local(state_columns, (split, split), split),
        "equal": local(states_equal, (split, split), split),
    }
    return progs


def spread_states(rows, r: int):
    """The (r, s, n) chain state that starts every chain from one state:
    ``rows`` is (d, s, n), d = ``replica_shards(r)``, one copy of the state
    made on each replica device, and each device repeats its copy over its
    own chains."""
    return _state_programs(r)["spread"](rows)


def place_states(base, best, states, take: np.ndarray, better: np.ndarray):
    """Each chain's rows and best snapshot after a dispatch, in one program.

    ``base`` and ``best`` are the (R, s, n) chain state and snapshots,
    ``states`` the dispatch's (R*M, s, n) post-swap rows (replica-major),
    ``take`` (R,) int32 the proposal (0..M-1) that chain r accepted, -1
    where it kept its rows, and ``better`` (R,) bool where the chain's
    snapshot takes its new rows.  Returns the new ``(base, best)``, split
    as ``base``: every chain's rows stay on its device."""
    return _state_programs(base.shape[0])["place"](
        base, best, states, np.asarray(take, dtype=np.int32),
        np.asarray(better, dtype=bool))


def prepare_states(base, mprop: int) -> None:
    """Compile the placement and exchange programs for chain states shaped
    and split as ``base`` and M = ``mprop`` proposals a chain, once per
    shape: a walk's first accept or exchange (which may come only after
    ``exchange_every`` iterations) then compiles nothing."""
    jax = _jax()
    import jax.numpy as jnp

    r, s, n = base.shape
    key = ("prepared", r, s, n, mprop, tuple(_mesh(r).devices.flat))
    if key in _CACHE:
        return
    split = _sharding(r, "r")

    def dev(shape):  # a device array split over the replica axis
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=split)

    def host(shape, dtype=jnp.int32):  # a numpy argument
        return jax.ShapeDtypeStruct(shape, dtype)

    progs = _state_programs(r)
    progs["place"].lower(dev(base.shape), dev(base.shape),
                         dev((r * mprop, s, n)), host((r,)),
                         host((r,), jnp.bool_)).compile()
    progs["pick"].lower(dev(base.shape), host(())).compile()
    progs["put"].lower(dev(base.shape), dev((replica_shards(r), s, n)),
                       host(())).compile()
    _CACHE[key] = True


def copy_state(dst, src, i: int, j: int):
    """``dst`` with chain j's rows replaced by chain i's rows of ``src``
    (both (R, s, n), split over the replica axis), and the bytes that
    crossed between devices: none where both chains live on one device,
    one (s, n) block where they do not."""
    jax = _jax()

    r = dst.shape[0]
    per = r // replica_shards(r)
    progs = _state_programs(r)
    rows = progs["pick"](src, np.int32(i))  # (d, s, n): chain i on its device
    moved = 0
    if i // per != j // per:
        at = {(sh.index[0].start or 0): sh for sh in rows.addressable_shards}
        row = jax.device_put(at[i // per].data, at[j // per].device)
        moved = row.nbytes
        rows = jax.make_array_from_single_device_arrays(
            rows.shape, rows.sharding,
            [row if pos == j // per else sh.data for pos, sh in at.items()])
    return progs["put"](dst, rows, np.int32(j)), moved


def state_columns(base, cols: np.ndarray) -> np.ndarray:
    """Gather columns of the chains' device state and pull them once.

    ``base`` is the (R, s, n) device state, ``cols`` (b, C) int32 vertex
    columns with proposal i reading chain ``i // (b // R)`` (replica-major,
    as the dispatch).  Returns the (b, s, C) int32 host array
    ``out[i] = base[i // M][:, cols[i]]``; each device gathers its own
    chains' columns."""
    return np.asarray(_state_programs(base.shape[0])["columns"](
        base, np.asarray(cols, dtype=np.int32)))


def states_equal(a, b) -> np.ndarray:
    """(R,) bool: whether each replica's (s, n) rows of two (R, s, n)
    states agree bit for bit, compared where the chains live; only the
    flags leave the devices."""
    return np.asarray(_state_programs(a.shape[0])["equal"](a, b))
