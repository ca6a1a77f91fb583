"""``engine="jax"`` — the jitted batched circulant pricer.

``circulant_search`` prices candidate offset sets; this module is the same
packed frontier sweep as the sequential ``search._circulant_profile``, jitted
and batched over candidate offset sets (each candidate's frontier is one
row; the while_loop advances every candidate's BFS level in lock step).
Exact integer hop counts, so the values — and therefore the hillclimb
trajectory — are identical to the numpy path.
"""
from __future__ import annotations

from collections.abc import Iterable

import numpy as np

from ... import obs

_CACHE: dict = {}
CHUNK = 32  # candidates per jitted call (padded, so shapes stay static)


def jax_modules():
    """(jax, jax.numpy) or (None, None); cached so the numpy path pays the
    import probe once."""
    if "modules" not in _CACHE:
        try:
            import jax
            import jax.numpy as jnp

            _CACHE["modules"] = (jax, jnp)
        except Exception:  # pragma: no cover - jax always present in CI
            _CACHE["modules"] = (None, None)
    return _CACHE["modules"]


def _jax_sweep(n: int, m: int):
    """Jitted batched frontier sweep for (chunk, m) shift arrays on C_n.

    Returns a function shifts -> (total_hops, diameter, connected) per
    candidate row.  Shift lists may contain duplicates (padding) — OR-ing a
    frontier with itself is a no-op, so the counts stay exact.
    """
    key = (n, m)
    fn = _CACHE.get(key)
    if fn is not None:
        return fn
    jax, jnp = jax_modules()

    def sweep(shifts):
        b = shifts.shape[0]
        idx = (jnp.arange(n)[None, None, :] - shifts[:, :, None]) % n  # (b, m, n)
        reach0 = jnp.zeros((b, n), bool).at[:, 0].set(True)
        zeros = jnp.zeros((b,), jnp.int32)

        def body(st):
            d, total, diam, reach, frontier = st
            nxt = jnp.zeros_like(frontier)
            for i in range(m):  # static unroll: m <= 2k shifts
                nxt = nxt | jnp.take_along_axis(frontier, idx[:, i, :], axis=1)
            newf = nxt & ~reach
            cnt = newf.sum(1, dtype=jnp.int32)
            d = d + 1
            return (d, total + d * cnt, jnp.where(cnt > 0, d, diam),
                    reach | newf, newf)

        st = (jnp.int32(0), zeros, zeros, reach0, reach0)
        _, total, diam, reach, _ = jax.lax.while_loop(
            lambda st: st[4].any(), body, st)
        return total, diam, reach.all(1)

    fn = jax.jit(sweep)
    _CACHE[key] = fn
    return fn


def profile_batch(n: int, offset_lists, engine: str,
                  pricer) -> "Iterable[tuple[float, float]]":
    """(MPL, diameter) for a batch of full offset lists (all the same length).

    ``engine="numpy"`` prices each list with ``pricer`` (the sequential
    ``search._circulant_profile``) — lazily, so a caller that stops consuming
    after an acceptance pays exactly the sequential cost; ``engine="jax"``
    packs the batch into padded ``CHUNK``-row chunks and prices each chunk in
    one jitted sweep.  Values are bit-identical.
    """
    if engine != "jax" or jax_modules()[0] is None:
        return (pricer(n, offs) for offs in offset_lists)
    if not offset_lists:
        return iter(())
    shifts = []
    for offs in offset_lists:
        ss = sorted({s % n for s in offs} - {0})
        shifts.append(sorted({sh for s in ss for sh in (s, n - s)}))
    m = max(len(s) for s in shifts)
    arr = np.empty((len(shifts), m), dtype=np.int32)
    for i, s in enumerate(shifts):
        arr[i] = np.resize(s, m)  # cyclic pad: duplicate shifts are no-ops
    sweep = _jax_sweep(n, m)

    def chunks():
        # lazy per-chunk pricing: a caller that stops consuming after an
        # acceptance never pays for the unexamined chunks (mirrors the
        # numpy generator)
        for lo in range(0, len(shifts), CHUNK):
            with obs.span("repro.hillclimb.chunk"):
                chunk = arr[lo : lo + CHUNK]
                real = len(chunk)
                if real < CHUNK:
                    chunk = np.concatenate(
                        [chunk, np.repeat(chunk[:1], CHUNK - real, axis=0)])
                total, diam, conn = (np.asarray(x) for x in sweep(chunk))
            for i in range(real):
                if conn[i]:
                    yield (int(total[i]) / (n - 1), float(diam[i]))
                else:
                    yield (float("inf"), float("inf"))

    return chunks()


def rows_priced(engine: str, consumed: int) -> int:
    """Rows ``profile_batch`` priced for a caller that took the first
    ``consumed`` values of one batch: whole ``CHUNK``-row chunks, padding
    included, on the jax engine (chunks are priced as they are reached);
    one per value on the lazy numpy path."""
    if engine != "jax" or jax_modules()[0] is None:
        return consumed
    return -(-consumed // CHUNK) * CHUNK
