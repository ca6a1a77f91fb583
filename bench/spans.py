"""Benchmark-side spans and counters around the program's layer boundaries.

The program has no spans of its own yet, so the traced run wraps the calls
into each layer from outside: the replica polish, its device dispatch, the
circulant hillclimb and each priced candidate chunk.  Each wrapper opens a
``jax.profiler.TraceAnnotation`` named ``bench.<layer>``, which lands in the
profiler's trace on the same clock as the device ops, and adds to the
counters the readers under ``bench/metrics`` take their work counts from.
The wrappers are installed for the traced window only and removed after it;
the timed runs (``--trace 0``) call the program untouched.
"""
from __future__ import annotations

import contextlib
import functools

from . import workcount


class Recorder:
    """Counters filled by the wrappers while they are installed."""

    def __init__(self):
        self.counters: dict = {
            "polish_calls": 0, "polish_iterations": 0,
            "dispatches": 0, "sweep_bytes": 0, "patch_bytes": 0,
            "hillclimb_calls": 0, "chunks": 0,
        }

    def add(self, **kw) -> None:
        for k, v in kw.items():
            self.counters[k] = self.counters.get(k, 0) + v

    @contextlib.contextmanager
    def installed(self):
        """Wrap the layer entry points for the duration of the block."""
        import jax

        from repro.core import search
        from repro.core.engines import jax_circulant, pallas_sweep

        ann = jax.profiler.TraceAnnotation
        saved = [(search, "_replica_polish", search._replica_polish),
                 (search, "circulant_search", search.circulant_search),
                 (pallas_sweep, "sharded_delta_state",
                  pallas_sweep.sharded_delta_state),
                 (jax_circulant, "_jax_sweep", jax_circulant._jax_sweep)]
        polish, hill, delta, make_sweep = (f for _, _, f in saved)

        @functools.wraps(polish)
        def polish_w(*a, **kw):
            self.add(polish_calls=1, polish_iterations=int(kw["n_iter"]))
            with ann("bench.polish"):
                return polish(*a, **kw)

        @functools.wraps(hill)
        def hill_w(*a, **kw):
            self.add(hillclimb_calls=1)
            with ann("bench.hillclimb"):
                return hill(*a, **kw)

        @functools.wraps(delta)
        def delta_w(base, nbrs, sources_list, patches, sentinel, **kw):
            self.add(dispatches=1,
                     sweep_bytes=workcount.sweep_bytes(nbrs.shape, sources_list),
                     patch_bytes=workcount.patch_bytes(base.shape, patches))
            with ann("bench.dispatch"):
                # returns after the totals are on the host: the whole
                # program has run
                return delta(base, nbrs, sources_list, patches, sentinel, **kw)

        @functools.wraps(make_sweep)
        def make_sweep_w(*a, **kw):
            sweep = make_sweep(*a, **kw)

            def timed(chunk):
                self.add(chunks=1)
                with ann("bench.chunk"):
                    return jax.block_until_ready(sweep(chunk))

            return timed

        for (mod, name, _), f in zip(saved, (polish_w, hill_w, delta_w,
                                             make_sweep_w)):
            setattr(mod, name, f)
        try:
            yield self
        finally:
            for mod, name, f in saved:
                setattr(mod, name, f)
