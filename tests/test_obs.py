"""The search's stage spans (``repro.obs``): where they fire, what they
count, and that a traced search returns what an untraced one does.

Each test records a JAX profiler trace of a tiny search on the CPU (the
polish priced by the jnp twins) and reads the ``repro.*`` host events back
from the ``.xplane.pb``; the trace is deleted when the test ends.
"""
from __future__ import annotations

import glob
import os
import shutil
import subprocess
import sys

import pytest

jax = pytest.importorskip("jax")

from repro import obs  # noqa: E402
from repro.core import search  # noqa: E402
from repro.core.engines import jax_circulant, pallas_sweep  # noqa: E402

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "src")


@pytest.fixture
def trace_dir(tmp_path):
    path = tmp_path / "trace"
    yield str(path)
    shutil.rmtree(path, ignore_errors=True)


def repro_events(trace_dir: str) -> dict:
    """name -> [(start_ns, end_ns, stats)] of the ``repro.*`` host events."""
    from jax.profiler import ProfileData

    [path] = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                       recursive=True)
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    start = int(e.start_ns)
                    out.setdefault(e.name, []).append(
                        (start, start + int(e.duration_ns), dict(e.stats)))
    return out


def count(events: dict, name: str) -> int:
    return len(events.get(name, ()))


def inside(inner, outer) -> bool:
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_span_and_mark_record_counts(trace_dir):
    with jax.profiler.trace(trace_dir):
        with obs.span("repro.test.outer", items=3):
            obs.mark("repro.test.tally", done=2, total=5)
    ev = repro_events(trace_dir)
    [outer] = ev["repro.test.outer"]
    [tally] = ev["repro.test.tally"]
    assert outer[2] == {"items": 3}
    assert tally[2] == {"done": 2, "total": 5}
    assert inside(tally, outer)


def test_spans_do_nothing_and_import_nothing_without_jax():
    """Where JAX was never imported, a numpy-only search runs its spans as
    no-ops and still imports no JAX."""
    code = (
        "import sys\n"
        "from repro import obs\n"
        "from repro.core import search\n"
        "with obs.span('repro.test', n=1):\n"
        "    obs.mark('repro.test.tally', n=1)\n"
        "search.circulant_search(64, 4, seed=1, n_iter=40, engine='numpy')\n"
        "print('jax' in sys.modules)\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, check=True,
                         env={**os.environ, "PYTHONPATH": SRC})
    assert out.stdout.strip() == "False"


def _tiny_polish(**kw):
    n, k, fold = 256, 6, 4
    orbits = search._circulant_orbits(n, n // fold, (1, 9, 40))
    return search._replica_polish(
        n, k, seed=5, n_iter=10, fold=fold, start_orbits=orbits, engine=None,
        replicas=2, exchange_every=5, resync_every=4, proposal_batch=2, **kw)


def _count_calls(monkeypatch, calls: dict, batch: int) -> None:
    """Count, apart from the spans, the dispatches that price a whole batch
    of ``batch`` proposals (one per iteration with a proposal) and the
    calls of the placement program and of the exchange's copy."""
    delta = pallas_sweep.sharded_delta_state
    place, copy = pallas_sweep.place_states, pallas_sweep.copy_state

    def delta_w(base, nbrs, *a, **kw):
        calls["batches"] += nbrs.shape[0] == batch
        return delta(base, nbrs, *a, **kw)

    def place_w(*a):
        calls["place"] += 1
        return place(*a)

    def copy_w(*a):
        calls["copy"] += 1
        return copy(*a)

    calls.update(batches=0, place=0, copy=0)
    monkeypatch.setattr(pallas_sweep, "sharded_delta_state", delta_w)
    monkeypatch.setattr(pallas_sweep, "place_states", place_w)
    monkeypatch.setattr(pallas_sweep, "copy_state", copy_w)


def test_replica_polish_stage_spans(trace_dir, monkeypatch):
    """One propose and one accept per iteration, one exchange per
    ``exchange_every``, one resync per ``resync_every`` plus the last, one
    column gather inside the propose of every iteration with a proposal,
    one pack per dispatch, one place per placement call and exchange copy,
    after the accept; every stage inside ``repro.polish``; the traced
    result equals the untraced one."""
    untraced = _tiny_polish()

    calls: dict = {}
    _count_calls(monkeypatch, calls, batch=2 * 2)
    with jax.profiler.trace(trace_dir):
        traced = _tiny_polish()
    assert traced == untraced

    ev = repro_events(trace_dir)
    n_iter, resyncs = 10, 3  # resyncs after iterations 4, 8 and 10
    [polish] = ev["repro.polish"]
    assert polish[2] == {"iterations": n_iter}
    assert count(ev, "repro.polish.setup") == 1
    assert count(ev, "repro.polish.finish") == 1
    assert count(ev, "repro.polish.propose") == n_iter
    assert count(ev, "repro.polish.accept") == n_iter
    assert count(ev, "repro.polish.exchange") == 1  # after iteration 5
    assert count(ev, "repro.polish.resync") == resyncs
    assert 0 < calls["batches"] <= n_iter
    assert count(ev, "repro.polish.columns") == calls["batches"]
    assert count(ev, "repro.polish.pull") == 0
    assert 0 < calls["place"] <= calls["batches"]
    assert count(ev, "repro.polish.place") == calls["place"] + calls["copy"]
    # one run and one pack per dispatch: the state is stacked on the device
    assert count(ev, "repro.dispatch.run") == traced.device_dispatches \
        == 1 + calls["batches"] + resyncs
    assert count(ev, "repro.dispatch.pack") == traced.device_dispatches
    for name, spans in ev.items():
        assert all(inside(s, polish) for s in spans), name
    proposes = ev["repro.polish.propose"]
    assert all(any(inside(c, p) for p in proposes)
               for c in ev["repro.polish.columns"])
    for run in ev["repro.dispatch.run"]:
        assert not any(inside(run, p) for p in proposes)
        assert not any(inside(run, a) for a in ev["repro.polish.accept"])
    for place in ev["repro.polish.place"]:
        assert not any(inside(place, a) for a in ev["repro.polish.accept"])


@pytest.mark.parametrize("engine,fold,want", [("pallas", 8, 8),
                                               ("bitset", 4, 1)])
def test_dispatch_run_counts_the_sweep_fold(trace_dir, engine, fold, want):
    """Every ``repro.dispatch.run`` carries ``sweep_fold``: the fold the
    orbit sweep kernel took, 1 where the rows were swept otherwise (here
    the jnp twin)."""
    n = 12 * fold
    orbits = search._circulant_orbits(n, 12, (2, 5))
    with jax.profiler.trace(trace_dir):
        res = search._replica_polish(
            n, 6, seed=3, n_iter=3, fold=fold, start_orbits=orbits,
            engine=engine, replicas=2, exchange_every=5)
    runs = repro_events(trace_dir)["repro.dispatch.run"]
    assert len(runs) == res.device_dispatches > 0
    assert [r[2] for r in runs] == [{"sweep_fold": want}] * len(runs)


@pytest.mark.parametrize("seed", [0, 4])
def test_replica_polish_tally(trace_dir, monkeypatch, seed):
    """At the benchmark cells' shape (4 replicas, 2 proposals each) on one
    device: the tally reads one column gather per iteration with a
    proposal and that gather's bytes, one placement call per placement
    program and exchange copy, and no chain state moved between devices."""
    n, fold, replicas, mprop, n_iter = 64, 4, 4, 2, 12
    calls: dict = {}
    _count_calls(monkeypatch, calls, batch=replicas * mprop)
    orbits = search._circulant_orbits(n, n // fold, (1, 2, 9))
    with jax.profiler.trace(trace_dir):
        res = search._replica_polish(
            n, 6, seed=seed, n_iter=n_iter, fold=fold, start_orbits=orbits,
            engine=None, replicas=replicas, exchange_every=5, resync_every=5,
            proposal_batch=mprop)
    [polish] = repro_events(trace_dir)["repro.polish"]
    [tally] = repro_events(trace_dir)["repro.polish.tally"]
    assert inside(tally, polish)
    kmax = 6
    width = 4 * fold * (1 + kmax)
    assert calls["batches"] > 0 and res.evals_delta > 0 and calls["copy"] > 0
    assert tally[2] == {
        "column_pulls": calls["batches"],
        "column_bytes": calls["batches"] * replicas * mprop * (n // fold)
        * width * 4,
        "place_calls": calls["place"] + calls["copy"],
        "state_moves": 0, "state_move_bytes": 0}


def test_circulant_hillclimb_tally(trace_dir):
    """The jax pricer's chunks lie inside ``repro.hillclimb``; the tally
    counts no more values consumed than rows priced, in whole chunks."""
    kw = dict(n=512, k=6, seed=3, n_iter=120)
    untraced = search.circulant_search(engine="jax", **kw)
    with jax.profiler.trace(trace_dir):
        traced = search.circulant_search(engine="jax", **kw)
    assert traced == untraced

    ev = repro_events(trace_dir)
    [hill] = ev["repro.hillclimb"]
    [tally] = ev["repro.hillclimb.tally"]
    examined, priced = tally[2]["examined"], tally[2]["priced_rows"]
    chunks = ev["repro.hillclimb.chunk"]
    assert 0 < examined <= priced
    assert priced % jax_circulant.CHUNK == 0
    assert priced == len(chunks) * jax_circulant.CHUNK
    assert count(ev, "repro.hillclimb.start") == max(1, kw["n_iter"] // 50)
    assert count(ev, "repro.hillclimb.finish") == 1
    assert all(inside(c, hill) for c in chunks)
    assert inside(tally, hill)


def test_rows_priced_counts_whole_chunks():
    chunk = jax_circulant.CHUNK
    assert jax_circulant.rows_priced("jax", 0) == 0
    assert jax_circulant.rows_priced("jax", 1) == chunk
    assert jax_circulant.rows_priced("jax", chunk) == chunk
    assert jax_circulant.rows_priced("jax", chunk + 1) == 2 * chunk
    assert jax_circulant.rows_priced("numpy", 7) == 7
