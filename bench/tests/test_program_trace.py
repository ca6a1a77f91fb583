"""The readers of the program's stage spans, on a small hand-made trace.

The trace, in ns, so that every number below can be checked by eye: a
window of 100 ns; device 0 busy in [10,20), [40,50) and [80,90), device 1
busy throughout; a hillclimb call with one chunk and its tally, then a
polish call of two iterations with their stages and one resync.  Device 0
is idle in [0,10), [20,40), [50,80) and [90,100): 70 ns, of which [0,8),
[22,30), [58,60), [64,65) and [95,100) lie under no stage span (24 ns).
"""
import shutil

import pytest

from bench import program_trace, run, trace
from bench.program_trace import Program
from bench.tests import tiny
from bench.trace import Event

OPS = {"/device:TPU:0": [Event("jit_sweep/while.1", 10, 20),
                         Event("jit_per_shard/sweep.1", 40, 50),
                         Event("jit_per_shard/sweep.1", 80, 90)],
       "/device:TPU:1": [Event("jit_per_shard/sweep.1", 0, 100)]}
SPANS = [  # name, start, end, stats
    ("repro.hillclimb", 0, 30, {}),
    ("repro.hillclimb.chunk", 8, 22, {}),
    ("repro.hillclimb.tally", 29, 29, {"examined": 40, "priced_rows": 64}),
    ("repro.polish", 30, 100, {"iterations": 2}),
    ("repro.polish.propose", 30, 36, {}),
    ("repro.dispatch.pack", 36, 38, {}),
    ("repro.dispatch.run", 38, 52, {}),
    ("repro.polish.accept", 52, 58, {}),
    ("repro.polish.pull", 53, 57, {}),
    ("repro.polish.propose", 60, 64, {}),
    ("repro.dispatch.pack", 65, 66, {}),
    ("repro.dispatch.run", 66, 70, {}),
    ("repro.polish.accept", 70, 76, {}),
    ("repro.polish.resync", 76, 95, {}),
    ("repro.dispatch.pack", 77, 79, {}),
    ("repro.dispatch.run", 79, 93, {}),
]


def ctx(spans=SPANS):
    t = trace.Trace(ops=dict(OPS), spans=[Event("bench.window", 0, 100)],
                    window=(0, 100))
    prog = Program([Event(n, a, b) for n, a, b, _ in spans],
                   [s for *_, s in spans])
    return {"trace": t, "program": prog}


def read(name, c):
    return run.load_reader(name)(c)


def test_stage_readers():
    c = ctx()
    # propose: 6 + 4 ns over 2 iterations
    assert read("propose_ms", c) == pytest.approx(5 / 1e6)
    # pack: 2 + 1 + 2 ns over 3 runs
    assert read("pack_ms", c) == pytest.approx(5 / 3 / 1e6)
    # pull: 4 ns over 2 iterations
    assert read("state_pull_ms", c) == pytest.approx(2 / 1e6)
    # one resync of 19 ns
    assert read("resync_ms", c) == pytest.approx(19 / 1e6)
    assert read("circulant_use_pct", c) == pytest.approx(62.5)


def test_idle_unexplained_counts_gaps_under_no_stage_span():
    c = ctx()
    assert read("idle_unexplained_pct", c) == pytest.approx(100 * 24 / 70)
    # the whole-call spans explain nothing
    c["program"] = Program([Event("repro.hillclimb", 0, 30),
                            Event("repro.polish", 30, 100)], [{}, {}])
    assert read("idle_unexplained_pct", c) == pytest.approx(100.0)


def test_a_program_without_spans():
    """The readers of a program that has no stage spans: the stage metrics
    read nothing; all of the device's idle time is unexplained."""
    c = ctx(spans=[])
    for name in ("propose_ms", "pack_ms", "state_pull_ms", "resync_ms",
                 "circulant_use_pct"):
        assert read(name, c) is None, name
    assert read("idle_unexplained_pct", c) == pytest.approx(100.0)
    c["trace"].ops = {}
    assert read("idle_unexplained_pct", c) is None


def test_overlap_of_sorted_intervals():
    from importlib import import_module

    mod = import_module("bench.metrics.idle_unexplained_pct")
    gaps = [(0, 10), (20, 40), (50, 80), (90, 100)]
    spans = [(8, 22), (30, 58), (60, 95)]
    assert mod.overlap_ns(gaps, spans) == 2 + 12 + 28 + 5
    assert mod.overlap_ns(gaps, []) == 0
    assert mod.overlap_ns([], spans) == 0


def test_split_by_stage():
    out = program_trace.split(ctx()["program"])
    assert out["repro.dispatch.run"]["calls"] == 3
    assert out["repro.polish.propose"]["ms_per_iteration"] == \
        pytest.approx(5 / 1e6)
    assert out["repro.polish.resync"]["ms_per_call"] == pytest.approx(19 / 1e6)


@pytest.fixture
def traced():
    yield tiny.cell(polish_iters=6, per_layer=[
        ("propose_ms", "ms"), ("pack_ms", "ms"), ("state_pull_ms", "ms"),
        ("resync_ms", "ms"), ("circulant_use_pct", "%")])
    shutil.rmtree(run.OUT / "trace" / "tiny", ignore_errors=True)


def test_traced_cpu_run_reads_the_program_spans(traced, capsys):
    """A tiny traced run: the readers find the program's spans in the
    trace it has just written, and the command-line summary reads them."""
    res = tiny.run(traced, trace=True)
    assert res["correct"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["propose_ms"] > 0 and m["pack_ms"] > 0 and m["resync_ms"] > 0
    assert m["state_pull_ms"] > 0
    # the numpy pricer at the tiny size prices exactly what is consumed
    assert m["circulant_use_pct"] == pytest.approx(100.0)
    assert program_trace.main([]) == 0
    out = capsys.readouterr().out
    assert '"repro.polish.propose"' in out and '"iterations"' in out
