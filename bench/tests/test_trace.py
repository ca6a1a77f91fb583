"""The trace reduction on a small recorded trace.

``trace_small.json`` is a hand-made trace in the form ``load_xplane``
produces, with the op names a traced polish run shows, in small numbers so
that every sum below can be checked by eye: two devices, a window of 100 ns,
benchmark spans nested job > polish > dispatch.
"""
import json
from pathlib import Path

from bench import trace
from bench.trace import Event

DATA = Path(__file__).with_name("trace_small.json")


def load():
    d = json.loads(DATA.read_text())
    tr = trace.Trace()
    for dev, evs in d["ops"].items():
        tr.ops[dev] = [Event(*e) for e in evs]
    tr.spans = [Event(*e) for e in d["spans"]]
    w = [s for s in tr.spans if s.name == trace.WINDOW_SPAN][0]
    tr.window = (w.start, w.end)
    return tr


def test_busy_union_merges_overlaps_and_clips_to_the_window():
    tr = load()
    lo, hi = tr.window
    # device 0: [0,10) [5,20) overlap -> 20; [40,50) -> 10; [95,110) clipped
    # to [95,100) -> 5; [-10,2) clipped to [0,2) inside [0,20) already
    assert trace.busy_ns(tr.ops["/device:TPU:0"], lo, hi) == 35
    # device 1: [10,30) and [60,70)
    assert trace.busy_ns(tr.ops["/device:TPU:1"], lo, hi) == 30
    assert trace.busy_s(tr) == (35 + 30) / 2 / 1e9


def test_idle_gaps_and_their_labels():
    tr = load()
    lo, hi = tr.window
    assert trace.gaps(tr.ops["/device:TPU:0"], lo, hi) == [(20, 40), (50, 95)]
    # the gap [20,40) has its midpoint in the polish span [15,60) but
    # outside the dispatch [40,52); [50,95) has its midpoint 72 in the job
    # span only
    assert trace.open_span(tr.spans, 30) == "bench.polish"
    assert trace.open_span(tr.spans, 72) == "bench.job"
    assert trace.open_span(tr.spans, 45) == "bench.dispatch"
    assert trace.open_span(tr.spans, 150) == "none"


def test_kernel_time_sums_by_name():
    tr = load()
    lo, hi = tr.window
    secs = trace.op_seconds(tr.ops["/device:TPU:0"], lo, hi,
                            lambda n: n.endswith("/sweep.1"))
    assert secs == {"jit_per_shard/sweep.1": (10 + 15) / 1e9}


def test_breakdown_lists_ops_and_gaps_longest_first():
    tr = load()
    b = trace.breakdown(tr, top=3)
    names = [n for n, _ in b["device_ops"]]
    assert names[0] == "jit_per_shard/sweep.1"  # 25 ns on device 0
    gaps = [g for _, g in b["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert b["idle_gaps"][0] == ["bench.job", 45 / 1e9]


class _Ev:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, lines):
        self.lines = lines


def test_device_ops_are_named_by_program_and_nested_ops_dropped():
    # the form of a chip trace: whole HLO lines as op names, programs on
    # their own line, a while loop's body ops inside the while op
    plane = _Plane([
        _Line("XLA Modules", [_Ev("jit_sweep(1234)", 0, 50),
                              _Ev("jit_per_shard(99)", 60, 40)]),
        _Line("XLA Ops", [
            _Ev("%while.1 = (s32[], pred[32,8192]) while(...)", 0, 50),
            _Ev("%fusion.43 = pred[262144] fusion(...)", 5, 10),
            _Ev("%sweep.1 = s32[8,8,8192,128] custom-call(...), "
                "custom_call_target=\"tpu_custom_call\"", 60, 30),
            _Ev("%per_shard.1 = s32[8,1024,8192] custom-call(...)", 92, 5)]),
    ])
    ops = trace._device_ops(plane)
    assert [(e.name, e.start, e.end) for e in ops] == [
        ("jit_sweep/while.1", 0, 50),
        ("jit_per_shard/sweep.1", 60, 90),
        ("jit_per_shard/per_shard.1", 92, 97)]


def test_roofline_readers_on_the_small_trace():
    from bench import run

    ctx = {"trace": load(), "peaks": {"hbm_bytes_per_s": 819e9},
           "counters": {"sweep_bytes": 819, "patch_bytes": 819}}
    # 819 bytes at 819 GB/s is 1 ns; the sweep ran 25 ns and the patch 5 ns
    # of the window
    assert abs(run.load_reader("sweep_roofline")(ctx) - 4.0) < 1e-9
    assert abs(run.load_reader("patch_roofline")(ctx) - 20.0) < 1e-9
    ctx["counters"] = {}
    assert run.load_reader("sweep_roofline")(ctx) is None
