"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A trace is loaded once into a plain ``Trace``: the device operations of each
device (name, start, end in ns, on the profiler's common clock), the
benchmark's host spans (``bench.*`` annotations) and the traced window.
Everything below works on that plain form, so the arithmetic is tested on a
small recorded trace without a chip (``bench/tests``).
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
# the device plane's line whose events are single operations (each HLO op,
# each Pallas kernel), and the line of the programs they belong to
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass(frozen=True)
class Event:
    name: str
    start: int  # ns
    end: int  # ns

    @property
    def dur(self) -> int:
        return self.end - self.start


@dataclass
class Trace:
    ops: dict = field(default_factory=dict)  # device name -> [Event]
    spans: list = field(default_factory=list)  # [Event], bench.* only
    window: tuple = (0, 0)  # (start, end) ns of the bench.window span

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


def op_name(hlo: str) -> str:
    """``%sweep.1 = s32[...] custom-call(...)`` -> ``sweep.1``: the trace
    names each op by its whole HLO line."""
    head = hlo.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def module_name(event_name: str) -> str:
    """``jit_per_shard(1770...)`` -> ``jit_per_shard``."""
    return event_name.split("(", 1)[0]


def top_level(events) -> list:
    """Drop ops that run inside another op (the body of a ``while``), so
    that no time is counted twice; events sorted by start."""
    out: list = []
    for e in events:
        if out and e.start >= out[-1].start and e.end <= out[-1].end:
            continue
        out.append(e)
    return out


def _device_ops(plane) -> list:
    modules, ops = [], []
    for line in plane.lines:
        evs = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
               for e in line.events]
        if line.name == MODULES_LINE:
            modules = sorted((s, t, module_name(n)) for n, s, t in evs)
        elif line.name == OPS_LINE:
            ops = sorted(evs, key=lambda e: (e[1], -e[2]))
    out, i = [], 0
    for name, start, end in ops:
        while i + 1 < len(modules) and modules[i + 1][0] <= start:
            i += 1
        mod = (modules[i][2] if modules and modules[i][0] <= start < modules[i][1]
               else "?")
        out.append(Event(f"{mod}/{op_name(name)}", start, end))
    return top_level(out)


def load_xplane(path: str) -> Trace:
    """Read one ``.xplane.pb``: the top-level device ops of every
    ``/device:`` plane, named ``<program>/<op>`` (``jit_per_shard/sweep.1``),
    and the ``bench.*`` spans of the host planes."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops = _device_ops(plane)
            if ops:
                tr.ops[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        tr.spans.append(Event(
                            e.name, int(e.start_ns),
                            int(e.start_ns + e.duration_ns)))
    tr.spans.sort(key=lambda e: (e.start, -e.end))
    windows = [s for s in tr.spans if s.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(windows)} in {path}")
    tr.window = (windows[0].start, windows[0].end)
    return tr


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def clip(events, lo: int, hi: int) -> list:
    """Events cut to [lo, hi); those outside vanish."""
    out = []
    for e in events:
        a, b = max(e.start, lo), min(e.end, hi)
        if b > a:
            out.append(Event(e.name, a, b))
    return out


def merge(events) -> list:
    """Union of intervals as sorted disjoint (start, end) pairs."""
    out: list = []
    for e in sorted(events, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end)
        else:
            out.append([e.start, e.end])
    return [(a, b) for a, b in out]


def busy_ns(events, lo: int, hi: int) -> int:
    """Length of the union of ``events`` inside [lo, hi)."""
    return sum(b - a for a, b in merge(clip(events, lo, hi)))


def gaps(events, lo: int, hi: int) -> list:
    """Idle intervals in [lo, hi): where no event runs."""
    out = []
    t = lo
    for a, b in merge(clip(events, lo, hi)):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def open_span(spans, t: int) -> str:
    """Innermost benchmark span open at time ``t`` (the one that started
    last among those that contain it); ``"none"`` outside every span."""
    best = None
    for s in spans:
        if s.start <= t < s.end and (best is None or s.start >= best.start):
            best = s
    return best.name if best is not None else "none"


def op_seconds(events, lo: int, hi: int, match=None) -> dict:
    """Seconds per op name inside [lo, hi), optionally only names for which
    ``match(name)`` is true."""
    out: dict = {}
    for e in clip(events, lo, hi):
        if match is None or match(e.name):
            out[e.name] = out.get(e.name, 0) + e.dur
    return {k: v / 1e9 for k, v in out.items()}


def busy_s(tr: Trace) -> float:
    """Device busy seconds in the window, averaged over the traced devices."""
    if not tr.ops:
        return 0.0
    lo, hi = tr.window
    return sum(busy_ns(ev, lo, hi) for ev in tr.ops.values()) / len(tr.ops) / 1e9


def breakdown(tr: Trace, top: int = 10) -> dict:
    """The device ops that took most time (summed over devices and over
    instances of one name) and the longest idle gaps over all traced
    devices, each gap named by the benchmark span open on the host at its
    midpoint."""
    lo, hi = tr.window
    per_name: dict = {}
    for ev in tr.ops.values():
        for name, sec in op_seconds(ev, lo, hi).items():
            per_name[name] = per_name.get(name, 0.0) + sec
    ops = sorted(per_name.items(), key=lambda kv: -kv[1])[:top]
    all_gaps = []
    for ev in tr.ops.values():
        all_gaps.extend(gaps(ev, lo, hi))
    all_gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[open_span(tr.spans, (a + b) // 2), (b - a) / 1e9]
            for a, b in all_gaps[:top]]
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": idle}
