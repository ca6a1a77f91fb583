"""The program's own stage spans (``repro.*``), read from a traced run.

The search marks its stages with ``repro.obs`` spans: ``repro.polish`` and
``repro.hillclimb`` around whole calls, and inside them the stages
(``repro.polish.propose``, ``repro.dispatch.run``, ``repro.hillclimb.chunk``
and the rest), some with counts as event stats (``iterations`` on
``repro.polish``; ``examined`` and ``priced_rows`` on the
``repro.hillclimb.tally`` mark).  This module loads those host events, with
their stats, from the ``.xplane.pb`` the traced run has just written (the
newest under ``bench/out/trace``), once per run, for the readers under
``bench/metrics`` that take their numbers from them.

    python3 -m bench.program_trace [--xplane PATH]

prints one JSON object: the readers' values and the split of the traced
jobs by stage (milliseconds per polish iteration and per call of each span),
for the newest trace or the one named.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
from dataclasses import dataclass, field
from pathlib import Path

from bench.trace import Event

PREFIX = "repro."
# spans that enclose whole calls: the stages lie inside them
WHOLE_CALLS = frozenset({"repro.polish", "repro.hillclimb"})
TRACE_DIR = Path(__file__).resolve().parent / "out" / "trace"
READERS = ("propose_ms", "pack_ms", "state_pull_ms", "resync_ms",
           "circulant_use_pct", "idle_unexplained_pct")


@dataclass
class Program:
    events: list = field(default_factory=list)  # [Event], sorted by start
    stats: list = field(default_factory=list)  # {stat: int} per event

    def named(self, name: str) -> list:
        return [e for e in self.events if e.name == name]

    def stat(self, name: str, key: str) -> int:
        """Sum of stat ``key`` over the events called ``name``."""
        return sum(int(s.get(key, 0)) for e, s in zip(self.events, self.stats)
                   if e.name == name)

    def stages(self) -> list:
        """Every span but the whole-call ones."""
        return [e for e in self.events if e.name not in WHOLE_CALLS]


def load(path: str) -> Program:
    """The ``repro.*`` events of the host planes of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    found = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    start = int(e.start_ns)
                    found.append((Event(e.name, start,
                                        start + int(e.duration_ns)),
                                  {k: v for k, v in e.stats}))
    found.sort(key=lambda p: (p[0].start, -p[0].end))
    return Program([e for e, _ in found], [s for _, s in found])


def newest(trace_dir: Path = TRACE_DIR) -> str | None:
    paths = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def of(ctx: dict) -> Program | None:
    """The program's spans for a reader: ``ctx["program"]``, loaded from
    the newest trace by the first reader that asks and kept there for the
    others, which share one ``ctx`` in a run."""
    if "program" not in ctx:
        path = newest()
        ctx["program"] = load(path) if path else None
    return ctx["program"]


def split(prog: Program) -> dict:
    """Per span name: calls, total ms, ms per call and ms per polish
    iteration."""
    iters = prog.stat("repro.polish", "iterations")
    out = {}
    for name in sorted({e.name for e in prog.events}):
        evs = prog.named(name)
        total = sum(e.dur for e in evs) / 1e6
        out[name] = {"calls": len(evs), "total_ms": total,
                     "ms_per_call": total / len(evs),
                     "ms_per_iteration": total / iters if iters else None}
    return out


def main(argv=None) -> int:
    from bench import run, trace

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--xplane", help="a .xplane.pb (default: the newest "
                                    "under bench/out/trace)")
    args = p.parse_args(argv)
    path = args.xplane or newest()
    if path is None:
        print(f"program_trace: no trace under {TRACE_DIR}")
        return 1
    prog = load(path)
    ctx = {"trace": trace.load_xplane(path), "program": prog}
    values = {name: run.load_reader(name)(ctx) for name in READERS}
    tally = {k: prog.stat("repro.hillclimb.tally", k)
             for k in ("examined", "priced_rows")}
    print(json.dumps({"xplane": path, "metrics": values, "tally": tally,
                      "iterations": prog.stat("repro.polish", "iterations"),
                      "split": split(prog)}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
