"""Run one benchmark cell once on the accelerator this process finds.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout that holds ``BENCHMARK.json``, ``bench/`` and the
program under ``src/``.  The cell names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); both are found through
``BENCHMARK.json``, so a new cell needs only new data files.

Set-up (from process start to the window): check the environment and the
chip, turn on JAX's persistent compilation cache, run the traffic's
warm-up job (the cell's spec, shortened, with a seed of its own) and, where
the jobs polish through the replica dispatch, drive that dispatch once per
shape bucket (``bench.warm``), so that no program the cell's jobs use
compiles inside the window.  The window: a closed loop of
``repro.api.search`` jobs, the next started when the last returns, for as
long as less than ``--seconds`` has elapsed; it runs from the first job's
start to the last job's end.  Then
``memory_peak_bytes`` is read and the plain reference (``bench.reference``)
replays the checked jobs.

With ``--trace 0`` the metrics are the end-to-end ones (``search_s``, the
window over the jobs completed in it, and ``setup_s``).  With ``--trace 1``
the window runs under the JAX profiler with the span wrappers of
``bench.spans`` installed, and the metrics are the per-layer ones, each read
by ``bench/metrics/<metric>.py`` from the trace and the counters.  A
per-layer metric that ``BENCHMARK.json`` lists for the cell and that reads
nothing (a span wrapper that never fired, a kernel name that no longer
matches) fails the run: it prints no result.

The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, ``breakdown`` when traced, ``checks``
last); the last lines of stderr repeat each compared number beside its
limit.  Without a TPU, with fewer or more chips than the cell asks for, or
with ``REPRO_ENGINE`` / ``REPRO_PALLAS_INTERPRET`` set, it exits non-zero and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
OUT = BENCH / "out"
CACHE_DIR = OUT / "jax_cache"
REFUSED_ENV = ("REPRO_ENGINE", "REPRO_PALLAS_INTERPRET")
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# jobs of a run that the reference replays, the longest among them: enough
# to cover the window's jobs where each is long, and a replay that stays
# shorter than the window where they are many
CHECK_JOBS = 4


class BenchError(RuntimeError):
    """A run that must not print a result."""


# --------------------------------------------------------------------------
# The cell, from BENCHMARK.json and the data files it names
# --------------------------------------------------------------------------

def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"no BENCHMARK.json at {root}")
    bench = json.loads(path.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return {"name": name, "chips": cell["chips"], "config": config,
            "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if _applies(m, name)],
            "per_layer": [m for m in bench["per_layer"] if _applies(m, name)]}


def load_peaks(kind: str, root: Path = ROOT) -> dict:
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise BenchError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table["devices"][kind]


def load_reader(name: str, root: Path = ROOT):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --------------------------------------------------------------------------
# Environment, device and compilation
# --------------------------------------------------------------------------

def check_env(environ=os.environ) -> None:
    bad = [k for k in REFUSED_ENV if environ.get(k, "") != ""]
    if bad:
        raise BenchError(f"{', '.join(bad)} set: it changes what runs; unset it")


def find_program(root: Path = ROOT) -> None:
    src = root / "src"
    if not (src / "repro" / "api.py").is_file():
        raise BenchError(f"no program under {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def enable_cache(jax) -> str:
    """JAX_COMPILATION_CACHE_DIR when set, else a fixed directory inside
    the checkout; every compile is cached, however short."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def find_devices(jax, chips: int, require_tpu: bool = True) -> list:
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) != chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    return devs


class CompileCounter:
    """Counts, through JAX's monitoring events, the programs lowered (each
    new program in this process) and compiled or loaded from the
    persistent cache, with the names of those lowered."""

    def __init__(self):
        import jax

        self.lowered = self.compiled = 0
        self.names: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == LOWER_EVENT:
            self.lowered += 1
            self.names.append(str(kw.get("fun_name", "?")))
        elif event == COMPILE_EVENT:
            self.compiled += 1


# --------------------------------------------------------------------------
# Set-up, window, check
# --------------------------------------------------------------------------

def warm_up(fields: dict, traffic: dict):
    from bench import traffic as gen
    from repro import api

    warm, seed = gen.warmup_fields(fields, traffic)
    return api.search(gen.spec(warm, seed))


def run_window(fields: dict, traffic: dict, seconds: float) -> tuple:
    """Closed loop of jobs; returns (jobs, window seconds)."""
    import jax

    from bench import traffic as gen
    from repro import api

    ann = jax.profiler.TraceAnnotation
    jobs = []
    seeds = gen.job_seeds(traffic)
    with ann("bench.window"):
        t0 = time.perf_counter()
        while not jobs or time.perf_counter() - t0 < seconds:
            job = {"seed": next(seeds), "start": time.perf_counter()}
            try:
                with ann("bench.job"):
                    job["result"] = api.search(gen.spec(fields, job["seed"]))
            except Exception as e:  # noqa: BLE001 - a failed job is counted
                job["error"] = f"{type(e).__name__}: {e}"
            job["end"] = time.perf_counter()
            jobs.append(job)
        t1 = time.perf_counter()
    return jobs, t1 - t0


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def choose_checked(jobs: list, seed: int, count: int) -> list:
    """The longest completed job and, drawn from the seed, others up to
    ``count``."""
    import numpy as np

    done = [j for j in jobs if "result" in j]
    if len(done) <= count:
        return done
    longest = max(done, key=lambda j: j["end"] - j["start"])
    rest = [j for j in done if j is not longest]
    rng = np.random.default_rng([int(seed) % (1 << 64), 0xC4EC])
    pick = rng.choice(len(rest), size=count - 1, replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


# --------------------------------------------------------------------------
# One run
# --------------------------------------------------------------------------

def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, t_start: float | None = None) -> dict:
    """Set up, run the window, check; returns the result object."""
    import jax

    from bench import check, spans, traffic as gen
    from bench import trace as tr

    t_start = T_START if t_start is None else t_start
    check_env()
    find_program()
    devices = find_devices(jax, cell["chips"], require_tpu)
    kind = devices[0].device_kind
    peaks = {}
    if require_tpu:
        peaks = load_peaks(kind)
        enable_cache(jax)
    config, traffic = cell["config"], cell["traffic"]
    gen.validate(traffic)
    fields = gen.job_fields(config, traffic)
    counter = CompileCounter()

    warm_up(fields, traffic)
    if gen.drives_delta_dispatch(fields):
        from bench import warm

        warm.warm_delta(fields)
    if require_tpu and fields.get("engine") == "pallas":
        from repro.core.engines import pallas_sweep

        if pallas_sweep.get_interpret():
            raise BenchError("the Pallas kernels would run in interpret mode")
    setup_s = time.perf_counter() - t_start

    lowered0, compiled0 = counter.lowered, counter.compiled
    names0 = len(counter.names)
    recorder = spans.Recorder()
    if trace:
        tdir = OUT / "trace" / cell["name"]
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
        try:
            with recorder.installed():
                jobs, window_s = run_window(fields, traffic, seconds)
        finally:
            jax.profiler.stop_trace()
    else:
        jobs, window_s = run_window(fields, traffic, seconds)
    in_window = {"lowered": counter.lowered - lowered0,
                 "compiled": counter.compiled - compiled0,
                 "names": counter.names[names0:]}
    done = [j for j in jobs if "result" in j]

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory_peak(devices)}
    metrics: dict = {}
    breakdown = None
    if trace:
        t = tr.load_xplane(tr.find_xplane(str(tdir)))
        ctx = {"trace": t, "counters": recorder.counters, "peaks": peaks,
               "config": config, "traffic": traffic}
        silent = []
        for m in cell["per_layer"]:
            value = load_reader(m["name"])(ctx)
            if value is None:
                silent.append(m["name"])
            else:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if silent:
            raise BenchError(
                f"per-layer metrics listed for {cell['name']} read nothing: "
                f"{', '.join(silent)} (counters {recorder.counters})")
        device["busy_s"] = tr.busy_s(t)
        device["window_s"] = t.window_s
        breakdown = tr.breakdown(t)
    else:
        units = {m["name"]: m["unit"] for m in cell["end_to_end"]}
        if done:
            metrics["search_s"] = {"value": window_s / len(done),
                                   "unit": units["search_s"]}
        metrics["setup_s"] = {"value": setup_s, "unit": units["setup_s"]}

    checked = choose_checked(jobs, seed, CHECK_JOBS)
    checks = check.check_jobs(fields, jobs, checked)
    result = {"correct": check.passed(checks) and bool(done),
              "attempted": len(jobs), "failed": len(jobs) - len(done),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["window"] = {"seconds": window_s, "jobs_completed": len(done),
                        "job_seconds": [j["end"] - j["start"] for j in jobs],
                        "job_seeds": [j["seed"] for j in jobs],
                        "lowered_in_window": in_window["lowered"],
                        "lowered_in_window_names": in_window["names"],
                        "compiled_in_window": in_window["compiled"]}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    try:
        cell = load_cell(args.workload)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    w = result["window"]
    print(f"window: {w['jobs_completed']} jobs in {w['seconds']} s; "
          f"lowered in window {w['lowered_in_window']}, compiled "
          f"{w['compiled_in_window']}", flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
