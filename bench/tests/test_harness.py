"""The harness's refusals, its data-driven layout and a traced CPU run."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import run, traffic
from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]


def _cli(cwd, *extra, env=None):
    env = dict(os.environ if env is None else env, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "polish.n8192_k8",
         "--seed", "3", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_exits_nonzero_without_a_tpu():
    out = _cli(ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert "{" not in out.stdout


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    out = _cli(tmp_path)
    assert out.returncode != 0
    assert "{" not in out.stdout


def test_unknown_device_kind_is_an_error():
    with pytest.raises(run.BenchError, match="no peaks"):
        run.load_peaks("TPU v99 imaginary")
    assert run.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("var", run.REFUSED_ENV)
def test_engine_overrides_are_refused(var):
    with pytest.raises(run.BenchError, match=var):
        run.check_env({var: "1"})
    run.check_env({var: ""})


def test_every_cell_resolves_from_its_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = run.load_cell(w["name"])
        fields = traffic.job_fields(cell["config"], cell["traffic"])
        assert fields["n"] % fields["fold"] == 0
        assert cell["config"]["chips"] == w["chips"]
        assert {m["name"] for m in cell["end_to_end"]} >= {"search_s",
                                                           "setup_s"}
        for m in cell["per_layer"]:
            assert callable(run.load_reader(m["name"]))
        traffic.validate(cell["traffic"])


def test_job_seeds_are_one_fixed_sequence_that_never_hits_zero():
    t = run.load_cell("polish.n8192_k8")["traffic"]
    a = [s for s, _ in zip(traffic.job_seeds(t), range(200))]
    b = [s for s, _ in zip(traffic.job_seeds(t), range(200))]
    assert a == b and len(set(a)) == 200
    assert 0 not in a and t["warmup"]["seed"] not in a


def test_warm_up_job_is_the_cells_spec_shortened():
    cell = run.load_cell("polish.n8192_k8")
    fields = traffic.job_fields(cell["config"], cell["traffic"])
    warm, seed = traffic.warmup_fields(fields, cell["traffic"])
    assert seed == cell["traffic"]["warmup"]["seed"]
    assert warm["polish_iters"] < fields["polish_iters"]
    assert {k: v for k, v in warm.items() if k not in ("budget",
                                                       "polish_iters")} == \
        {k: v for k, v in fields.items() if k not in ("budget",
                                                      "polish_iters")}


@pytest.mark.parametrize("cell,drives", [("polish.n8192_k8", True),
                                         ("polish.n4096_k6", True),
                                         ("hillclimb.n8192_k8", False)])
def test_delta_dispatch_is_warmed_where_the_jobs_polish(cell, drives):
    c = run.load_cell(cell)
    fields = traffic.job_fields(c["config"], c["traffic"])
    assert traffic.drives_delta_dispatch(fields) is drives
    assert not traffic.drives_delta_dispatch({**fields, "replicas": 1})


def test_warm_buckets_cover_every_lane_bucket():
    from bench import warm
    from repro.kernels import bfs_sweep

    fields = {"n": 8192, "k": 8, "fold": 8}
    buckets = warm.delta_buckets(fields)
    lanes = {bfs_sweep.source_lanes(m) for m, _ in buckets}
    assert lanes == {bfs_sweep.source_lanes(m) for m in range(1, 1025)}
    assert (1024, None) in buckets
    patch = buckets[0][1]
    assert len(patch) == 16 and len({x for e in patch for x in e}) == 32


@pytest.fixture
def traced():
    """A short tiny cell for traced runs (an interpret-mode trace of a full
    tiny job runs to hundreds of MB); its trace is removed afterwards."""
    yield tiny.cell(polish_iters=6)
    shutil.rmtree(run.OUT / "trace" / "tiny", ignore_errors=True)


def test_a_listed_metric_that_reads_nothing_fails_the_run(traced):
    # no device kernel and no peaks on the CPU: the roofline reads nothing
    traced["per_layer"].append({"name": "sweep_roofline", "unit": "%"})
    with pytest.raises(run.BenchError, match="sweep_roofline"):
        tiny.run(traced, trace=True)


def test_traced_cpu_run_reads_the_span_metrics(traced):
    res = tiny.run(traced, trace=True)
    assert res["correct"]
    m = res["metrics"]
    assert m["polish_host_ms"]["value"] > 0 and m["dispatch_ms"]["value"] > 0
    assert "search_s" not in m
    assert set(res["device"]) >= {"busy_s", "window_s", "memory_peak_bytes"}
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(res)[-1] == "checks"
