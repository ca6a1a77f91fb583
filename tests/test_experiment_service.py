"""Experiment-service tier: parallel run_experiment equivalence, the
hardened ``python -m repro.api`` CLI, and registry rejection paths.

The parallel path's contract is *bit-identity*: the process pool runs the
same ``_run_cell`` evaluator as the serial loop, so values (and provenance)
must serialize byte-identically — only the wall-clock timings may differ.
Checked across three paper suites × three workloads, on both a cold and a
warm spec-hash build cache.
"""
import json
import pathlib
import subprocess
import sys

import pytest

from repro import api
from repro.core import netsim, specs, topologies

ROOT = pathlib.Path(__file__).resolve().parent.parent

SUITES = ("16", "32", "dragonfly")
WORKLOADS = ("stats",
             ("alltoall", {"unit_bytes": 1 << 16}),
             "pingpong_mean")


def _canon(exp: api.ExperimentResult) -> str:
    """Everything but the timings, as canonical JSON bytes."""
    return json.dumps(
        {"names": exp.names, "values": exp.values,
         "provenance": exp.provenance(),
         "edges": {n: list(g.edges) for n, g in exp.graphs.items()},
         "table": exp.table()},
        sort_keys=True, default=api._json_default)


@pytest.mark.parametrize("suite", SUITES)
def test_parallel_matches_serial(suite, tmp_path):
    cache = str(tmp_path / "cache")
    # cache-cold serial run populates the spec-hash cache
    serial = api.run_experiment(api.paper_suite(suite), WORKLOADS,
                                cache_dir=cache, parallel=False)
    # cache-hit parallel run must be byte-identical (modulo timings)
    par_hit = api.run_experiment(api.paper_suite(suite), WORKLOADS,
                                 cache_dir=cache, parallel=True)
    # cache-cold parallel run (fresh dir) must also be byte-identical:
    # the searched builds re-run from scratch in-process
    par_cold = api.run_experiment(api.paper_suite(suite), WORKLOADS,
                                  cache_dir=str(tmp_path / "cold"),
                                  parallel=True)
    assert _canon(serial) == _canon(par_hit) == _canon(par_cold)
    # per-cell timing/provenance structure is preserved either way
    for exp in (serial, par_hit, par_cold):
        for n in exp.names:
            assert set(exp.seconds[n]) == set(exp.values[n])
            assert all(s >= 0 for s in exp.seconds[n].values())


def test_parallel_env_default(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_PARALLEL", "1")
    exp = api.run_experiment({"r": "ring:16", "t": "torus:4x4"},
                             ["stats", "pingpong_mean"])
    monkeypatch.setenv("REPRO_PARALLEL", "0")
    ser = api.run_experiment({"r": "ring:16", "t": "torus:4x4"},
                             ["stats", "pingpong_mean"])
    assert _canon(exp) == _canon(ser)


def test_parallel_falls_back_on_unpicklable_factory():
    captured = []

    def factory(g):  # a closure: unpicklable, forces the serial fallback
        captured.append(g.name)
        return netsim.TAISHAN(g)

    exp = api.run_experiment({"r": "ring:16", "t": "torus:4x4"},
                             ["pingpong_mean"], cluster_factory=factory,
                             parallel=True)
    assert captured  # the fallback ran the closure in-process
    assert set(exp.values) == {"r", "t"}


def test_parallel_refuses_once_an_accelerator_is_initialised(monkeypatch):
    """A process holding a chip must not fork workers; the request is
    refused, not quietly run serially."""
    assert not api._accelerator_in_use()  # the CPU backend never counts
    monkeypatch.setattr(api, "_accelerator_in_use", lambda: True)
    with pytest.raises(RuntimeError, match="accelerator"):
        api.run_experiment({"r": "ring:16", "t": "torus:4x4"}, ["stats"],
                           parallel=True)
    ser = api.run_experiment({"r": "ring:16", "t": "torus:4x4"}, ["stats"],
                             parallel=False)
    assert set(ser.values) == {"r", "t"}


def test_parallel_propagates_workload_errors():
    api.register_workload("test-raises",
                          lambda g, cl, **kw: (_ for _ in ()).throw(
                              RuntimeError("cell boom")))
    try:
        with pytest.raises(RuntimeError, match="cell boom"):
            api.run_experiment({"r": "ring:16", "t": "torus:4x4"},
                               ["test-raises"], parallel=True)
    finally:
        api._WORKLOADS.pop("test-raises")
        api.WORKLOADS = tuple(w for w in api.WORKLOADS if w != "test-raises")


# ------------------------------------------------------------------------------
# CLI subprocess tests: the hardened python -m repro.api
# ------------------------------------------------------------------------------

def _run_cli(*argv, cwd=ROOT):
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
           "JAX_PLATFORMS": "cpu", "HOME": "/tmp"}
    return subprocess.run([sys.executable, "-m", "repro.api", *argv],
                          capture_output=True, text=True, cwd=cwd, env=env)


def test_cli_subprocess_happy_path(tmp_path):
    sf = tmp_path / "spec.json"
    sf.write_text(json.dumps({
        "topologies": {"Ring": "ring:16", "Torus": "torus:4x4"},
        "workloads": ["stats", ["alltoall", {"unit_bytes": 65536}]],
        "parallel": True,
    }))
    out = tmp_path / "out.json"
    r = _run_cli(str(sf), "-o", str(out))
    assert r.returncode == 0, r.stderr
    d = json.loads(out.read_text())
    assert d["names"] == ["Ring", "Torus"]
    # GraphStats serializes as a field dict, not a repr string
    assert isinstance(d["values"]["Ring"]["stats"], dict)
    assert d["values"]["Ring"]["stats"]["diameter"] == 8
    assert not out.with_name("out.json.tmp").exists()  # atomic write


@pytest.mark.parametrize("spec,needle", [
    ({"topologys": {"Ring": "ring:16"}}, "topologys"),       # typo'd key
    ({"suite": "16", "workload": ["stats"]}, "workload"),    # singular typo
    ({"topologies": {"R": "ring:16"}, "workloads": ["nope"]}, "nope"),
    ({"suite": "no-such-suite"}, "no-such-suite"),
])
def test_cli_subprocess_rejects_malformed_spec(tmp_path, spec, needle):
    sf = tmp_path / "spec.json"
    sf.write_text(json.dumps(spec))
    out = tmp_path / "out.json"
    r = _run_cli(str(sf), "-o", str(out))
    assert r.returncode != 0
    assert needle in r.stderr  # the offending key is named
    assert not out.exists()  # no half-written table left behind


def test_cli_subprocess_rejects_unreadable_spec(tmp_path):
    bad = tmp_path / "nope.json"
    r = _run_cli(str(bad))
    assert r.returncode != 0 and "nope.json" in r.stderr
    bad.write_text("{not json")
    r = _run_cli(str(bad))
    assert r.returncode != 0 and "nope.json" in r.stderr


# ------------------------------------------------------------------------------
# Registry rejection paths
# ------------------------------------------------------------------------------

def test_traffic_time_rejects_unknown_pattern():
    cl = netsim.TAISHAN(api.build_topology("ring:16"))
    with pytest.raises(ValueError, match="unknown traffic pattern"):
        netsim.traffic_time(cl, "no-such-pattern", 1 << 16)


def test_run_experiment_rejects_unknown_workload():
    with pytest.raises(ValueError, match="no-such-workload"):
        api.run_experiment({"r": "ring:16"}, ["no-such-workload"])


def test_duplicate_topology_family_rejected():
    build = lambda s: api.build_topology("ring:16")  # noqa: E731
    topologies.register_topology("test-dup-family", build)
    try:
        with pytest.raises(ValueError, match="already registered"):
            topologies.register_topology("test-dup-family", build)
        # replace=True is the explicit escape hatch
        topologies.register_topology("test-dup-family", build, replace=True)
        with pytest.raises(ValueError, match="already registered"):
            topologies.register_topology("ring", build)  # built-ins guarded too
    finally:
        topologies._REGISTRY.pop("test-dup-family")
        topologies.FAMILIES = tuple(
            f for f in topologies.FAMILIES if f != "test-dup-family")


def test_duplicate_objective_rejected():
    run = specs._run_pinned
    specs.register_objective("test-dup-objective", run)
    try:
        with pytest.raises(ValueError, match="already registered"):
            specs.register_objective("test-dup-objective", run)
        specs.register_objective("test-dup-objective", run, replace=True)
    finally:
        specs._OBJECTIVES.pop("test-dup-objective")
        specs.OBJECTIVES = tuple(
            o for o in specs.OBJECTIVES if o != "test-dup-objective")


def test_duplicate_strategy_and_workload_rejected():
    with pytest.raises(ValueError, match="already registered"):
        specs.register_strategy("sa", specs._run_sa)
    with pytest.raises(ValueError, match="already registered"):
        api.register_workload("stats", lambda g, cl, **kw: None)
