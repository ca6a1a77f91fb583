"""The comparison that decides ``correct``.

Each checked job is replayed by the plain reference (``bench.reference``)
and its returned graph is recounted on its own.  Every number compared is
exact, so every limit is 0 (the readings they were set from are in
``PERF.md``):

* ``failed_jobs`` — jobs that raised instead of returning;
* ``mismatched_jobs`` — checked jobs whose accepted count, best-MPL history,
  returned edge set or circulant offsets differ from the replay;
* ``mpl_gap`` — the largest gap between a returned MPL and the replay's, or
  the recount of the returned graph;
* ``diameter_gap`` — the same for the diameter;
* ``structure_faults`` — returned graphs that are not k-regular, miss a
  ring edge or are not invariant under rotation by n / fold.
"""
from __future__ import annotations

from . import reference

LIMITS = {"failed_jobs": 0, "mismatched_jobs": 0, "mpl_gap": 0.0,
          "diameter_gap": 0.0, "structure_faults": 0}

_BFS: dict = {}


def dense_bfs(n: int, s: int) -> reference.DenseBFS:
    """One compiled reference BFS per (n, s) in this process, on device 0."""
    if (n, s) not in _BFS:
        import jax

        _BFS[(n, s)] = reference.DenseBFS(n, s, device=jax.devices()[0])
    return _BFS[(n, s)]


def expected(fields: dict, seed: int, precision: str = "float64"
             ) -> reference.Expected:
    """The replay of the job ``fields`` with ``seed``."""
    if fields.get("strategy") != "large":
        raise ValueError(f"the reference replays strategy 'large' only, "
                         f"not {fields.get('strategy')!r}")
    n, fold = fields["n"], fields["fold"]
    polish = fields.get("polish", True)
    bfs = dense_bfs(n, n // fold) if polish else None
    return reference.replay_large(
        n, fields["k"], seed, fields.get("budget"), fold, polish,
        fields["replicas"], fields.get("proposal_batch", 1),
        fields.get("polish_iters"), bfs, precision)


def observed(res) -> reference.Expected:
    """The compared fields of a ``SearchResult``."""
    return reference.Expected(
        mpl=float(res.mpl), diameter=float(res.diameter),
        accepted=int(res.accepted), history=[float(h) for h in res.history],
        edges=frozenset(tuple(e) for e in res.graph.edges),
        offsets=None if res.offsets is None else tuple(res.offsets))


def _gap(a: float, b: float) -> float:
    if a == b:
        return 0.0
    return abs(a - b)


def compare(fields: dict, got: reference.Expected,
            want: reference.Expected) -> dict:
    """Numbers of one job: its result against the replay, and its graph
    recounted alone."""
    n, k, fold = fields["n"], fields["k"], fields["fold"]
    g = reference.graph_checks(n, k, fold, got.edges,
                               dense_bfs(n, n // fold))
    same = (got.accepted == want.accepted and got.history == want.history
            and got.edges == want.edges
            and (want.offsets is None or got.offsets == want.offsets))
    return {"mismatched": not same,
            "mpl_gap": max(_gap(got.mpl, want.mpl), _gap(got.mpl, g["mpl"])),
            "diameter_gap": max(_gap(got.diameter, want.diameter),
                                _gap(got.diameter, g["diameter"])),
            "structure_faults": len(g["faults"]),
            "faults": g["faults"]}


def summarize(failed: int, per_job: list) -> dict:
    values = {
        "failed_jobs": failed,
        "mismatched_jobs": sum(int(j["mismatched"]) for j in per_job),
        "mpl_gap": max((j["mpl_gap"] for j in per_job), default=0.0),
        "diameter_gap": max((j["diameter_gap"] for j in per_job), default=0.0),
        "structure_faults": sum(j["structure_faults"] for j in per_job),
    }
    return {k: {"value": v, "limit": LIMITS[k]} for k, v in values.items()}


def check_jobs(fields: dict, jobs: list, checked: list) -> dict:
    failed = sum(1 for j in jobs if "result" not in j)
    per_job = [compare(fields, observed(j["result"]),
                       expected(fields, j["seed"])) for j in checked]
    out = summarize(failed, per_job)
    out["checked_jobs"] = {"value": len(checked), "limit": 1}
    return out


def passed(checks: dict) -> bool:
    """Every number within its limit; at least one job checked."""
    ok = all(c["value"] <= c["limit"] for k, c in checks.items()
             if k != "checked_jobs")
    return ok and checks["checked_jobs"]["value"] >= checks["checked_jobs"]["limit"]
