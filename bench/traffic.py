"""The one traffic generator: a closed loop of ``repro.api.search`` jobs.

A job's spec is the configuration's sizes and search settings (``n``, ``k``,
``fold``, ``replicas``, and ``budget`` and ``polish_iters`` where it states
them) under the traffic's shared fields.  A traffic mix is a data file,
``bench/traffic/<name>.json``:

* ``job`` — the ``SearchSpec`` fields every job shares (strategy, engine,
  and tier parameters such as ``proposal_batch`` or ``polish``);
* ``seed_range`` — ``[lo, hi)`` of the spec seeds jobs draw, never
  containing 0 (seed 0 takes pinned offsets and skips the hillclimb, a
  different job);
* ``sequence_seed`` — the seed of the one fixed sequence of spec seeds that
  every run sends, in the same order;
* ``warmup`` — the one set-up job: its ``seed``, outside ``seed_range`` so
  that no timed job repeats it, and the spec fields it shortens (a smaller
  ``budget`` or ``polish_iters`` runs every program of the full job).

One client sends the jobs: the next starts when the last has returned.

A job's spec seed sets its trajectory, and so how much work the job does:
on one TPU v5e chip, polish jobs of 16 iterations at n = 8192 took 8.5 to
13 s by seed.  A run sends the prefix of one fixed sequence that fits its
window, so every run does the same work whatever its ``--seed``; with job
seeds drawn from ``--seed``, runs of different seeds spread ten times wider
than two runs of one seed.  ``--seed`` draws which of the window's jobs the
reference replays.
"""
from __future__ import annotations

import numpy as np

SIZE_KEYS = ("n", "k", "fold", "replicas")
SEARCH_KEYS = ("budget", "polish_iters")


def job_fields(config: dict, traffic: dict) -> dict:
    """Spec fields of one job, less its seed."""
    fields = {key: config[key] for key in SIZE_KEYS}
    fields.update({key: config[key] for key in SEARCH_KEYS if key in config})
    fields.update(traffic["job"])
    return fields


def warmup_fields(fields: dict, traffic: dict) -> tuple[dict, int]:
    """Spec fields and seed of the set-up job."""
    warm = dict(traffic["warmup"])
    seed = warm.pop("seed")
    return {**fields, **warm}, seed


def validate(traffic: dict) -> None:
    lo, hi = traffic["seed_range"]
    if not 0 < lo < hi:
        raise ValueError(f"seed_range {traffic['seed_range']} must exclude 0")
    if lo <= traffic["warmup"]["seed"] < hi:
        raise ValueError("the warm-up seed must lie outside seed_range")


def job_seeds(traffic: dict):
    """The endless fixed sequence of spec seeds every run sends."""
    lo, hi = traffic["seed_range"]
    rng = np.random.default_rng(traffic["sequence_seed"])
    while True:
        yield int(rng.integers(lo, hi))


def drives_delta_dispatch(fields: dict) -> bool:
    """Whether the job's polish prices through the replica dispatch
    (``pallas_sweep.sharded_delta_state``): a delta-priced polish stage
    with more than one replica."""
    return (fields.get("polish", True) is not False
            and fields.get("delta", True) is not False
            and fields["replicas"] > 1)


def spec(fields: dict, seed: int):
    from repro.api import SearchSpec

    f = dict(fields)
    return SearchSpec.make(f.pop("n"), f.pop("k"), seed=seed, **f)
