"""Readings that the limits of ``bench.check`` are set from, on the chip.

    python3 -m bench.control --workload <cell> --seeds 11 12 13 ... [--jobs 1]

For each seed, in one process: ``--jobs`` jobs with spec seeds drawn from
that seed (not the timed runs' fixed sequence, so that the readings cover
other trajectories) run through ``repro.api.search`` at the cell's own size (the timed
path), each compared with the float64 replay (the program's readings); then
the control — the same replay with MPLs in float32 — is compared with the
float64 replay in the program's place.  One JSON line per seed, then a
summary line with the largest reading of each number on each side.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--jobs", type=int, default=1)
    args = p.parse_args(argv)

    from bench import run

    try:
        cell = run.load_cell(args.workload)
        run.check_env()
        run.find_program()
        import jax

        run.find_devices(jax, cell["chips"])
        run.enable_cache(jax)
    except run.BenchError as e:
        print(f"bench.control: {e}", file=sys.stderr)
        return 2
    from bench import check, traffic as gen
    from repro import api

    fields = gen.job_fields(cell["config"], cell["traffic"])
    worst = {"program": {}, "control": {}}
    for seed in args.seeds:
        lo, hi = cell["traffic"]["seed_range"]
        rng = np.random.default_rng([seed % (1 << 64), 0xC0])
        for _ in range(args.jobs):
            job_seed = int(rng.integers(lo, hi))
            t0 = time.perf_counter()
            res = api.search(gen.spec(fields, job_seed))
            t1 = time.perf_counter()
            want = check.expected(fields, job_seed)
            t2 = time.perf_counter()
            ctl = check.expected(fields, job_seed, "float32")
            readings = {
                "program": check.summarize(0, [check.compare(
                    fields, check.observed(res), want)]),
                "control": check.summarize(0, [check.compare(
                    fields, ctl, want)])}
            for side, nums in readings.items():
                for k, v in nums.items():
                    worst[side][k] = max(worst[side].get(k, 0), v["value"])
            print(json.dumps({
                "seed": seed, "job_seed": job_seed, "job_s": t1 - t0,
                "reference_s": t2 - t1, "mpl": float(res.mpl),
                "accepted": res.accepted,
                "program": {k: v["value"] for k, v in readings["program"].items()},
                "control": {k: v["value"] for k, v in readings["control"].items()},
            }), flush=True)
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "largest": worst}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
