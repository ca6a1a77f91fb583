"""Tiny cells for CPU runs of the harness (same code path, small sizes).

The polish runs 66 iterations, so that a job holds one replica exchange
(every 50 iterations) and one drift-guard re-sweep before the last
(every 64), as the cells' jobs do."""
import time

POLISH = {"job": {"strategy": "large", "proposal_batch": 2,
                  "engine": "pallas"},
          "seed_range": [2, 2147483647], "sequence_seed": 13,
          "warmup": {"seed": 1, "budget": 8, "polish_iters": 2}}
HILLCLIMB = {"job": {"strategy": "large", "polish": False},
             "seed_range": [2, 2147483647], "sequence_seed": 13,
             "warmup": {"seed": 1, "budget": 8}}
# the per-layer metrics a CPU run can read: the host spans (no device
# kernels, no peaks, and the circulant pricer is numpy at these sizes)
PER_LAYER = [("polish_host_ms", "ms"), ("dispatch_ms", "ms")]


def cell(traffic=POLISH, n=256, k=6, fold=4, replicas=4, chips=1,
         budget=8, polish_iters=66, per_layer=PER_LAYER):
    return {"name": "tiny", "chips": chips,
            "config": {"n": n, "k": k, "fold": fold, "replicas": replicas,
                       "budget": budget, "polish_iters": polish_iters},
            "traffic": traffic,
            "end_to_end": [{"name": "search_s", "unit": "s"},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": [{"name": m, "unit": u} for m, u in per_layer]}


def run(c, seed=2**31 + 11, seconds=1.0, trace=False):
    from bench import run as harness

    return harness.run_cell(c, seed, seconds, trace, require_tpu=False,
                            t_start=time.perf_counter())
