"""Benchmark driver: one module per paper table/figure.  Prints
``name,us_per_call,derived`` CSV rows and saves JSON under results/benchmarks/.

    PYTHONPATH=src python -m benchmarks.run [--only table1,fig4,...]
    python benchmarks/run.py --smoke        # CI: fast subset + BENCH_*.json
"""
import argparse
import os
import sys
import time

if __package__ in (None, ""):  # executed as a script: bootstrap the paths
    _REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, _REPO)
    sys.path.insert(0, os.path.join(_REPO, "src"))

from benchmarks import (bench_search, fig2_pingpong, fig3_pingpong_ratios,
                        fig4_collectives, fig5_beff, fig6_ffte, fig7_graph500,
                        fig8_npb, fig10_large_sim, fig_routing, roofline,
                        table1_graph_properties, table2_3_dragonfly,
                        table4_large_scale, table5_6_large_dragonfly,
                        topology_term)

MODULES = {
    "table1": table1_graph_properties,
    "fig2": fig2_pingpong,
    "fig3": fig3_pingpong_ratios,
    "fig4": fig4_collectives,
    "fig5": fig5_beff,
    "fig6": fig6_ffte,
    "fig7": fig7_graph500,
    "fig8": fig8_npb,
    "table2_3": table2_3_dragonfly,
    "table4": table4_large_scale,
    "table5_6": table5_6_large_dragonfly,
    "fig10": fig10_large_sim,
    "fig_routing": fig_routing,
    "roofline": roofline,
    "topology_term": topology_term,
    "bench_search": bench_search,
}

# fast, dependency-light subset for the CI bench-smoke job (bench_search
# additionally honours smoke=True with reduced budgets; fig4 emits the
# spec-embedded BENCH_fig4.json rows in seconds; fig_routing the static-vs-
# adaptive BENCH_routing.json rows the smoke job asserts on)
SMOKE_KEYS = ["bench_search", "fig4", "fig_routing"]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--only", default=None, help="comma-separated module keys")
    p.add_argument("--smoke", action="store_true",
                   help="fast CI subset with reduced budgets (emits BENCH_*.json)")
    p.add_argument("--parallel", action="store_true",
                   help="fan run_experiment grids out over a process pool "
                        "(sets REPRO_PARALLEL=1 for every module)")
    args = p.parse_args(argv)
    if args.parallel:
        # the experiment-service default: api.run_experiment reads this env
        # var when parallel= is not passed explicitly, so every benchmark
        # module's workload x topology grid fans out without code changes
        os.environ["REPRO_PARALLEL"] = "1"
    if args.only:
        keys = args.only.split(",")  # --smoke then only reduces budgets
    elif args.smoke:
        keys = SMOKE_KEYS
    else:
        keys = list(MODULES)
    unknown = [k for k in keys if k not in MODULES]
    if unknown:
        p.error(f"unknown module(s) {unknown}; choose from {sorted(MODULES)}")
    from repro import compile_cache

    compile_cache.enable()
    print("name,us_per_call,derived")
    for k in keys:
        t0 = time.time()
        mod = MODULES[k]
        rows = mod.run(smoke=True) if args.smoke and k == "bench_search" else mod.run()
        rows.emit()
        rows.save()
        print(f"# {k} done in {time.time()-t0:.1f}s", file=sys.stderr)
    return 0


def run_all():  # pytest convenience
    return main([])


if __name__ == "__main__":
    sys.exit(main())
