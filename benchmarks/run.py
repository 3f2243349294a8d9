"""Benchmark runner (deliverable d): one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME]

Prints ``name,us_per_call,derived`` CSV lines per the contract.
``--full`` restores the paper's protocol sizes (hours on a CPU; the
default fast mode keeps every structural element).

JAX's persistent compilation cache is on for every suite
(``repro.compile_cache``): in ``JAX_COMPILATION_CACHE_DIR`` when that is
set, else in ``<checkout>/.jax_cache``.  Every BENCH_*.json records
``compile_s`` so cached and cold runs are distinguishable."""
from __future__ import annotations

import argparse
import sys
import time
import traceback

SUITES = [
    ("fig5_kl", "benchmarks.fig5_kl"),
    ("selection_cost", "benchmarks.selection_cost"),
    ("kernel_bench", "benchmarks.kernel_bench"),
    ("table1_six_cases", "benchmarks.table1_six_cases"),
    ("fig6_fig7_bias_sweep", "benchmarks.fig6_fig7_bias_sweep"),
    ("fig8_fig9_cases_a", "benchmarks.fig8_fig9_cases_a"),
    ("fig10_table2_proportion", "benchmarks.fig10_table2_proportion"),
    ("dirichlet_ablation", "benchmarks.dirichlet_ablation"),
    ("hotpath", "benchmarks.hotpath"),
    ("sim_grid", "benchmarks.sim_grid"),
    ("workload_grid", "benchmarks.workload_grid"),
    ("clustered", "benchmarks.clustered"),
    ("robust", "benchmarks.robust"),
    ("sharded_round", "benchmarks.sharded_round"),
    ("population", "benchmarks.population"),
    ("roofline_report", "benchmarks.roofline_report"),
]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--sim-grid", action="store_true",
                    help="only run the compiled-engine vs host-loop grid "
                         "comparison and emit BENCH_sim_grid.json")
    ap.add_argument("--sharded-round", action="store_true",
                    help="only run the gather-based vs masked-psum SPMD "
                         "round comparison (8/16/32 emulated devices) and "
                         "emit BENCH_sharded_round.json")
    ap.add_argument("--workload-grid", action="store_true",
                    help="only run the per-workload (cnn vs lm) compiled "
                         "grid vs host-loop comparison and emit "
                         "BENCH_workloads.json")
    ap.add_argument("--hotpath", action="store_true",
                    help="only run the round hot-path micro-bench (one_hot "
                         "vs fused histogram, tree-map vs fused "
                         "aggregation) and emit BENCH_hotpath.json")
    ap.add_argument("--clustered", action="store_true",
                    help="only run the clustered_fedavg (per-cluster global "
                         "models) vs single-model fedavg accuracy "
                         "comparison on the non-IID cases and emit "
                         "BENCH_clustered.json")
    ap.add_argument("--robust", action="store_true",
                    help="only run the byzantine-robustness grid (25% "
                         "poisoned clients x {fedavg, median, trimmed_mean, "
                         "krum} on the non-IID cases) and emit "
                         "BENCH_robust.json")
    ap.add_argument("--population", action="store_true",
                    help="only run the population-scale suite (hier≡sim "
                         "micro parity, N-sweep 10³→10⁶ with per-shard "
                         "compiled-memory measurements, async FedBuff demo) "
                         "and emit BENCH_population.json")
    args = ap.parse_args(argv)
    if args.sim_grid:
        args.only = "sim_grid"
    if args.sharded_round:
        args.only = "sharded_round"
    if args.workload_grid:
        args.only = "workload_grid"
    if args.hotpath:
        args.only = "hotpath"
    if args.clustered:
        args.only = "clustered"
    if args.robust:
        args.only = "robust"
    if args.population:
        args.only = "population"
    if args.only and args.only not in {n for n, _ in SUITES}:
        ap.error(f"unknown suite {args.only!r}; have "
                 f"{sorted(n for n, _ in SUITES)}")

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()   # before any suite's first jit lowering

    import importlib
    failures = []
    for name, modname in SUITES:
        if args.only and args.only != name:
            continue
        t0 = time.time()
        print(f"# === {name} ===", flush=True)
        try:
            mod = importlib.import_module(modname)
            mod.main(fast=not args.full)
        except Exception:
            traceback.print_exc()
            failures.append(name)
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
    if failures:
        print("# FAILED:", failures)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
