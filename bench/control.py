#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from, on the chip.

    python3 bench/control.py <cell> --seeds 12 --control-seeds 3 --base-seed <n>
        [--faults state_unchanged,half_of_each_batch,...]

In one process: the cell's own program, built as ``bench/run.py`` builds it,
runs calls until ``--seeds`` trial seeds have run every strategy; then the
plain reference recomputes every trial (the lower readings: the largest
gaps sound runs give), and the reference in the precision its module
names as ``CONTROL_PRECISION`` — the control, the step below the
configuration's precision that a later change would be tempted by — takes
the program's place on the first ``--control-seeds`` seeds (the upper
readings: the smallest the control gives).  Each fault named in ``--faults``
(``bench/faults.py``) is planted in turn, the program built and run again
on the same plans and seeds, and read against the same references.  As a
run judges its ``check_trials`` checked trials together, the trials are
taken in groups of that many, in the order they ran, and each group gives
one reading.  Prints one JSON line per trial and a summary line last.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _groups(trials, k):
    from bench import correct
    return [correct.aggregate(trials[j:j + k])
            for j in range(0, len(trials) - k + 1, k)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--base-seed", type=int, default=0)
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    from bench import cells, correct, faults, traffic_gen
    jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform == "cpu":
        print("control: no accelerator", file=sys.stderr)
        return 1
    cell = cells.load_cell(args.cell)
    cfg, tr = cell.config, cell.traffic
    strategies, r_n = tr["strategies"], tr["seeds_per_call"]
    n_calls = math.ceil(args.seeds / r_n)
    seeds = [traffic_gen.call_seeds(tr, args.base_seed, i) for i in range(n_calls)]

    def plans(i):
        return traffic_gen.call_plans(cfg, tr, args.base_seed, i)

    variants = ["program"] + [f for f in args.faults.split(",") if f]
    outs = {}
    for v in variants:
        with (faults.planted(v) if v != "program" else contextlib.nullcontext()):
            engine = cells.engine(cfg, tr)(cfg, tr, plans)
            engine.setup(0)
            outs[v] = [engine.call(i, seeds[i]) for i in range(n_calls)]
            engine.free()
        print(f"control: {v} ran {n_calls} calls; spans {engine.spans}",
              file=sys.stderr, flush=True)
    ref = cells.module("references", cfg["reference"])
    trials = {v: [] for v in variants + ["control"]}
    n_seed = 0
    for i in range(n_calls):
        plan_i = plans(i)
        for r in range(r_n):
            for s, strat in enumerate(strategies):
                t0 = time.perf_counter()
                want = ref.run_trial(cfg, tr, plan_i[r], strat, int(seeds[i][r]))
                line = {"seed": int(seeds[i][r]), "strategy": strat,
                        "reference_s": time.perf_counter() - t0,
                        "reference_loss": [w["loss"].tolist() for w in want],
                        "reference_accuracy": [w["accuracy"].tolist()
                                               for w in want]}
                for v in variants:
                    prog = {k: outs[v][i][k][s, r] for k in want[0]}
                    trials[v].append(correct.trial_gaps(prog, want))
                    line[v] = trials[v][-1]
                    line[v + "_loss"] = prog["loss"].tolist()
                if n_seed < args.control_seeds:
                    ctl = ref.run_trial(cfg, tr, plan_i[r], strat, int(seeds[i][r]),
                                        precision=ref.CONTROL_PRECISION,
                                        ties=False)[0]
                    trials["control"].append(correct.trial_gaps(ctl, want))
                    line["control"] = trials["control"][-1]
                    line["control_loss"] = ctl["loss"].tolist()
                print(json.dumps(line), flush=True)
            n_seed += 1
    # A run checks ``check_trials`` trials and judges their aggregate: group
    # the trials the same way, in the order they ran.
    k = tr["check_trials"]
    groups = {v: _groups(t, k) for v, t in trials.items() if t}
    smallest = {v: {n: min(g[n] for g in gs) for n in correct.READINGS}
                for v, gs in groups.items() if v != "program"}
    summary = {"cell": cell.name, "check_trials": k, "program_seeds": n_seed,
               "control_seeds": min(n_seed, args.control_seeds),
               "lower": {n: max(g[n] for g in groups["program"])
                         for n in correct.READINGS},
               "upper": smallest.pop("control", None),
               "faults": smallest, "groups": groups}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
