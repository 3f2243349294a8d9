#!/usr/bin/env python3
"""Run one benchmark cell once, on the machine's accelerator.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's inputs from ``--seed``, compiles (or loads from the
persistent cache in ``<checkout>/.jax_cache``) the program the window drives,
and warms it with one call.  The window then calls the program back to back
for ``--seconds`` (whole calls), and afterwards a sample of its answers,
drawn from the seed, is recomputed by the plain reference.  The last line
of standard output is the result as one JSON object; the numbers compared
and their limits are also the last lines of standard error.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` runs the
same window under the profiler and reports the per-layer metrics instead.
Where JAX finds no accelerator, fewer chips than the cell asks for, or no
program beside the benchmark, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
WARM_CALL = 2 ** 20          # the warm-up call: its own plans and seeds, apart from the window's


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def window_work(cell, engine, calls, seeds) -> dict:
    """The required work of the given window calls, from shapes and plans:
    the model's counts come from the configuration's reference module."""
    from bench import cells, work
    cfg, tr = cell.config, cell.traffic
    ref = cells.module("references", cfg["reference"])
    s_n, r_n, t_n = len(tr["strategies"]), tr["seeds_per_call"], tr["rounds_per_call"]
    n_clients, n_max = engine.plan(0, 0).shape[1:]
    k, params = cfg["clients_per_round"], ref.num_params(cfg)
    train = sum(ref.trial_train_flops(cfg, tr, engine.plan(c.index, r),
                                      tr["strategies"][s], int(seeds[c.index][r]))
                for c in calls for s in range(s_n) for r in range(r_n))
    # Data, and so its histograms, is shared by the strategies of one seed.
    return {"train_flops": train,
            "label_hist_bytes": len(calls) * t_n * r_n * work.label_hist_bytes(
                n_clients, n_max, cfg["num_classes"]),
            "weighted_agg_bytes": len(calls) * t_n * s_n * r_n
            * work.weighted_agg_bytes(k, params),
            "weighted_agg_flops": len(calls) * t_n * s_n * r_n
            * work.weighted_agg_flops(k, params)}


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             peak: dict, t_start: float = T_START, trace_dir: str = TRACE_DIR):
    """Set-up, window and check of one cell → (result dict, stderr lines)."""
    import jax
    import numpy as np

    from bench import cells, correct, trace_reduce, traffic_gen, window

    cfg, tr = cell.config, cell.traffic
    strategies = tr["strategies"]
    s_n, r_n, t_n = len(strategies), tr["seeds_per_call"], tr["rounds_per_call"]
    log = []
    engine = cells.engine(cfg, tr)(
        cfg, tr, lambda i: traffic_gen.call_plans(cfg, tr, seed, i))
    engine.setup(WARM_CALL)
    engine.call(WARM_CALL, traffic_gen.call_seeds(tr, seed, WARM_CALL))
    setup_s = time.perf_counter() - t_start
    log.append(f"setup {setup_s!r} s; spans {engine.spans}; "
               f"compiled memory {engine.memory}")

    seeds = {}

    def call(i: int):
        with _annotate("bench:call"):
            seeds[i] = traffic_gen.call_seeds(tr, seed, i)
            return engine.call(i, seeds[i])

    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    try:
        with _annotate("bench:window"):
            w = window.run_window(call, seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    calls = w.calls
    rounds = len(calls) * s_n * r_n * t_n
    log.append(f"window {w.seconds!r} s, {len(calls)} calls of "
               f"{s_n * r_n} trials x {t_n} rounds; call seconds "
               f"{[c.end - c.start for c in calls]}")
    # The program's compiled temp area counts in the runtime's reservation
    # and not in its live buffers: 9.4 GB reserved beside 30 MB in use.
    stats = [d.memory_stats() or {} for d in devices]
    memory_peak = max(max(int(st.get("peak_bytes_in_use", 0)),
                          int(st.get("peak_bytes_reserved", 0))) for st in stats)
    log.append(f"memory_stats {stats}")
    # The traced ops are named by the executable that ran: keep its text.
    hlo_text = engine.compiled.as_text() if trace else None
    engine.free()
    gc.collect()

    failed = 0
    for c in calls:
        o = c.out
        bad = ((o["num_selected"] != o["mask_sum"])
               | ~np.isfinite(o["loss"]) | ~np.isfinite(o["accuracy"]))
        failed += int(bad.any(axis=-1).sum())
    ref = cells.module("references", cfg["reference"])
    checked = []
    for ci, s, r in traffic_gen.check_sample(len(calls), s_n, r_n,
                                             tr["check_trials"], seed):
        o = calls[ci].out
        prog = {k: o[k][s, r] for k in ("accuracy", "loss", "num_selected")}
        t0 = time.perf_counter()
        want = ref.run_trial(cfg, tr, engine.plan(ci, r), strategies[s],
                             int(seeds[ci][r]))
        g = correct.trial_gaps(prog, want)
        checked.append(g)
        log.append(f"checked call {ci} {strategies[s]} seed {seeds[ci][r]} "
                   f"({time.perf_counter() - t0:.1f} s): {g}; program "
                   f"{ {k: v.tolist() for k, v in prog.items()} } reference "
                   f"{[{k: v.tolist() for k, v in x.items()} for x in want]}")
    numbers = correct.aggregate(checked)
    log.append(f"readings {numbers}")
    ok, table, lines = correct.judge(numbers, cell.limits)
    if not ok:
        failed += len(checked)
    ok = ok and failed == 0

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(), "memory_peak_bytes": memory_peak}
    result = {"correct": ok, "attempted": len(calls) * s_n * r_n,
              "failed": failed}
    if not trace:
        result["metrics"] = {
            "trial_rounds_per_s": {"value": rounds / w.seconds,
                                   "unit": "rounds/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
        result["device"] = device
    else:
        summary = trace_reduce.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        # What a per-layer reader reads (``bench/metrics/<name>.py``).
        ctx = {"config": cfg, "traffic": tr, "spans": engine.spans,
               "trace": summary, "peak": peak, "chips": cell.chips,
               "window_s": w.seconds, "trial_rounds": rounds,
               "hlo_text": hlo_text,
               "work": window_work(cell, engine, calls, seeds)}
        metrics = {}
        for m in cell.per_layer:
            v = cells.module("metrics", m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        device.update(busy_s=summary.mean_busy_s, window_s=summary.window_s)
        result["device"] = device
        result["breakdown"] = summary.breakdown()
        log.append(f"trace: window {summary.window_s!r} s, busy "
                   f"{summary.busy_s}, work {ctx['work']}; device seconds by "
                   f"op kind {summary.by_kind()}")
    result["checks"] = table
    return result, log + lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"bench: no program beside the benchmark ({ROOT}/src/repro); "
              "nothing was run", file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import cells, work
    try:
        cell = cells.load_cell(args.workload)
    except (KeyError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2

    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"bench: JAX found no device: {e}", file=sys.stderr)
        return 1
    if devices[0].platform == "cpu" or len(devices) < cell.chips:
        print(f"bench: cell {cell.name} needs {cell.chips} accelerator chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s); "
              "nothing was run", file=sys.stderr)
        return 1
    try:
        peak = work.peaks(devices[0].device_kind)
    except KeyError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    with contextlib.redirect_stdout(sys.stderr):
        result, lines = run_cell(cell, args.seed, args.seconds,
                                 bool(args.trace), devices[:cell.chips], peak)
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
