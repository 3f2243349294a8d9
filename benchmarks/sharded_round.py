"""BENCH_sharded_round: gather-based vs masked-psum SPMD FL round, plus the
O(B) vs O(N) batch-exchange comparison inside the gather mode.

The gather-based round (repro.fl.sharded, mode="gather") trains only the
selected budget of clients — B padded to a multiple of the group count —
while the legacy masked-psum baseline (mode="masked") trains every client and
masks unselected deltas out of the reduction.  Within the gather mode the
selected batch shards can move two ways: ``exchange="a2a"`` (default), the
O(B) selected-shard exchange — one psum_scatter over the replicated slot
routing — or ``exchange="allgather"``, the O(N) full-round-batch all-gather
baseline.  Both exchanges are bit-identical (pinned by the subprocess parity
test); this suite records their wall-clock AND analytic per-device ring bytes
(repro.fl.sharded.exchange_bytes_per_device) so the communication claim is
auditable: at the benchmark's budget (one client per device, 4 clients per
device → 0.75 FLOP sparsity) a2a moves ¼ of the all-gather's bytes.

This suite measures steady-state wall-clock on N = 8, 16, 32 emulated host
devices (``--xla_force_host_platform_device_count``, real FLOPs on the CPU
thread pool).  Each device count runs in its own subprocess (the XLA
device-count flag must be set before jax initializes); the child reports one
JSON line that the parent collects into ``BENCH_sharded_round.json`` at the
repo root plus the usual CSV lines.  Every variant records ``compile_s``
(first-call wall minus a steady round — the jit happens on first call).
It is a CPU-emulation suite: where a TPU is visible it refuses to run rather
than start children that would contend for the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_PATH = os.path.join(ROOT, "BENCH_sharded_round.json")
MARKER = "SHARDED_ROUND_CHILD_JSON:"

DEVICE_COUNTS = (8, 16, 32)
CLIENTS_PER_DEVICE = 4
SPC = 8               # samples per client
BATCH = 8
LOCAL_EPOCHS = 1
WARMUP_ROUNDS = 1
TIMED_ROUNDS = 3

# (report key, mode, exchange) — gather/a2a is the production hot path.
VARIANTS = (
    ("gather_a2a", "gather", "a2a"),
    ("gather_allgather", "gather", "allgather"),
    ("masked", "masked", "a2a"),       # exchange unused in masked mode
)


def _child(devices: int, rounds: int) -> dict:
    """Runs inside the forced-device-count subprocess: time every variant."""
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.core import case_label_plan
    from repro.data import ImageDataset, client_batches, materialize_round
    from repro.fl import exchange_bytes_per_device, make_sharded_fl_round
    from repro.fl.client import local_train
    from repro.models import cnn_init, cnn_loss
    from repro.optim import get_optimizer

    assert jax.device_count() == devices, (jax.device_count(), devices)
    n_clients = CLIENTS_PER_DEVICE * devices
    budget = devices                      # one selected client per device
    mesh = jax.make_mesh((devices,), ("clients",))
    ds = ImageDataset()
    opt = get_optimizer("adam", 1e-3)

    def loss_fn(params, batch):
        return cnn_loss(params, batch["images"], batch["labels"],
                        batch["valid"])

    def local_step(params, batch):
        return local_train(params, opt, batch, loss_fn, LOCAL_EPOCHS)[0]

    key = jax.random.PRNGKey(0)
    params = cnn_init(jax.random.fold_in(key, 1))
    pspec = jax.tree_util.tree_map(lambda _: P(), params)
    plan = case_label_plan("iid", seed=0, num_rounds=1,
                           num_clients=n_clients, samples_per_client=SPC,
                           majority=int(SPC * 200 / 290))
    data = materialize_round(ds, plan[0], jax.random.fold_in(key, 2))
    batches = client_batches(data, BATCH)

    report = {"devices": devices, "clients": n_clients, "budget": budget,
              "rounds_timed": rounds}
    for name, mode, exchange in VARIANTS:
        round_fn = make_sharded_fl_round(
            mesh, "clients", local_step, n_select=budget,
            num_classes=ds.num_classes, params_pspec=pspec,
            batch_pspec={"images": P(), "labels": P(), "valid": P()},
            num_clients=n_clients, strategy="labelwise", mode=mode,
            exchange=exchange)
        t0 = time.perf_counter()
        p = params
        for t in range(WARMUP_ROUNDS):
            p, info = round_fn(p, batches, data["labels"], data["valid"],
                               jax.random.fold_in(key, 10 + t))
        jax.block_until_ready(p)
        t1 = time.perf_counter()
        for t in range(rounds):
            p, info = round_fn(p, batches, data["labels"], data["valid"],
                               jax.random.fold_in(key, 100 + t))
        jax.block_until_ready(p)
        t2 = time.perf_counter()
        s_per_round = (t2 - t1) / rounds
        entry = {
            "warmup_s": t1 - t0,     # compile + WARMUP_ROUNDS executed rounds
            # uniform BENCH key; the jit compiles on the first warmup call,
            # so compile ≈ warmup wall minus the rounds it also executed
            "compile_s": max(0.0, (t1 - t0) - WARMUP_ROUNDS * s_per_round),
            "s_per_round": s_per_round,
            "trained_per_round": round_fn.trained_per_round,
            "flop_sparsity": round_fn.flop_sparsity,
            "num_selected": float(np.asarray(info["num_selected"])),
        }
        if mode == "gather":
            entry["exchange"] = exchange
            entry["exchange_bytes_per_device"] = exchange_bytes_per_device(
                batches, n_clients, round_fn.budget_padded, devices, exchange)
        report[name] = entry
    report["speedup_gather_vs_masked"] = (
        report["masked"]["s_per_round"] / report["gather_a2a"]["s_per_round"])
    report["a2a_vs_allgather_bytes"] = (
        report["gather_a2a"]["exchange_bytes_per_device"]
        / report["gather_allgather"]["exchange_bytes_per_device"])
    report["a2a_vs_allgather_speedup"] = (
        report["gather_allgather"]["s_per_round"]
        / report["gather_a2a"]["s_per_round"])
    return report


def main(fast: bool = True) -> dict:
    import jax

    from .common import emit, write_report

    if jax.default_backend() == "tpu":
        # One process per chip: the children would contend for the TPU this
        # process now holds.  The sharded engine's chip path is
        # ``python chip_smoke.py --four-chips``.
        raise RuntimeError(
            "benchmarks.sharded_round emulates devices on the CPU in child "
            "processes and refuses to run where a TPU is visible")

    rounds = TIMED_ROUNDS if fast else 4 * TIMED_ROUNDS
    results = []
    for devices in DEVICE_COUNTS:
        env = dict(
            os.environ,
            XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
            PYTHONPATH=os.path.join(ROOT, "src") + os.pathsep
            + os.environ.get("PYTHONPATH", ""))
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.sharded_round", "--child",
             "--devices", str(devices), "--rounds", str(rounds)],
            env=env, cwd=ROOT, capture_output=True, text=True, timeout=1800)
        if proc.returncode != 0:
            raise RuntimeError(
                f"sharded_round child (devices={devices}) failed:\n"
                + proc.stderr[-3000:])
        line = next(l for l in proc.stdout.splitlines()
                    if l.startswith(MARKER))
        results.append(json.loads(line[len(MARKER):]))

    report = {
        "config": {"clients_per_device": CLIENTS_PER_DEVICE,
                   "samples_per_client": SPC, "batch_size": BATCH,
                   "local_epochs": LOCAL_EPOCHS, "strategy": "labelwise",
                   "budget": "one client per device (N/4 of the fleet)",
                   "exchanges": "a2a = O(B) selected-shard psum_scatter; "
                                "allgather = O(N) full-batch baseline"},
        "compile_s": sum(r[name]["compile_s"]
                         for r in results for name, _, _ in VARIANTS),
        "by_device_count": results,
    }
    write_report(OUT_PATH, report)

    for r in results:
        ga, gall = r["gather_a2a"], r["gather_allgather"]
        emit(f"sharded_round/gather_a2a_n{r['devices']}",
             ga["s_per_round"] * 1e6,
             f"trained={ga['trained_per_round']}/{r['clients']} "
             f"sparsity={ga['flop_sparsity']:.2f} "
             f"bytes={ga['exchange_bytes_per_device']}")
        emit(f"sharded_round/gather_allgather_n{r['devices']}",
             gall["s_per_round"] * 1e6,
             f"bytes={gall['exchange_bytes_per_device']} "
             f"a2a_bytes_ratio={r['a2a_vs_allgather_bytes']:.2f}")
        emit(f"sharded_round/masked_n{r['devices']}",
             r["masked"]["s_per_round"] * 1e6,
             f"trained={r['masked']['trained_per_round']}/{r['clients']}")
        emit(f"sharded_round/speedup_n{r['devices']}", 0.0,
             f"gather_vs_masked={r['speedup_gather_vs_masked']:.2f}x "
             f"a2a_vs_allgather={r['a2a_vs_allgather_speedup']:.2f}x")
    print(f"# -> {OUT_PATH}")
    return report


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=TIMED_ROUNDS)
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    if args.child:
        print(MARKER + json.dumps(_child(args.devices, args.rounds)))
    else:
        main(fast=not args.full)
