#!/usr/bin/env python3
"""Bring-up check: the paper's FL experiment on a TPU, through ``repro.fl.run``.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips: sharded engine vs sim

One chip.  ``run(ExperimentSpec(engine="sim", workload="cnn"))`` at the
paper's §VI width: ``FLConfig()`` (100 clients, 30 per round, 4 local epochs,
batch 32, Adam 1e-3), the paper CNN (conv 32/64, hidden 128), 290 samples per
client and 50 eval images per class, on case1b × (random, labelwise) × seed 0
for 3 rounds — one compiled program.  Checks:

* every accuracy and loss is finite;
* 30 clients train every round under both strategies;
* the compiled grid holds both Pallas kernels (``label_hist_kernel``,
  ``weighted_agg_kernel``) as TPU custom calls, not interpreted;
* on round 0's real inputs (the materialized batch and the 30 locally
  trained client models), each kernel agrees with its XLA reference on the
  chip: histograms bit-identical, the FedAvg mean within the f32 ulp
  tolerance tests/test_compute_dispatch.py pins on the CPU.

Four chips (``--four-chips``, and nothing else).  The same spec on
``engine="sharded"`` over a 4-way client mesh and on ``engine="sim"``, in
this one process: 4 groups; ``num_selected`` exactly equal and accuracy
within the atol tests/test_experiment.py::TestShardedEngine pins on the
CPU; loss within bf16's epsilon (see ``TPU_LOSS_TOL``).

Where JAX finds no TPU, or the repository is not beside this file, it exits
non-zero before running anything.  A failed check exits non-zero too.  Only
on success is the last line of standard output the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
One process holds the chip(s) throughout; nothing is started as a child.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
ROUNDS = 3
STRATEGIES = ("random", "labelwise")
# The CPU pins: kernel ≡ reference (tests/test_compute_dispatch.py) and
# sharded ≡ sim (tests/test_experiment.py::TestShardedEngine).
AGG_TOL = dict(rtol=3e-7, atol=3e-7)
ACC_ATOL = 5e-3
# The CPU pins loss to rtol 2e-4, which no two programs of the same f32 math
# reach on the TPU: XLA's default matmul precision rounds operands to bf16,
# and each program rounds and reduces in its own order.  On one v5e, sim
# with 2 strategies vs with 1, and sim vs the host engine, differ by 2.4e-3
# and 2.2e-3 in round-0 loss (8.7e-4 and 6.7e-4 even at "highest").  So on the
# chip loss is held to bf16's epsilon, 2**-7.
TPU_LOSS_TOL = dict(rtol=2.0 ** -7, atol=2e-5)
KERNELS = ("label_hist_kernel", "weighted_agg_kernel")


class Checks:
    """Prints every check as it is made; ``ok`` is False once one failed."""

    def __init__(self):
        self.failed = []

    def __call__(self, cond: bool, what: str) -> bool:
        print(f"check {'ok' if cond else 'FAILED'}: {what}", flush=True)
        if not cond:
            self.failed.append(what)
        return cond

    @property
    def ok(self) -> bool:
        return not self.failed


def experiment_spec(engine: str, fl):
    from repro.fl import ExperimentSpec, ScenarioSpec
    return ExperimentSpec(
        scenarios=(ScenarioSpec.from_case("case1b"),), strategies=STRATEGIES,
        seeds=(0,), engine=engine, workload="cnn", fl=fl, rounds=ROUNDS,
        eval_n_per_class=50)


def print_trajectories(res, tag: str) -> None:
    for s, strat in enumerate(res.strategies):
        print(f"{tag} {strat} accuracy {res.accuracy[0, s, 0].tolist()}")
        print(f"{tag} {strat} loss     {res.loss[0, s, 0].tolist()}")
        print(f"{tag} {strat} trained  {res.num_selected[0, s, 0].tolist()}")


def check_trajectories(check: Checks, res, fl, tag: str) -> None:
    check(bool(np.isfinite(res.accuracy).all() and np.isfinite(res.loss).all()),
          f"{tag}: accuracy and loss finite every round")
    check(bool((res.num_selected == fl.clients_per_round).all()),
          f"{tag}: {fl.clients_per_round} clients trained every round, "
          f"both strategies")


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def round_zero(fl):
    """Round 0 of seed 0 as the engines build it: the materialized round
    batch, labelwise selection, and the selected clients' local models."""
    import jax
    import jax.numpy as jnp

    from repro.core import get_strategy
    from repro.data import client_batches
    from repro.fl.client import local_train
    from repro.fl.workloads import get_workload
    from repro.optim import get_optimizer

    spec = experiment_spec("sim", fl)
    plan = spec.scenarios[0].lower(fl, spec.seeds, ROUNDS).plan
    wl = get_workload(spec.workload)
    ds = wl.dataset()
    key = jax.random.PRNGKey(spec.seeds[0])
    kt = jax.random.fold_in(key, 1000)
    data = wl.materialize(ds, plan[0], jax.random.fold_in(kt, 0))
    n_sel = fl.clients_per_round
    sel = get_strategy("labelwise")(jax.random.fold_in(kt, 1), data["hists"],
                                    n_sel)
    idx = sel.order[:n_sel]
    batches = client_batches(data, fl.batch_size, wl.batch_keys)
    data_sel = jax.tree_util.tree_map(lambda x: x[idx], batches)
    params = wl.init(jax.random.fold_in(key, 1), ds)
    opt = get_optimizer(fl.optimizer, fl.lr)
    loss_fn = wl.make_loss(ds)
    trained, _ = jax.jit(jax.vmap(
        lambda b: local_train(params, opt, b, loss_fn, fl.local_epochs)))(
            data_sel)
    sizes = data_sel["valid"].reshape(n_sel, -1).sum(-1).astype(jnp.float32)
    return data, trained, sel.mask[idx], sizes


def check_kernels_against_references(check: Checks, fl) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels import client_histograms, masked_weighted_mean

    data, trained, live, sizes = round_zero(fl)
    labels = jnp.where(data["valid"], data["labels"], 0)
    h_pal = np.asarray(client_histograms(labels, 10, data["valid"],
                                         backend="pallas"))
    h_ref = np.asarray(client_histograms(labels, 10, data["valid"],
                                         backend="reference"))
    print(f"round 0 histograms: {h_ref.shape}, {int(h_ref.sum())} labels")
    check(np.array_equal(h_pal, h_ref),
          "label_hist_kernel ≡ XLA histogram, bit-identical, on round 0")
    m_pal = masked_weighted_mean(trained, live, sizes, backend="pallas")
    m_ref = masked_weighted_mean(trained, live, sizes, backend="reference")
    worst = 0.0
    close = True
    for a, b in zip(jax.tree_util.tree_leaves(m_pal),
                    jax.tree_util.tree_leaves(m_ref)):
        a, b = np.asarray(a), np.asarray(b)
        worst = max(worst, float(np.max(np.abs(a - b))))
        close &= bool(np.allclose(a, b, **AGG_TOL))
    print(f"round 0 FedAvg mean over {int(live.sum())} clients: "
          f"max |kernel - reference| = {worst!r}")
    check(close, f"weighted_agg_kernel ≡ masked_mean within {AGG_TOL} "
                 "on round 0's trained client models")


def one_chip(check: Checks, fl) -> None:
    import jax

    from repro.fl import run
    from repro.kernels import compute_backend

    check(compute_backend() == "pallas",
          "the compute dispatch resolves to the compiled Pallas kernels")
    t0 = time.perf_counter()
    res = run(experiment_spec("sim", fl))
    print(f"sim grid: compile_s {res.compile_s!r} wall_s {res.wall_s!r} "
          f"(run() total {time.perf_counter() - t0!r} s)")
    snaps = [m for m in res.meta["telemetry"].get("memory_analysis") or ()
             if m["label"] == "sim:grid"]
    snap = snaps[-1] if snaps else {}
    print(f"sim grid compiled.memory_analysis(): {snap}")
    print(f"peak_bytes_in_use after the grid: {peak_bytes(jax.devices()[0])}")
    kernels = snap.get("pallas_kernels", {})
    print(f"tpu_custom_call in the compiled grid: {sum(kernels.values())} "
          f"{kernels}")
    print_trajectories(res, "sim")
    check_trajectories(check, res, fl, "sim")
    check(all(kernels.get(k, 0) > 0 for k in KERNELS),
          f"the compiled grid calls both {KERNELS} as tpu_custom_call")
    check_kernels_against_references(check, fl)


def four_chips(check: Checks, fl) -> None:
    import jax

    from repro.fl import run

    if not check(jax.device_count() == 4,
                 f"four devices (JAX sees {jax.device_count()})"):
        return
    t0 = time.perf_counter()
    sh = run(experiment_spec("sharded", fl))
    t1 = time.perf_counter()
    peaks = [peak_bytes(d) for d in jax.devices()]
    sim = run(experiment_spec("sim", fl))
    t2 = time.perf_counter()
    print(f"sharded: wall_s {sh.wall_s!r} (compile included; run() total "
          f"{t1 - t0!r} s); peak_bytes_in_use per device after it {peaks}")
    print(f"sim: compile_s {sim.compile_s!r} wall_s {sim.wall_s!r} "
          f"(run() total {t2 - t1!r} s)")
    print(f"sharded meta: {json.dumps(sh.meta['sharded'])}")
    print_trajectories(sh, "sharded")
    print_trajectories(sim, "sim")
    print(f"max |sharded - sim|: loss "
          f"{float(np.max(np.abs(sh.loss - sim.loss)))!r} accuracy "
          f"{float(np.max(np.abs(sh.accuracy - sim.accuracy)))!r}")
    check_trajectories(check, sh, fl, "sharded")
    check(sh.meta["sharded"]["groups"] == 4, "sharded engine: 4 client groups")
    check(bool(np.array_equal(sh.num_selected, sim.num_selected)),
          "sharded ≡ sim: num_selected exactly")
    check(bool(np.all(np.abs(sh.accuracy - sim.accuracy) <= ACC_ATOL)),
          f"sharded ≡ sim: accuracy within atol {ACC_ATOL}")
    check(bool(np.allclose(sh.loss, sim.loss, **TPU_LOSS_TOL)),
          f"sharded ≡ sim: loss within {TPU_LOSS_TOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded engine on four chips and the "
                         "sim engine it is compared with")
    args = ap.parse_args(argv)

    import jax
    try:
        device = jax.devices()[0]
    except RuntimeError as e:
        print(f"chip_smoke: JAX found no device: {e}", file=sys.stderr)
        return 1
    if device.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {device.platform!r}); "
              "nothing was run", file=sys.stderr)
        return 1
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repository beside this file ({src}/repro is "
              "missing); nothing was run", file=sys.stderr)
        return 1
    sys.path.insert(0, src)

    from repro.compile_cache import enable_compile_cache
    from repro.configs.paper_cnn import FLConfig

    print(f"device_kind {device.device_kind!r}, {jax.device_count()} "
          f"device(s), jax {jax.__version__}")
    print(f"compilation cache: {enable_compile_cache()}")
    check = Checks()
    t0 = time.perf_counter()
    (four_chips if args.four_chips else one_chip)(check, FLConfig())
    print(f"total {time.perf_counter() - t0!r} s")
    if not check.ok:
        print(f"chip_smoke: {len(check.failed)} check(s) failed: "
              f"{check.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
