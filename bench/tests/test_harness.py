"""The command's contract, and a whole run at a tiny size on the CPU with
the harness's look for a chip skipped."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from bench import cells, run, window
from bench.tests import tiny

RUN = os.path.join(cells.BENCH_DIR, "run.py")
BIG_SEED = 2 ** 31 + 11
CPU_PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def _cli(args, cwd=cells.ROOT, script=RUN):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


ARGS = ["--workload", "paper_cnn.case1b", "--seed", str(BIG_SEED),
        "--seconds", "10", "--trace", "0"]


def test_without_an_accelerator_it_exits_nonzero_and_prints_no_result():
    p = _cli(ARGS)
    assert p.returncode != 0 and p.stdout == ""
    assert "accelerator" in p.stderr


def test_without_the_program_beside_it_it_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(cells.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(cells.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(ARGS, cwd=tmp_path, script=str(tmp_path / "bench" / "run.py"))
    assert p.returncode != 0 and p.stdout == ""


@pytest.mark.parametrize("args", [
    ["--workload", "no_such.cell", "--seed", "1", "--seconds", "1", "--trace", "0"],
    ["--workload", "paper_cnn.case1b", "--seconds", "1", "--trace", "0"],
    ["--workload", "paper_cnn.case1b", "--seed", "1", "--seconds", "1", "--trace", "2"],
])
def test_bad_arguments_exit_nonzero(args):
    p = _cli(args)
    assert p.returncode != 0 and p.stdout == ""


def test_window_closes_at_the_end_of_the_first_call_past_seconds():
    now = [0.0]

    def clock():
        return now[0]

    def call(i):
        now[0] += 4.0
        return i

    w = window.run_window(call, 10.0, clock)
    assert [c.out for c in w.calls] == [0, 1, 2]
    assert w.seconds == 12.0
    assert window.run_window(call, 0.0, clock).calls[0].out == 0


def test_a_whole_run_at_a_tiny_size():
    cell = tiny.cell()
    result, lines = run.run_cell(cell, BIG_SEED, 0.5, False, jax.devices(),
                                 CPU_PEAK)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "checks"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 3
    m = result["metrics"]
    assert set(m) == {"trial_rounds_per_s", "setup_s"}
    assert m["trial_rounds_per_s"]["value"] > 0 and m["setup_s"]["unit"] == "s"
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    # The numbers compared, each beside its limit, end standard error.
    assert [ln.split(":")[0] for ln in lines[-3:]] == [
        "check num_selected_gap", "check loss_mean_gap", "check loss_gap"]
    assert set(result["checks"]) == {"num_selected_gap", "loss_mean_gap", "loss_gap"}
    json.dumps(result)


def test_a_traced_run_reports_the_per_layer_metrics(tmp_path):
    cell = tiny.cell(strategies=("labelwise",))
    result, _ = run.run_cell(cell, BIG_SEED, 0.5, True, jax.devices(),
                             CPU_PEAK, trace_dir=str(tmp_path / "trace"))
    assert result["correct"] is True
    m = result["metrics"]
    assert "trial_rounds_per_s" not in m
    # No Pallas kernel runs on the CPU: their readers find nothing to read.
    assert set(m) == {"trace_lower_s", "compile_s", "round_mfu",
                      "device_idle_share"}
    assert 0 < m["round_mfu"]["value"] < 100
    d = result["device"]
    assert 0 < d["busy_s"] <= d["window_s"]
    b = result["breakdown"]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert list(result)[-1] == "checks"
    assert not (tmp_path / "trace").exists()
