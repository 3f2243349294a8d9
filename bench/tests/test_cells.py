"""BENCHMARK.json and the files its cells are found by, by name."""
from __future__ import annotations

import json
import os
import re

import pytest

from bench import cells

SPEC = cells.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_keys_and_names_follow_the_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"] and 1 <= SPEC["run_seconds"] <= 51
    names = ([c["name"] for c in SPEC["configs"]]
             + [w["name"] for w in SPEC["workloads"]]
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
    assert all(NAME.match(n) for n in names)
    metric_names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in {m["name"] for m in SPEC["end_to_end"]}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    cells_ = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        assert set(m.get("workloads", cells_)) <= cells_
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for w in SPEC["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_every_cell_finds_its_files_by_name(w):
    cell = cells.load_cell(w["name"])
    assert cell.config["name"] == w["config"]
    assert set(cell.limits) == {"num_selected_gap", "loss_mean_gap", "loss_gap"}
    cells.engine(cell.config, cell.traffic)
    cells.module("references", cell.config["reference"]).run_trial
    for m in cell.per_layer:
        assert callable(cells.module("metrics", m["name"]).read)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}


@pytest.mark.parametrize("c", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file_is_the_configuration_as_run(c):
    with open(os.path.join(cells.ROOT, c["file"])) as f:
        cfg = json.load(f)
    assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
    used = {w["config"] for w in SPEC["workloads"]}
    assert c["name"] in used


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        cells.load_cell("no_such.cell")


def test_a_cell_added_by_files_alone_is_found(tmp_path):
    """A later cell needs new files and entries only: no harness edit."""
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "limits"):
        (bench / sub).mkdir(parents=True)
    spec = dict(SPEC, workloads=[{"name": "x.y", "config": "x",
                                  "traffic": "y", "chips": 1, "why": "t"}])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    (bench / "configs" / "x.json").write_text(json.dumps({"name": "x"}))
    (bench / "traffic" / "y.json").write_text(json.dumps({"engine": "sim"}))
    (bench / "limits" / "x.y.json").write_text(json.dumps(
        {"loss_gap": {"limit": 0.5}}))
    cell = cells.load_cell("x.y", root=str(tmp_path))
    assert cell.config == {"name": "x"} and cell.limits == {"loss_gap": 0.5}
