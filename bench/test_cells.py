"""Cells, mixes, drivers and metric readers are found by name alone."""
from __future__ import annotations

import json
import shutil

import pytest

import cells
from conftest import BENCH, ROOT


@pytest.fixture
def copy(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return tmp_path


def test_a_new_mix_file_is_picked_up_by_name(copy):
    mix = json.loads((copy / "bench/traffic/open.json").read_text())
    mix["submissions"]["rate_per_s"] = 7
    (copy / "bench/traffic/testonly.json").write_text(json.dumps(mix))
    spec = json.loads((copy / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "flora_gcp.testonly",
                              "config": "flora_gcp", "traffic": "testonly",
                              "chips": 1, "why": "test"})
    (copy / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = cells.resolve(copy, "flora_gcp.testonly", bench=copy / "bench")
    assert cell.traffic["submissions"]["rate_per_s"] == 7
    assert cell.config["name"] == "flora_gcp"
    # metrics without a workloads key apply to every cell
    assert [m["name"] for m in cell.e2e] == ["setup_s"]


def test_every_metric_of_every_cell_has_a_reader():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = cells.resolve(ROOT, w["name"])
        assert {m["name"] for m in cell.e2e} >= {"setup_s"}
        assert len(cell.e2e) >= 2 and cell.layer
        for m in cell.e2e:
            assert callable(cells.reader("e2e_metrics", m["name"]).read)
        for m in cell.layer:
            assert callable(cells.reader("layer_metrics", m["name"]).read)
        assert callable(cells.driver(cell.config["driver"]).run)


def test_a_reader_falls_back_to_the_name_before_the_dot(copy):
    (copy / "bench/layer_metrics/only_here.py").write_text(
        "def read(run):\n    return 42.0\n")
    mod = cells.reader("layer_metrics", "only_here.open", copy / "bench")
    assert mod.read(None) == 42.0
    with pytest.raises(FileNotFoundError):
        cells.reader("layer_metrics", "nowhere.open", copy / "bench")


def test_an_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        cells.resolve(ROOT, "flora_gcp.nosuchmix")
