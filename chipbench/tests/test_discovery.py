"""A new cell or metric is found by name: one file added, none edited."""
import json
import shutil

import pytest

from chipbench.spec import HERE, ROOT, load_cell


@pytest.fixture
def checkout(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return tmp_path


def test_added_workload_and_metric_are_picked_up(checkout):
    bench = json.loads((checkout / "BENCHMARK.json").read_text())
    mix = json.loads((checkout / "chipbench" / "workloads" /
                      "smollm-360m.code-reuse.json").read_text())
    mix["arrival"] = {"process": "gamma", "cv": 2.0}
    (checkout / "chipbench" / "workloads" / "smollm-360m.added.json").write_text(
        json.dumps(mix))
    (checkout / "chipbench" / "metrics" / "added_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    bench["workloads"].append({"name": "smollm-360m.added",
                               "config": "smollm-360m",
                               "traffic": "smollm-360m.added", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "added_metric", "unit": "ms",
                               "better": "lower", "source": "program_span",
                               "layer": "test", "moves": "ttft_p95_ms",
                               "workloads": ["smollm-360m.added"]})
    (checkout / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = load_cell("smollm-360m.added", checkout)
    assert cell.traffic["arrival"]["cv"] == 2.0
    assert cell.config["name"] == "smollm-360m"
    added = [m for m in cell.per_layer if m.name == "added_metric"]
    assert added and added[0].read(None) == 42.0
    # the metric is for the added cell only
    other = load_cell("smollm-360m.code-reuse", checkout)
    assert "added_metric" not in [m.name for m in other.per_layer]


def test_every_named_file_exists():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = load_cell(w["name"])
        names = {m.name for m in cell.end_to_end + cell.per_layer}
        assert "setup_s" in names
        assert len(cell.end_to_end) >= 2 and cell.per_layer
