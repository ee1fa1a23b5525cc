"""A configuration, the module of its kind, a traffic mix and a per-layer
metric are found by name: adding them is adding files and entries, never
editing one."""
import json
import shutil
from pathlib import Path

import pytest

from harness import BenchError, Layout

BENCH = Path(__file__).parents[1]


@pytest.fixture
def root(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    return tmp_path


def test_new_config_mix_and_metric_are_found_by_name(root):
    spec = json.loads((root / "BENCHMARK.json").read_text())
    (root / "bench/configs/other.json").write_text(json.dumps(
        {"kind": "other_kind", "model": {"num_layers": 2}}))
    (root / "bench/other_kind.py").write_text(
        "def run(cell, layout, seed, seconds, trace, t_start):\n"
        "    return {'seed': seed}, {}\n")
    (root / "bench/traffic/burst.json").write_text(json.dumps(
        {"kind": "open_loop", "rate_per_s": 3.0}))
    (root / "bench/metrics/new_metric.py").write_text(
        "def read(rec):\n    return rec['x'] * 2\n")
    spec["configs"].append({"name": "other", "source": "s",
                            "file": "bench/configs/other.json",
                            "reduced": [], "why": "w"})
    spec["workloads"].append({"name": "other-burst", "config": "other",
                              "traffic": "burst", "chips": 1, "why": "w"})
    spec["per_layer"].append({"name": "new_metric", "unit": "ms",
                              "better": "lower", "source": "program_span",
                              "layer": "model", "moves": "ttft_p90_ms",
                              "workloads": ["other-burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    lay = Layout(root)
    cell = lay.cell("other-burst")
    assert cell.config["model"] == {"num_layers": 2}
    assert cell.traffic["rate_per_s"] == 3.0
    assert [m["name"] for m in cell.per_layer] == ["new_metric"]
    assert lay.reader("new_metric")({"x": 21}) == 42
    run = lay.runner(cell.config["kind"])
    assert run(cell, lay, 7, 1.0, False, 0.0) == ({"seed": 7}, {})
    # the cells already there are unchanged
    old = lay.cell("danube-chat-bf16")
    assert "new_metric" not in [m["name"] for m in old.per_layer]


def test_every_metric_of_the_benchmark_has_a_reader():
    lay = Layout(BENCH.parent)
    for m in lay.spec()["per_layer"]:
        assert callable(lay.reader(m["name"]))


def test_unknown_cell_and_device_are_errors(root):
    lay = Layout(root)
    with pytest.raises(BenchError):
        lay.cell("no-such-cell")
    with pytest.raises(BenchError):
        lay.runner("no_such_kind")
    with pytest.raises(BenchError):
        lay.peaks("cpu")
    assert lay.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
