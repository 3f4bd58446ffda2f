"""A new cell, configuration, traffic mix or per-layer metric needs only
new files under bench/ and new entries in BENCHMARK.json."""
import copy
import json
import shutil

from bench import harness
from bench.tests.cells import ROOT


def _copy_tree(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    return root


def test_new_metric_file_is_found_by_name(tmp_path, monkeypatch):
    root = _copy_tree(tmp_path)
    (root / "bench" / "metrics" / "steps_seen.train.py").write_text(
        "def read(m):\n    return m.get('steps')\n")
    monkeypatch.setattr(harness, "ROOT", root)
    monkeypatch.setattr(harness, "BENCH", root / "bench")
    cell = harness.find_cell("qwen2-0.5b.train.s4k")
    cell.bench = copy.deepcopy(cell.bench)
    cell.bench["per_layer"].append(
        {"name": "steps_seen.train", "unit": "steps", "better": "higher",
         "source": "host_clock", "layer": "train step",
         "moves": "train_tokens_per_s"})
    meas = {"kind": "train", "steps": 7, "chips": 1, "window_s": 1.0,
            "step_flops": 1.0, "peak": {"bf16_flops_per_s": 1e12},
            "trace": {"idle_share_worst": 0.25}}
    got = harness.per_layer(cell, meas)
    assert got["steps_seen.train"] == {"value": 7.0, "unit": "steps"}
    assert got["idle_share.train"]["value"] == 25.0
    # a metric listed for other cells only is not read here
    assert "exposed_collective_share.train" not in got


def test_new_cell_config_and_traffic_by_name(tmp_path, monkeypatch):
    root = _copy_tree(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "bench/configs/qwen2-0.5b.json").read_text())
    (root / "bench/configs/qwen2-0.5b-x.json").write_text(json.dumps(cfg))
    shutil.copy(root / "bench/configs/qwen2-0.5b.py",
                root / "bench/configs/qwen2-0.5b-x.py")
    mix = json.loads((root / "bench/traffic/train.s4k.json").read_text())
    mix["seq"] = 2048
    (root / "bench/traffic/train.s2k.json").write_text(json.dumps(mix))
    (root / "bench/limits/qwen2-0.5b-x.train.s2k.json").write_text(
        (root / "bench/limits/qwen2-0.5b.train.s4k.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="qwen2-0.5b-x",
                                 file="bench/configs/qwen2-0.5b-x.json"))
    bench["workloads"].append(
        {"name": "qwen2-0.5b-x.train.s2k", "config": "qwen2-0.5b-x",
         "traffic": "train.s2k", "chips": 1, "why": "test"})
    monkeypatch.setattr(harness, "ROOT", root)
    monkeypatch.setattr(harness, "BENCH", root / "bench")
    cell = harness.find_cell("qwen2-0.5b-x.train.s2k", bench)
    assert cell.kind == "train" and cell.traffic["seq"] == 2048
    assert cell.model.shapes(cell.config)["embed"] == (151936, 896)
