"""BENCHMARK.json against the benchmark's files, and the rules it keeps."""

import json
import re
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_runs  # noqa: E402
from bench import harness, work  # noqa: E402

SPEC = harness.load_spec()
WITH_PENDING = bench_runs.spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", [w["name"] for w in WITH_PENDING["workloads"]])
def test_cell_resolves_by_name(cell):
    parts = harness.load_cell(WITH_PENDING, cell)
    assert parts["config"]["name"] == parts["cell"]["config"]
    for fn in ("setup", "step", "release", "check", "end_to_end", "after_check"):
        assert callable(getattr(parts["kind"], fn))
    names = {m["name"] for m in parts["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert parts["per_layer"], "every cell reports a per-layer metric"


@pytest.mark.parametrize("entry", WITH_PENDING["configs"], ids=lambda c: c["name"])
def test_config_file(entry):
    config = json.loads((ROOT / entry["file"]).read_text())
    assert config["name"] == entry["name"]
    if "reduced" in entry:
        assert config["source"] == entry["source"]
        assert sorted(config["reduced"]) == sorted(entry["reduced"])
    assert config["codec"]["coder"] == "huffman"


@pytest.mark.parametrize("metric", WITH_PENDING["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_reader_and_moves(metric):
    assert callable(harness.load_metric(metric["name"]))
    for cell in metric["workloads"]:
        reported = {m["name"] for m in harness.load_cell(WITH_PENDING, cell)["end_to_end"]}
        assert metric["moves"] in reported


def test_save_rate_is_the_save_cells_alone():
    """``save_GBps`` lists exactly the cells whose traffic saves."""
    saving = [w["name"] for w in SPEC["workloads"]
              if harness.load_cell(SPEC, w["name"])["traffic"]["kind"] == "save"]
    listed = next(m for m in SPEC["end_to_end"] if m["name"] == "save_GBps")["workloads"]
    assert listed == saving and "save.hubert_xlarge-train" in saving


def test_contract_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in SPEC[k]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    assert len({(w["config"], w["traffic"]) for w in SPEC["workloads"]}) == len(SPEC["workloads"])
    for w in SPEC["workloads"] + SPEC["configs"]:
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(1, len(SPEC["workloads"]) // 2)
    # a full check of 24 cells fits its time
    assert (2 + 14 * 24) * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_new_files_are_found_without_edits(tmp_path):
    """A configuration, a traffic mix, a cell and a metric added as files."""
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench, ignore=shutil.ignore_patterns("tests", "__pycache__"))
    config = json.loads((bench / "configs" / "qwen15_4b-L2.json").read_text())
    config["name"] = "qwen15_4b-L4"
    config["num_hidden_layers"] = 4
    (bench / "configs" / "qwen15_4b-L4.json").write_text(json.dumps(config))
    mix = json.loads((bench / "traffic" / "restore_loop.json").read_text())
    mix["subtrees"] = None
    (bench / "traffic" / "restore_all.json").write_text(json.dumps(mix))
    (bench / "metrics" / "restores.restore.py").write_text("def read(m):\n    return m['ops']\n")
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": "qwen15_4b-L4", "source": config["source"],
                            "file": "bench/configs/qwen15_4b-L4.json",
                            "reduced": ["num_hidden_layers"], "why": "x"})
    spec["workloads"].append({"name": "restore_all.qwen15_4b-L4", "config": "qwen15_4b-L4",
                              "traffic": "restore_all", "chips": 1, "why": "x"})
    for m in spec["end_to_end"]:
        if m["name"] == "restore_GBps":
            m["workloads"].append("restore_all.qwen15_4b-L4")
    spec["per_layer"].append({"name": "restores.restore", "unit": "ops", "better": "higher",
                              "source": "host_clock", "layer": "device",
                              "moves": "restore_GBps"})
    parts = harness.load_cell(spec, "restore_all.qwen15_4b-L4", bench_dir=bench)
    assert parts["config"]["num_hidden_layers"] == 4
    assert parts["traffic"]["subtrees"] is None
    assert "restores.restore" in [m["name"] for m in parts["per_layer"]]
    assert harness.load_metric("restores.restore", bench_dir=bench)({"ops": 3}) == 3


def test_peaks_known_and_unknown_device():
    v5e = work.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["bf16_flop_per_s"] == 197e12
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
    assert "cloud.google.com" in json.loads(work.PEAKS.read_text())["source"]
