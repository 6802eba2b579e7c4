"""The entry point refuses a machine without a TPU; whole runs of every cell
at a small size on the CPU come out correct, and a traced one reports every
per-layer metric listed for its cell."""

import json
import math
import os
import subprocess
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_runs as runs  # noqa: E402
from bench import harness  # noqa: E402

ROOT = runs.ROOT


def test_run_py_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "restore.qwen15_4b-L2",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "metrics" not in p.stdout and "correct" not in p.stdout
    assert "not a TPU" in p.stderr


@pytest.mark.parametrize("cell", ["restore.qwen15_4b-L2", "save.hubert_xlarge-train",
                                  "serve.qwen15_4b-L2", "resume.hubert_xlarge-train"])
def test_sound_run_is_correct(cell):
    r = runs.run(cell)
    assert r["correct"], r["log"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert "compiles=0" in r["log"]
    assert list(r)[-2:] == ["compared", "log"]               # compared comes last
    assert set(r["metrics"]) >= {"setup_s", "stored_per_raw"}
    json.dumps(r)


def test_save_window_holds_whole_cycles():
    """Past its deadline the save cell's window runs on to the end of a
    base/delta cycle, so that every window saves one base to four deltas."""
    r = runs.run("save.hubert_xlarge-train", seconds=0.01)
    base_every = harness.load_cell(runs.spec(), "save.hubert_xlarge-train")[
        "config"]["checkpoint"]["base_every"]
    assert r["correct"], r["log"]
    assert r["attempted"] == base_every and f"ops={base_every} " in r["log"]


# Every kernel a cell's timed path runs, under its op name on the chip.
KERNEL_OPS = ("huffdecode_chunks_multi", "plane_consumer", "bitpack_encode_chunks_multi",
              "xor_elems_2d", "bytegroup_fp32_2d", "bytegroup_bf16_2d", "chunk_histogram_2d")


@pytest.mark.parametrize("cell", [w["name"] for w in runs.spec()["workloads"]])
def test_traced_run_reports_every_listed_metric(cell, monkeypatch):
    """The whole traced path, with the chip's part stood in for: a v5e named
    for the table of peaks, and a device trace reduced to one second of each
    kernel in a ten-second window (a CPU trace holds no TPU plane)."""
    from bench import trace

    real = harness.device_info
    monkeypatch.setattr(harness, "device_info", lambda *a: {
        **real(*a), "platform": "tpu", "kind": "TPU v5 lite"})
    monkeypatch.setattr(trace, "find_xplane", lambda d: d)
    monkeypatch.setattr(trace, "reduce_file", lambda path: trace.Reduced(
        window_s=10.0, busy_s=9.0, chips=1, op_s=dict.fromkeys(KERNEL_OPS, 1.0)))
    r = runs.run(cell, trace=True)
    assert r["correct"], r["log"]
    listed = {m["name"]: m for m in harness.load_cell(runs.spec(), cell)["per_layer"]}
    assert set(r["metrics"]) == set(listed)
    for name, v in r["metrics"].items():
        assert math.isfinite(v["value"]) and v["value"] >= 0, (name, v)
        assert v["unit"] == listed[name]["unit"]
        if v["unit"] == "%":
            assert v["value"] <= 100.0, (name, v)
    assert r["device"]["busy_s"] == 9.0 and r["breakdown"]["device_ops"]
