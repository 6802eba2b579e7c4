"""The entry point refuses a machine without a TPU; whole runs of every cell
at a small size on the CPU come out correct."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_runs as runs  # noqa: E402

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
