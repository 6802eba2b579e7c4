"""Whole benchmark runs at a size a CPU test holds, with the chip check
skipped; shared by the run and fault tests."""

import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

QWEN = {"d_model": 128, "d_ff": 256, "n_heads": 4, "n_kv_heads": 4, "head_dim": 32,
        "vocab_size": 512, "max_position": 512}
HUBERT = {"d_model": 64, "d_ff": 128, "n_heads": 4, "n_kv_heads": 4, "head_dim": 16,
          "vocab_size": 64, "max_position": 256, "frontend_dim": 32}


def overrides(cell):
    return {"model": QWEN if "qwen" in cell else HUBERT,
            "codec": {"chunk_param_bytes": 8192},
            "traffic": ({"batch": 2, "cache_len": 16, "start_pos": 8, "control_steps": 2}
                        if cell.startswith("serve") else {})}


# Cells whose code and files are here but which BENCHMARK.json may not list
# yet; the tests run them all the same.
PENDING = {
    "config": {"name": "hubert_xlarge-train", "file": "bench/configs/hubert_xlarge-train.json"},
    "workloads": [("save.hubert_xlarge-train", "save_loop", "save_GBps"),
                  ("resume.hubert_xlarge-train", "resume_delta", "restore_GBps")],
}


def spec():
    """BENCHMARK.json with the pending cells added where it lacks them."""
    from bench import harness

    s = harness.load_spec()
    if not any(c["name"] == PENDING["config"]["name"] for c in s["configs"]):
        s["configs"].append(dict(PENDING["config"]))
    for name, traffic, metric in PENDING["workloads"]:
        if any(w["name"] == name for w in s["workloads"]):
            continue
        s["workloads"].append({"name": name, "config": PENDING["config"]["name"],
                               "traffic": traffic, "chips": 1})
        e2e = next((m for m in s["end_to_end"] if m["name"] == metric), None)
        if e2e is None:
            e2e = {"name": metric, "unit": "GB/s", "workloads": []}
            s["end_to_end"].append(e2e)
        e2e["workloads"].append(name)
    return s


def run(cell, seed=2**33 + 5, seconds=0.5, control=False):
    import io
    import tempfile

    from bench import harness

    log = io.StringIO()
    with tempfile.TemporaryDirectory() as work:
        r = harness.run_cell(cell, seed, seconds, False, t_start=time.perf_counter(),
                             require_tpu=False, overrides=overrides(cell), control=control,
                             workroot=Path(work), spec=spec(), log=log)
    r["log"] = log.getvalue()
    return r
