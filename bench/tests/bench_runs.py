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
            "traffic": TRAFFIC.get(cell.split(".")[0], {})}


TRAFFIC = {"serve": {"batch": 2, "cache_len": 16, "start_pos": 8, "control_steps": 2},
           "save": {"updates_between_saves": 2}}


# A cell whose code and files are here but which BENCHMARK.json does not list
# (its rate spread too widely on the chip, PERF.md §7); the tests run it all
# the same, listed for its end-to-end metric and every reader of that metric.
PENDING = {
    "workload": {"name": "resume.hubert_xlarge-train", "config": "hubert_xlarge-train",
                 "traffic": "resume_delta", "chips": 1},
    "moves": "restore_GBps",
    "per_layer": [{"name": "plane_consumer_roofline.resume", "unit": "%", "better": "higher",
                   "source": "device_trace", "layer": "kernels", "moves": "restore_GBps"}],
}


def spec():
    """BENCHMARK.json with the pending cell added where it lacks it."""
    from bench import harness

    s = harness.load_spec()
    cell = PENDING["workload"]["name"]
    if any(w["name"] == cell for w in s["workloads"]):
        return s
    s["workloads"].append(dict(PENDING["workload"]))
    s["per_layer"] += [dict(m, workloads=[]) for m in PENDING["per_layer"]]
    for m in s["end_to_end"] + s["per_layer"]:
        if PENDING["moves"] in (m["name"], m.get("moves")) and "workloads" in m:
            m["workloads"].append(cell)
    return s


def run(cell, seed=2**33 + 5, seconds=0.5, control=False, trace=False):
    import io
    import tempfile

    from bench import harness

    log = io.StringIO()
    with tempfile.TemporaryDirectory() as work:
        r = harness.run_cell(cell, seed, seconds, trace, t_start=time.perf_counter(),
                             require_tpu=False, overrides=overrides(cell), control=control,
                             workroot=Path(work), spec=spec(), log=log)
    r["log"] = log.getvalue()
    return r
