"""The benchmark's driver: one cell, one process, one result line.

``run_cell`` reads the cell's entry in ``BENCHMARK.json`` and finds by name
the configuration file (``configs/``), the traffic mix (``traffic/<mix>.json``,
whose ``kind`` names the code in ``kinds/<kind>.py``) and the per-layer
metric readers (``metrics/<metric>.py``).  A cell, a mix or a metric is
added with new files and new entries, never by editing this file.

Every run: set-up (state from the seed, what the traffic needs, every shape
of the window warmed), then the window (operations back to back until
``seconds`` have passed; the one in flight at the deadline finishes and
counts, and a kind with a ``closes(run, ops)`` may ask for more, so that
the window holds whole units of its traffic), then the check against the
plain reference, then one JSON line.
"""

from __future__ import annotations

import contextlib
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
WORK = ROOT / ".bench_work"          # scratch checkpoints, removed at exit
TRACE_DIR = ROOT / ".bench_trace"


def load_spec(path: Path = ROOT / "BENCHMARK.json") -> dict:
    return json.loads(path.read_text())


def cell_entry(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no cell {name!r} in BENCHMARK.json")


def config_entry(spec: dict, name: str) -> dict:
    for c in spec["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def load_cell(spec: dict, name: str, bench_dir: Path = BENCH) -> dict:
    """Everything a cell names, resolved to files."""
    cell = cell_entry(spec, name)
    cfg_entry = config_entry(spec, cell["config"])
    config = json.loads((bench_dir.parent / cfg_entry["file"]).read_text())
    traffic = json.loads((bench_dir / "traffic" / f"{cell['traffic']}.json").read_text())
    kind = importlib.import_module(f"bench.kinds.{traffic['kind']}")
    end_to_end = [m for m in spec["end_to_end"]
                  if name in m.get("workloads", [name])]
    moved = {m["name"] for m in end_to_end}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m else m["moves"] in moved)]
    return {"cell": cell, "config": config, "traffic": traffic, "kind": kind,
            "end_to_end": end_to_end, "per_layer": per_layer}


def load_metric(name: str, bench_dir: Path = BENCH) -> Callable:
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileCounter:
    """Seconds and count of JAX compilations, from JAX's own events."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.seconds = 0.0
        self.backend_compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration
        if event == self.EVENTS[1]:
            self.backend_compiles += 1


@dataclass
class Run:
    """What a kind sees: the cell's files, the seed, and where to write."""

    name: str
    config: dict
    traffic: dict
    seed: int
    workdir: Path
    overrides: Dict[str, Any] = field(default_factory=dict)   # smaller sizes, for tests
    per_op: List[float] = field(default_factory=list)         # host seconds per operation
    extra: Dict[str, Any] = field(default_factory=dict)       # kind's own readings


def span(jax, name: str):
    return jax.profiler.TraceAnnotation(f"bench.{name}")


def device_info(jax, chips: int, require_tpu: bool = True) -> dict:
    devs = jax.devices()
    d0 = devs[0]
    if require_tpu and d0.platform != "tpu":
        raise SystemExit(f"refusing to measure: the default device is {d0.platform!r}, not a TPU")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, {len(devs)} found")
    return {"platform": d0.platform, "kind": d0.device_kind, "count": chips}


def memory_peak(jax, chips: int) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()[:chips]]
    return int(max(peaks))


def run_cell(name: str, seed: int, seconds: float, trace: bool, *, t_start: float,
             require_tpu: bool = True, overrides: Optional[dict] = None,
             spec: Optional[dict] = None, control: bool = False, workroot: Path = WORK,
             log=sys.stderr) -> dict:
    import jax

    spec = load_spec() if spec is None else spec
    parts = load_cell(spec, name)
    cell, kind = parts["cell"], parts["kind"]
    device = device_info(jax, cell["chips"], require_tpu)
    compiles = CompileCounter(jax)
    workdir = workroot / name
    shutil.rmtree(workdir, ignore_errors=True)
    workdir.mkdir(parents=True)
    overrides = overrides or {}
    config = dict(parts["config"])
    config["codec"] = {**config["codec"], **overrides.get("codec", {})}
    traffic = {**parts["traffic"], **overrides.get("traffic", {})}
    run = Run(name, config, traffic, seed, workdir, overrides)
    run.extra["control"] = control
    try:
        t_setup0 = time.perf_counter()
        c0 = compiles.seconds
        kind.setup(run, jax)
        setup_s = time.perf_counter() - t_start
        setup_compile_s = compiles.seconds - c0
        print(f"setup: setup_s={setup_s:.3f} compile_s={setup_compile_s:.3f} "
              f"work_s={setup_s - setup_compile_s:.3f} "
              f"before_setup_s={t_setup0 - t_start:.3f} "
              + " ".join(f"{k}={v}" for k, v in run.extra.pop("setup_split", {}).items()),
              file=log, flush=True)

        n_compiles = compiles.backend_compiles
        # What set-up left behind (compilation's above all) is collected now and
        # kept out of the collector's later passes, so no long pass lands in the
        # window of one run and not another's.
        gc.collect()
        gc.freeze()
        trace_dir = None
        if trace:
            trace_dir = TRACE_DIR / name
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        ops = 0
        closes = getattr(kind, "closes", lambda run, ops: True)
        with span(jax, "window"):
            w0 = time.perf_counter()
            while True:
                t = time.perf_counter()
                kind.step(run, jax, ops)
                run.per_op.append(time.perf_counter() - t)
                ops += 1
                if time.perf_counter() - w0 >= seconds and closes(run, ops):
                    break
            window_s = time.perf_counter() - w0
        gc.unfreeze()
        if trace:
            jax.profiler.stop_trace()
        window_compiles = compiles.backend_compiles - n_compiles
        print(f"window: ops={ops} window_s={window_s:.6f} compiles={window_compiles} "
              f"per_op_s={','.join(f'{x:.4f}' for x in run.per_op)}", file=log, flush=True)
        device["memory_peak_bytes"] = memory_peak(jax, cell["chips"])
        kind.release(run, jax)
        gc.collect()
        t_check = time.perf_counter()
        checks, attempted, failed = kind.check(run, jax, ops)
        print(f"check: seconds={time.perf_counter() - t_check:.3f}", file=log, flush=True)
        if trace:
            kind.after_check(run, jax)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    correct = failed == 0 and all(c["value"] <= c["limit"] for c in checks.values())
    from bench import work as work_mod

    # what a per-layer metric's reader sees
    m = {"window_s": window_s, "ops": ops, "run": run, "trace": None,
         "peaks": work_mod.peaks(device["kind"]) if device["platform"] == "tpu" else None}
    metrics: Dict[str, dict] = {}
    result: Dict[str, Any] = {"correct": correct, "attempted": attempted, "failed": failed}
    if not trace:
        values = kind.end_to_end(run, window_s, ops)
        values["setup_s"] = setup_s
        for e in parts["end_to_end"]:
            if e["name"] in values:
                metrics[e["name"]] = {"value": values[e["name"]], "unit": e["unit"]}
    else:
        from bench import trace as trace_mod

        red = trace_mod.reduce_file(trace_mod.find_xplane(str(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        m["trace"] = red
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        for e in parts["per_layer"]:
            value = load_metric(e["name"])(m)
            if value is not None:
                metrics[e["name"]] = {"value": value, "unit": e["unit"]}
        result["breakdown"] = {"device_ops": [[n, s] for n, s in red.device_ops()],
                               "idle_gaps": [[n, s] for n, s in red.idle_gaps]}
    if control:
        result["control"] = {k: run.extra[k] for k in ("served_gap", "control_gap")
                             if k in run.extra}
    result["metrics"] = metrics
    result["device"] = device
    for k, c in checks.items():
        print(f"compared: {k}={c['value']} limit={c['limit']}", file=log, flush=True)
    result["compared"] = {k: {"value": c["value"], "limit": c["limit"]} for k, c in checks.items()}
    return result
