"""The readers of the program's spans and counters, on a tiny restore,
resume, save and ring step of the benchmark's own kinds, traced on the
CPU."""

import math
import time

import pytest

from bench_runs import overrides, spec

RESTORE = ["ckpt_host_share.restore", "codec_host_share.restore",
           "device_wait_share.restore", "host_syncs_per_op.restore"]
READERS = {
    "restore.qwen15_4b-L2": RESTORE,
    "resume.hubert_xlarge-train": RESTORE,
    "save.hubert_xlarge-train": ["snapshot_share.save", "write_share.save"],
    "serve.qwen15_4b-L2": ["ring_wait_share.serve", "feed_dispatch_share.serve",
                           "dispatches_per_step.serve"],
}
SHARES = {"ckpt_host_share.restore", "codec_host_share.restore", "device_wait_share.restore",
          "ring_wait_share.serve", "feed_dispatch_share.serve", "snapshot_share.save",
          "write_share.save"}


@pytest.mark.parametrize("cell", sorted(READERS))
def test_readers_read_the_programs_spans(cell, tmp_path):
    import jax

    from bench import harness
    from repro.core import tracing

    parts = harness.load_cell(spec(), cell)
    assert set(READERS[cell]) <= {m["name"] for m in parts["per_layer"]}
    ov = overrides(cell)
    config = dict(parts["config"])
    config["codec"] = {**config["codec"], **ov["codec"]}
    run = harness.Run(cell, config, {**parts["traffic"], **ov["traffic"]}, 2**33 + 7,
                      tmp_path / "work", ov)
    run.workdir.mkdir()
    run.extra["control"] = False
    kind = parts["kind"]
    kind.setup(run, jax)
    readers = {name: harness.load_metric(name) for name in READERS[cell]}

    tracing.reset()
    empty = {"window_s": 1.0, "ops": 1, "run": run, "trace": None, "peaks": None}
    assert all(read(empty) is None for read in readers.values())

    ops = 2
    with jax.profiler.trace(str(tmp_path / "trace")):
        w0 = time.perf_counter()
        for i in range(ops):
            kind.step(run, jax, i)
        window_s = time.perf_counter() - w0
    m = {"window_s": window_s, "ops": ops, "run": run, "trace": None, "peaks": None}
    values = {name: read(m) for name, read in readers.items()}
    kind.release(run, jax)
    for name, v in values.items():
        assert v is not None and math.isfinite(v) and v >= 0, (name, v)
        if name in SHARES:
            assert v <= 100.0, (name, v)
    if cell.startswith(("restore", "resume")):
        assert values["host_syncs_per_op.restore"] >= 1
        assert values["device_wait_share.restore"] > 0
    elif cell.startswith("save"):
        assert values["snapshot_share.save"] > 0 and values["write_share.save"] > 0
    else:
        assert values["dispatches_per_step.serve"] > 0
        assert values["ring_wait_share.serve"] > 0
    tracing.reset()
    assert all(read(m) is None for read in readers.values())
