"""Spans and counters of the codec, checkpoint and serving paths.

The contract under test (``repro.core.tracing``): spans record exactly
while a profiler session is active and land on the profiler's host plane;
self time is a span's duration less its children's on the same thread; a
ring step's worker spans carry the step's operation id; the counters keep
their meaning (``payload_*`` as the entropy paths have always counted
them, one ``d2h_fetches`` per blocking fetch) and repeat exactly from one
ring step to the next.
"""

import math
import time

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointConfig, CheckpointManager
from repro.core import codec, container, device_entropy, tracing, zipnn
from repro.serve import CompressedParamStore, make_compressed_serve_step
from test_serve_compressed import _tiny

HUFF = zipnn.ZipNNConfig(chunk_param_bytes=1 << 14, backend="huffman")
DEV = zipnn.CodecOptions(backend="device", entropy_backend="device")


@pytest.fixture
def fresh():
    tracing.reset()
    yield
    tracing.reset()


def _params(seed: int = 0):
    rng = np.random.default_rng(seed)
    return {
        "w": rng.standard_normal((64, 512)).astype(ml_dtypes.bfloat16),
        "b": rng.standard_normal((96, 256)).astype(np.float32),
        "z": np.zeros((32, 128), ml_dtypes.bfloat16),
    }


@pytest.fixture
def manager(tmp_path):
    mgr = CheckpointManager(CheckpointConfig(
        directory=str(tmp_path / "ckpt"), zipnn=HUFF,
        backend="device", entropy_backend="device",
    ))
    mgr.save(1, _params(), blocking=True)
    mgr.restore(device_resident=True)               # compile every shape
    return mgr


def _traced(tmp_path, fn):
    with jax.profiler.trace(str(tmp_path / "trace")):
        out = fn()
        jax.block_until_ready(out)
    return out


def test_span_nesting_and_self_time(tmp_path, fresh):
    def work():
        with tracing.operation("znn.test.op"):
            with tracing.span("znn.test.outer"):
                time.sleep(0.02)
                with tracing.span("znn.test.inner"):
                    time.sleep(0.03)
                with tracing.span("znn.test.inner"):
                    time.sleep(0.01)
        return 0

    _traced(tmp_path, work)
    spans = tracing.snapshot()["spans"]
    op = spans["znn.test.op"]["caller"]
    outer = spans["znn.test.outer"]["caller"]
    inner = spans["znn.test.inner"]["caller"]
    assert (op["count"], outer["count"], inner["count"]) == (1, 1, 2)
    assert inner["total_s"] >= 0.04 and outer["total_s"] >= 0.06
    assert outer["self_s"] == pytest.approx(outer["total_s"] - inner["total_s"], abs=1e-9)
    assert op["self_s"] == pytest.approx(op["total_s"] - outer["total_s"], abs=1e-9)
    assert 0.015 <= outer["self_s"] < outer["total_s"]
    recs = tracing.records()
    assert [r[0] for r in recs] == ["znn.test.inner", "znn.test.inner",
                                    "znn.test.outer", "znn.test.op"]
    assert [r[5] for r in recs] == ["znn.test.outer", "znn.test.outer",
                                    "znn.test.op", None]
    assert len({r[6] for r in recs}) == 1 and recs[0][6] is not None


def test_nothing_recorded_without_a_session(tmp_path, fresh, manager):
    manager.restore(device_resident=True)
    with tracing.span("znn.test.off"):
        pass
    snap = tracing.snapshot()
    assert snap["spans"] == {} and snap["traced"] == {} and snap["records"] == 0
    assert tracing.current_op() is None
    assert snap["counters"]["launches.huffdecode"] > 0      # counters are always on

    _traced(tmp_path, lambda: manager.restore(device_resident=True)[1])
    snap = tracing.snapshot()
    for name in ("znn.ckpt.restore", "znn.ckpt.scan", "znn.ckpt.read",
                 "znn.ckpt.entry_crc", "znn.codec.decode_tree", "znn.codec.parse",
                 "znn.codec.chunk_crc", "znn.codec.luts", "znn.codec.pack_words",
                 "znn.codec.launch", "znn.codec.fetch", "znn.codec.cursor_check",
                 "znn.codec.host_chunks", "znn.codec.splice", "znn.codec.unplane"):
        assert snap["spans"][name]["caller"]["count"] >= 1, name
    assert snap["spans"]["znn.ckpt.restore"]["caller"]["count"] == 1
    assert snap["records"] == sum(
        a["count"] for by_role in snap["spans"].values() for a in by_role.values()
    )


def test_a_new_session_starts_a_fresh_buffer(tmp_path, fresh):
    def one(name):
        with tracing.span(name):
            pass
        return 0

    with jax.profiler.trace(str(tmp_path / "a")):
        one("znn.test.first")
    assert set(tracing.snapshot()["spans"]) == {"znn.test.first"}
    with jax.profiler.trace(str(tmp_path / "b")):
        one("znn.test.second")
    assert set(tracing.snapshot()["spans"]) == {"znn.test.second"}
    tracing.reset()
    assert tracing.snapshot()["spans"] == {}


def test_off_cost_is_one_check():
    tracing.counters()
    n, best = 20_000, math.inf
    for _ in range(5):
        t = time.perf_counter()
        for _ in range(n):
            with tracing.span("znn.test.off"):
                pass
        best = min(best, (time.perf_counter() - t) / n)
    assert tracing.snapshot()["records"] == 0
    assert best < 5e-6


def test_spans_land_on_the_host_plane(tmp_path, fresh, manager):
    from jax.profiler import ProfileData

    _traced(tmp_path, lambda: manager.restore(device_resident=True)[1])
    xplane, = (tmp_path / "trace").rglob("*.xplane.pb")
    profile = ProfileData.from_file(str(xplane))
    lines = [
        [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events
         if e.name.startswith("znn.")]
        for plane in profile.planes if plane.name.startswith("/host:")
        for line in plane.lines
    ]
    line = next(ev for ev in lines if any(n == "znn.ckpt.restore" for n, _, _ in ev))
    (a, b), = [(s, e) for n, s, e in line if n == "znn.ckpt.restore"]
    codec_events = [(s, e) for n, s, e in line if n.startswith("znn.codec.")]
    assert codec_events and all(a <= s and e <= b for s, e in codec_events)
    names = {n for n, _, _ in line}
    assert {"znn.ckpt.scan", "znn.codec.launch", "znn.codec.fetch"} <= names


def _expected_payload(manager, step: int):
    """``payload_*`` of one device-resident restore, from the containers:
    one upload of packed words per launch window (chunk-capacity words per
    HUFF chunk) and one splice upload of a leaf's non-HUFF chunks; and the
    number of HUFF chunks."""
    import json
    import os

    d = os.path.join(manager.cfg.directory, f"step_{step}")
    man = json.load(open(os.path.join(d, "manifest.json")))
    data = open(os.path.join(d, "data.bin"), "rb").read()
    uploads = nbytes = n_huff = 0
    for e in man["entries"]:
        meta, _ = container.unpack_stream(data[e["offset"]: e["offset"] + e["size"]])
        chunks = [c for pe in meta.entries for c in pe]
        huff = [c for c in chunks if c.method == codec.Method.HUFF]
        other = [c for c in chunks if c.method != codec.Method.HUFF]
        n_huff += len(huff)
        if huff:
            per_launch = max(1, device_entropy.MAX_BATCH_BYTES // (2 * meta.chunk_bytes))
            uploads += -(-len(huff) // per_launch)
            nbytes += len(huff) * meta.chunk_bytes
        if huff and other:
            uploads += 1
            nbytes += sum(c.raw_len for c in other)
    return uploads, nbytes, n_huff


def test_restore_counts_one_fetch_per_launch(tmp_path, fresh, manager):
    device_entropy.reset_transfer_stats()
    c0 = tracing.counters()
    manager.restore(device_resident=True)
    c1 = tracing.counters()
    untraced = device_entropy.transfer_stats()
    uploads, nbytes, n_huff = _expected_payload(manager, 1)
    assert (untraced["payload_uploads"], untraced["payload_bytes"]) == (uploads, nbytes)

    _traced(tmp_path, lambda: manager.restore(device_resident=True)[1])
    traced = tracing.snapshot()["traced"]
    assert traced["launches.huffdecode"] > 0
    assert traced["d2h_fetches"] == traced["launches.huffdecode"]
    assert traced["d2h_bytes"] == 4 * n_huff              # one int32 cursor a chunk
    for k in ("payload_uploads", "payload_bytes", "d2h_fetches", "launches.huffdecode",
              "launches.plane_consumer"):
        assert traced[k] == c1[k] - c0[k], k
    assert traced["payload_uploads"] == untraced["payload_uploads"]
    assert traced["payload_bytes"] == untraced["payload_bytes"]
    assert traced["compiles"] == 0


def test_transfer_stats_is_a_view_of_the_payload_counters(fresh):
    tracing.count_payload_upload(100)
    tracing.count("d2h_fetches", 3)
    assert device_entropy.transfer_stats() == {"payload_uploads": 1, "payload_bytes": 100}
    device_entropy.reset_transfer_stats()
    assert device_entropy.transfer_stats() == {"payload_uploads": 0, "payload_bytes": 0}
    assert tracing.counters()["d2h_fetches"] == 3


def test_compiles_are_counted(fresh):
    tracing.counters()
    before = tracing.counters()
    f = jax.jit(lambda x: x * 3 + 1)
    f(jnp.arange(7, dtype=jnp.int32)).block_until_ready()
    after = tracing.counters()
    assert after["compiles"] >= before["compiles"] + 1
    assert after["compile_s"] > before["compile_s"]


@pytest.fixture(scope="module")
def ring():
    cfg, model, params = _tiny("repro_gpt_100m")
    store = CompressedParamStore.from_params(
        params, zipnn.ZipNNConfig(chunk_param_bytes=1 << 15, backend="huffman"),
        options=DEV, payload_feed=True,
    )
    step = make_compressed_serve_step(model, store, ring=2)
    state = model.init_decode_state(1, 8, start_pos=0)
    toks = jnp.ones((1, 1), jnp.int32)
    _, state = step(state, toks)                      # compile every shape
    return cfg, store, step, [state, toks]


def test_ring_worker_spans_carry_the_step_id(tmp_path, fresh, ring):
    cfg, store, step, st = ring

    def two_steps():
        for _ in range(2):
            logits, st[0] = step(st[0], st[1])
        return logits

    _traced(tmp_path, two_steps)
    recs = tracing.records()
    steps = [r for r in recs if r[0] == "znn.ring.step"]
    assert len(steps) == 2 and all(r[4] == "caller" for r in steps)
    ops = [r[6] for r in steps]
    assert len(set(ops)) == 2
    workers = [r for r in recs if r[4] == "worker"]
    decodes = [r for r in workers if r[0] == "znn.ring.decode"]
    assert len(decodes) == 2 * cfg.n_layers
    for op, (_, t0, t1, *_rest) in zip(ops, steps):
        mine = [r for r in workers if r[6] == op]
        assert sum(r[0] == "znn.ring.decode" for r in mine) == cfg.n_layers
        assert all(t0 <= r[1] and r[2] <= t1 for r in mine)
    assert {r[6] for r in workers} == set(ops)
    assert {r[0] for r in workers} >= {"znn.ring.decode", "znn.feed.decode"}
    spans = tracing.snapshot()["spans"]
    assert spans["znn.ring.wait"]["caller"]["count"] == 2 * cfg.n_layers
    assert spans["znn.ring.layer"]["caller"]["count"] == 2 * cfg.n_layers
    assert "worker" not in spans["znn.ring.wait"]


def test_feed_dispatches_repeat_across_steps(fresh, ring):
    cfg, store, step, st = ring
    per_step = []
    for _ in range(3):
        c0 = tracing.counters()["feed_dispatches"]
        _, st[0] = step(st[0], st[1])
        per_step.append(tracing.counters()["feed_dispatches"] - c0)
    feeds = [f for key in store.stack_keys for layer in store._feeds[key]
             for f in layer if f is not None]
    assert feeds
    assert per_step == [sum(f.dispatches for f in feeds)] * 3
    assert per_step[0] > len(feeds)


def test_counters_and_spans_survive_many_threads(tmp_path, fresh):
    import sys
    import threading

    threads, n = 16, 400
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work(op):
            with tracing.joined(op):
                for _ in range(n):
                    with tracing.span("znn.test.thread"):
                        tracing.count_payload_upload(3)
                        tracing.count("feed_dispatches", 2)

        with jax.profiler.trace(str(tmp_path / "trace")):
            with tracing.operation("znn.test.root"):
                op = tracing.current_op()
                ts = [threading.Thread(target=work, args=(op,)) for _ in range(threads)]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join(timeout=60)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(old)
    snap = tracing.snapshot()
    assert snap["spans"]["znn.test.thread"]["worker"]["count"] == threads * n
    assert snap["traced"]["payload_uploads"] == threads * n
    assert snap["traced"]["payload_bytes"] == 3 * threads * n
    assert snap["traced"]["feed_dispatches"] == 2 * threads * n
    assert {r[6] for r in tracing.records()} == {op}
