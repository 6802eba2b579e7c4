"""work.py byte counts and the plain checkpoint reader against the program."""

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import work  # noqa: E402
from bench.reference import znn  # noqa: E402


def _leaf(seed, shape, dtype):
    import ml_dtypes

    rng = np.random.default_rng(seed)
    x = rng.standard_normal(shape) * 0.02
    return x.astype(ml_dtypes.bfloat16 if dtype == "bfloat16" else dtype)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_kernel_bytes_match_container(dtype):
    from repro.core import codec, container, zipnn

    x = _leaf(5, (96, 1000), dtype)
    blob = zipnn.compress_array(x, zipnn.ZipNNConfig(backend="huffman", chunk_param_bytes=16384)).blob
    meta, _ = container.unpack_stream(blob)
    huff = [e for plane in meta.entries for e in plane if e.method == codec.Method.HUFF]
    assert huff, "the exponent plane is Huffman coded"
    st = znn.parse(blob)
    want = sum(e.comp_len + e.raw_len for e in huff)
    assert work.huffdecode_bytes([st]) == want == work.bitpack_bytes([st])
    assert st.n_bytes == x.nbytes


def test_reader_rebuilds_full_and_delta_chains(tmp_path):
    from repro.checkpoint import CheckpointConfig, CheckpointManager
    from repro.core import zipnn

    mgr = CheckpointManager(CheckpointConfig(
        str(tmp_path), async_save=False,
        zipnn=zipnn.ZipNNConfig(backend="huffman", chunk_param_bytes=16384)))
    p, m = _leaf(1, (64, 700), "float32"), _leaf(2, (64, 700), "float32")
    b = _leaf(3, (300,), "bfloat16")
    states = []
    for i in range(3):
        s = {"params": {"p": p + i * 1e-4, "b": b}, "opt": {"m": {"p": m * 0.9 ** i}}}
        mgr.save(i, s, blocking=True)
        states.append(s)
    ck = znn.Checkpoint(tmp_path)
    kinds = {e["key"]: e["kind"] for e in ck.manifest(2)["entries"]}
    assert kinds == {"opt/m/p": "delta_prev", "params/b": "delta", "params/p": "delta"}
    wants, expect = [], {}
    for step, s in enumerate(states):
        for key, arr in (("params/p", s["params"]["p"]), ("params/b", s["params"]["b"]),
                         ("opt/m/p", s["opt"]["m"]["p"])):
            e = ck.stream(step, key).chunk_bytes
            words = arr.reshape(-1).view(np.uint32 if arr.itemsize == 4 else np.uint16)
            for c in range(len(ck.stream(step, key).chunks)):
                wants.append((step, key, c))
                expect[(step, key, c)] = words[c * e:(c + 1) * e]
    got = ck.read_chunks(wants)
    for w in wants:
        np.testing.assert_array_equal(got[w], expect[w], err_msg=str(w))


def test_reader_rejects_a_corrupt_payload(tmp_path):
    from repro.core import zipnn

    blob = bytearray(zipnn.compress_array(_leaf(4, (64, 512), "float32"),
                                          zipnn.ZipNNConfig(backend="huffman")).blob)
    st = znn.parse(bytes(blob))
    ch = next(c for row in st.chunks for c in row if c.method == znn.HUFF)
    blob[ch.offset + 3] ^= 0x10
    step = tmp_path / "step_0"
    step.mkdir()
    (step / "data.bin").write_bytes(bytes(blob))
    (step / "manifest.json").write_text(
        '{"kind": "base", "entries": [{"key": "x", "kind": "full", "offset": 0, "size": %d}]}'
        % len(blob))
    with pytest.raises(ValueError):
        znn.Checkpoint(tmp_path).read_chunks([(0, "x", 0)])


def test_plane_bytes_hand_counted():
    """An fp32 leaf of 40,000 words (4 planes of 40,000 bytes in chunks of
    16,384, 16,384 and 7,232) stored in full, and one of 1,000 words (one
    chunk of 1,000 bytes a plane) stored as an XOR delta."""
    def stream(plane_chunks):
        rows = [[znn.Chunk(znn.HUFF, 1, n, 0, 0) for _ in range(4)] for n in plane_chunks]
        return znn.Stream("fp32", 4 * sum(plane_chunks), 16384, [None] * 4, rows)

    full, delta = stream([16384, 16384, 7232]), stream([1000])
    assert work.raw_bytes(full) == 160_000 and work.raw_bytes(delta) == 4_000
    pairs = [(full, False), (delta, True)]
    # producer: full raw read + planes written, delta raw and base read + planes
    # written; consumer: the same bytes the other way
    assert work.plane_bytes(pairs) == 2 * 160_000 + 3 * 4_000 == 332_000
    assert work.plane_bytes([]) == 0


def test_histogram_bytes_hand_counted():
    """The same two leaves as above: every plane byte read, 1,024 bytes of
    bins written for each of the 3 x 4 and 1 x 4 chunks of a plane."""
    def stream(plane_chunks):
        rows = [[znn.Chunk(znn.HUFF, 1, n, 0, 0) for _ in range(4)] for n in plane_chunks]
        return znn.Stream("fp32", 4 * sum(plane_chunks), 16384, [None] * 4, rows)

    full, delta = stream([16384, 16384, 7232]), stream([1000])
    assert work.histogram_bytes([full, delta]) == 160_000 + 4_000 + 1024 * (12 + 4) == 180_384
    assert work.histogram_bytes([]) == 0


def test_raw_bytes_match_container():
    from repro.core import zipnn

    x = _leaf(6, (3, 7001), "float32")               # a short last chunk
    blob = zipnn.compress_array(x, zipnn.ZipNNConfig(backend="huffman", chunk_param_bytes=16384)).blob
    assert work.raw_bytes(znn.parse(blob)) == x.nbytes


def test_window_work_counts_saves_that_retention_removed(tmp_path):
    """Five saves, a base every second and two bases kept: the first base and
    delta are gone, and count as the mean of the saves of their kind left."""
    from bench.kinds import save
    from repro.checkpoint import CheckpointConfig, CheckpointManager
    from repro.core import zipnn

    mgr = CheckpointManager(CheckpointConfig(
        str(tmp_path), async_save=False, base_every=2, keep_bases=2,
        zipnn=zipnn.ZipNNConfig(backend="huffman", chunk_param_bytes=16384)))
    p, m = _leaf(7, (32, 900), "float32"), _leaf(8, (32, 900), "float32")
    for i in range(5):
        mgr.save(i, {"params": {"p": p + i * 1e-3}, "opt": {"m": {"p": m * 0.9 ** i}}},
                 blocking=True)
    ck = znn.Checkpoint(tmp_path)
    assert ck.steps() == [2, 3, 4]

    def count(step):
        pairs = [(ck.stream(step, e["key"]), e["kind"] != "full")
                 for e in ck.manifest(step)["entries"]]
        return np.array([work.bitpack_bytes(st for st, _ in pairs),
                         work.plane_bytes(pairs)], np.float64)

    want = count(2) + count(3) + count(4) + (count(2) + count(4)) / 2 + count(3)
    got = save.window_work(ck, [(i, i) for i in range(5)], 0, 2)
    assert list(got) == list(save.WORK)
    np.testing.assert_allclose([got["bitpack"], got["plane_producer"]], want)
    assert got["plane_producer"] == 2 * p.nbytes * 2 * 3 + 3 * p.nbytes * 2 * 2  # 3 bases, 2 deltas
    assert save.window_work(ck, [(i, i) for i in range(2)], 0, 1) == {}
