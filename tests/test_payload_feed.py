"""Device-resident payload feed: zero per-token host→device payload traffic.

The contract under test: a :class:`~repro.core.device_entropy.PayloadFeed`
(and its per-leaf wrapper :class:`~repro.core.zipnn.ArrayFeed`) parses a
ZNN1 payload **once**, uploads the packed words to device memory **once**,
and every later :meth:`decode` re-runs the fused Huffman kernel straight
from those resident buffers — the module's transfer counters record zero
payload uploads per decode.  Residency and tiling are wall-clock/memory
knobs only: decoded bytes, ring logits and stream files stay bit-identical.

Rides along: the per-tile ring scheduler (`tiles=` in
``make_compressed_serve_step``), the bounded `_stacked_luts` cache, the
``ZIPNN_MAX_BATCH_BYTES`` env knob, the engine's ``pipeline_depth``, and
the encode-side resident-plane symbol gather.
"""

import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    codec,
    container,
    device_entropy,
    device_plane,
    engine,
    huffman,
    zipnn,
)
from test_device_decode import ASSEMBLY_CASES, assembly_stream
from test_serve_compressed import _lockstep, _tiny

from repro.serve import CompressedParamStore, make_compressed_serve_step

# fp32 + 1<<14 param bytes -> chunk_bytes 4096: word-aligned (feed-eligible)
# but *not* a CHUNK_ALIGN_BYTES multiple, so the plane stage runs on host —
# the decode feed must not care which encoder produced the blob.
HUFF = zipnn.ZipNNConfig(chunk_param_bytes=1 << 14, backend="huffman")
DEV = zipnn.CodecOptions(backend="device", entropy_backend="device")


def _feed_payloads(blob: bytes):
    """Container-parse ``blob`` into PayloadFeed's build inputs."""
    meta, mv = container.unpack_stream(blob)
    payloads = [
        [container.payload_view(meta, mv, p, c) for c in range(len(meta.entries[p]))]
        for p in range(meta.n_planes)
    ]
    return meta, payloads


# ---------------------------------------------------------------------------
# ArrayFeed / PayloadFeed: byte identity + the zero-upload decode contract
# ---------------------------------------------------------------------------

class TestArrayFeed:
    def test_round_trip_zero_decode_uploads(self):
        rng = np.random.default_rng(0)
        arr = rng.standard_normal(12_345).astype(np.float32)
        ct = zipnn.compress_array(arr, HUFF)
        feed = zipnn.build_array_feed(ct, HUFF)
        assert feed is not None
        assert feed.device_bytes > 0
        device_entropy.reset_transfer_stats()
        for _ in range(3):                      # every decode, not just the first
            out = feed.decode()
            assert not isinstance(out, np.ndarray)        # stayed on device
            assert np.asarray(out).tobytes() == arr.tobytes()
        assert device_entropy.transfer_stats()["payload_uploads"] == 0

    def test_mixed_methods_match_per_call_decode(self):
        """ZERO + STORE/ZLIB chunks ride the resident splice, HUFF chunks the
        resident words — reassembly equals the per-call decoder bit for bit."""
        rng = np.random.default_rng(1)
        arr = rng.standard_normal(3 * (1 << 12) + 777).astype(np.float32)
        arr[: 1 << 12] = 0.0                    # ZERO chunks in the top planes
        ct = zipnn.compress_array(arr, HUFF)
        feed = zipnn.build_array_feed(ct, HUFF)
        assert feed is not None
        want = zipnn.decompress_array(ct, HUFF, options=DEV.replace(device_resident=True))
        assert np.asarray(feed.decode()).tobytes() == np.asarray(want).tobytes()
        assert np.asarray(feed.decode()).tobytes() == arr.tobytes()

    def test_bf16_round_trip(self):
        import ml_dtypes

        rng = np.random.default_rng(2)
        arr = rng.standard_normal((96, 64)).astype(ml_dtypes.bfloat16)
        ct = zipnn.compress_array(arr, HUFF)
        feed = zipnn.build_array_feed(ct, HUFF)
        assert feed is not None
        out = feed.decode()
        assert out.shape == (96, 64)
        assert np.asarray(out).tobytes() == arr.tobytes()

    def test_empty_and_tail_and_foreign_blob_fall_back(self):
        """Ineligible leaves return None — the store then uses the per-call
        decoder, so None is a fallback signal, never an error."""
        empty = zipnn.compress_array(np.empty(0, np.float32), HUFF)
        assert zipnn.build_array_feed(empty, HUFF) is None
        # trailing bytes past the recorded payloads (TAIL remainder shape)
        ct = zipnn.compress_array(np.ones(64, np.float32), HUFF)
        tail = zipnn.CompressedTensor(ct.blob + b"\x00", ct.dtype, ct.shape)
        assert zipnn.build_array_feed(tail, HUFF) is None
        # non-word chunk geometry: whole feed build refuses up front
        meta, payloads = _feed_payloads(ct.blob)
        with pytest.raises(ValueError, match="whole-uint32-word"):
            device_entropy.PayloadFeed(
                meta.entries, payloads, meta.tables,
                codec.CodecParams(chunk_bytes=6),
            )

    def test_build_detects_corrupt_payload(self):
        """Integrity moves to build time: a flipped payload byte fails the
        CRC check while constructing the feed, not at some later decode."""
        rng = np.random.default_rng(3)
        arr = rng.standard_normal(1 << 12).astype(np.float32)
        ct = zipnn.compress_array(arr, HUFF)
        meta, payloads = _feed_payloads(ct.blob)
        params = codec.CodecParams(chunk_bytes=meta.chunk_bytes, backend="huffman")
        # unmutated build works
        device_entropy.PayloadFeed(meta.entries, payloads, meta.tables, params)
        victim = next(
            (p, c)
            for p in range(meta.n_planes)
            for c in range(len(payloads[p]))
            if len(payloads[p][c])
        )
        bad = bytearray(payloads[victim[0]][victim[1]])
        bad[0] ^= 0xFF
        payloads[victim[0]][victim[1]] = bytes(bad)
        with pytest.raises(IOError, match="CRC mismatch"):
            device_entropy.PayloadFeed(meta.entries, payloads, meta.tables, params)


class TestFeedAssembly:
    """The feed puts its planes together with the same compiled assembly as
    ``decode_planes(device_resident=True)``: byte-identical to the host
    path on every stream shape, in a launch per window plus one assembly."""

    @pytest.mark.parametrize("name", ASSEMBLY_CASES)
    def test_decode_matches_host_path(self, name, monkeypatch):
        planes, entries, payloads, tables, params, methods, max_batch = (
            assembly_stream(name)
        )
        assert methods <= {e.method for pe in entries for e in pe}
        monkeypatch.setattr(device_entropy, "MAX_BATCH_BYTES", max_batch)
        host = device_entropy.decode_planes(entries, payloads, tables, params)
        feed = device_entropy.PayloadFeed(entries, payloads, tables, params)
        assert feed.n_planes == len(host)
        for _ in range(2):                    # every decode, not just the first
            got = feed.decode()
            assert len(got) == len(host)
            for g, h in zip(got, host):
                assert np.array_equal(np.asarray(g), h)
        if planes is not None:
            for h, p in zip(host, planes):
                assert np.array_equal(h, p)

    @pytest.mark.parametrize("name", ["mixed_methods", "two_windows", "all_store"])
    def test_decode_issues_counted_dispatches(self, name, monkeypatch):
        """Every device op :meth:`decode` issues is a top-level equation
        of its trace: exactly the feed's own count of them."""
        _, entries, payloads, tables, params, _, max_batch = assembly_stream(name)
        monkeypatch.setattr(device_entropy, "MAX_BATCH_BYTES", max_batch)
        feed = device_entropy.PayloadFeed(entries, payloads, tables, params)
        eqns = jax.make_jaxpr(feed.decode)().eqns
        assert len(eqns) == feed._count_dispatches() == feed.dispatches
        assert len(eqns) == len(feed._windows) + 1


# ---------------------------------------------------------------------------
# serving: the per-token transfer contract and per-tile decode
# ---------------------------------------------------------------------------

SERVE_CFG = zipnn.ZipNNConfig(chunk_param_bytes=1 << 15, backend="huffman")


class TestServeTransferContract:
    def test_zero_payload_uploads_after_warmup(self):
        """payload_feed=True: all uploads happen at store build; tokens after
        the jit warmup move zero payload bytes host→device.  The same ring
        without the feed re-uploads payloads every single token."""
        cfg, model, params = _tiny("repro_gpt_100m")
        store = CompressedParamStore.from_params(
            params, SERVE_CFG, options=DEV, payload_feed=True
        )
        assert store.device_payload_bytes > 0
        cstep = make_compressed_serve_step(model, store)
        B, steps = 1, 2
        state = model.init_decode_state(B, steps + 1, start_pos=0)
        toks = jnp.ones((B, 1), jnp.int32)
        _, state = cstep(state, toks)           # warmup: compile + first ring
        device_entropy.reset_transfer_stats()
        for _ in range(steps):
            _, state = cstep(state, toks)
        assert device_entropy.transfer_stats() == {
            "payload_uploads": 0,
            "payload_bytes": 0,
        }
        # contrast: the feed-less ring pays per-token payload uploads
        store2 = CompressedParamStore.from_params(params, SERVE_CFG, options=DEV)
        cstep2 = make_compressed_serve_step(model, store2)
        state = model.init_decode_state(B, steps + 1, start_pos=0)
        _, state = cstep2(state, toks)
        device_entropy.reset_transfer_stats()
        _, state = cstep2(state, toks)
        assert device_entropy.transfer_stats()["payload_uploads"] > 0

    @pytest.mark.parametrize(
        "arch",
        [
            "repro_gpt_100m",      # dense
            "olmoe_1b_7b",         # moe
            "deepseek_v2_236b",    # moe + dense prefix + MLA caches
        ],
    )
    def test_per_tile_ring_bit_identical(self, arch):
        cfg, model, params = _tiny(arch)
        store = CompressedParamStore.from_params(
            params, SERVE_CFG, options=DEV, payload_feed=True
        )
        ring, tiles = 2, 2
        cstep = make_compressed_serve_step(model, store, ring=ring, tiles=tiles)
        assert cstep.tiles == tiles
        assert _lockstep(cfg, model, params, cstep, steps=2)
        assert 1 <= store.peak_resident <= ring * tiles
        assert store.resident_count == 0

    def test_tiles_validation_and_geometry(self):
        cfg, model, params = _tiny("repro_gpt_100m")
        store = CompressedParamStore.from_params(params, SERVE_CFG)
        with pytest.raises(ValueError, match="tiles"):
            make_compressed_serve_step(model, store, tiles=0)
        key = store.stack_keys[0]
        n = store.n_leaves(key)
        for tiles in (1, 2, n, n + 3):          # more tiles than leaves is fine
            ids = [store.tile_leaf_ids(key, t, tiles) for t in range(tiles)]
            flat = [j for r in ids for j in r]
            assert flat == list(range(n))       # contiguous, complete, ordered

    def test_many_tiles_lockstep(self):
        """tiles > leaves-per-layer: trailing empty tiles are scheduled and
        released without affecting bytes."""
        cfg, model, params = _tiny("repro_gpt_100m")
        store = CompressedParamStore.from_params(params, SERVE_CFG)
        n = store.n_leaves(store.stack_keys[0])
        cstep = make_compressed_serve_step(model, store, ring=2, tiles=n + 2)
        assert _lockstep(cfg, model, params, cstep, steps=1)
        assert store.resident_count == 0


# ---------------------------------------------------------------------------
# satellite: bounded LUT cache
# ---------------------------------------------------------------------------

class TestLutCacheBound:
    def test_cache_is_bounded(self):
        info = device_entropy._stacked_luts_cached.cache_info()
        assert info.maxsize == device_entropy.LUT_CACHE_SIZE
        rng = np.random.default_rng(0)
        for i in range(device_entropy.LUT_CACHE_SIZE + 8):
            freqs = np.zeros(256, dtype=np.int64)
            hot = rng.choice(256, size=8, replace=False)
            freqs[hot] = rng.integers(1, 1000, size=8) + i
            tb = huffman.pack_table(huffman.code_lengths(freqs))
            device_entropy._stacked_luts((tb,))
        info = device_entropy._stacked_luts_cached.cache_info()
        assert info.currsize <= info.maxsize


# ---------------------------------------------------------------------------
# satellite: ZIPNN_MAX_BATCH_BYTES env knob
# ---------------------------------------------------------------------------

class TestBatchBytesEnv:
    def test_default_when_unset(self, monkeypatch):
        monkeypatch.delenv("ZIPNN_MAX_BATCH_BYTES", raising=False)
        assert (
            device_plane._batch_bytes_from_env()
            == device_plane.DEFAULT_BATCH_BYTES
        )

    @pytest.mark.parametrize(
        "raw,want", [("123456", 123456), ("0x100000", 1 << 20), ("1", 1)]
    )
    def test_accepts_positive_ints(self, monkeypatch, raw, want):
        monkeypatch.setenv("ZIPNN_MAX_BATCH_BYTES", raw)
        assert device_plane._batch_bytes_from_env() == want

    @pytest.mark.parametrize("raw", ["abc", "", "1.5", "0", "-4096"])
    def test_rejects_garbage(self, monkeypatch, raw):
        monkeypatch.setenv("ZIPNN_MAX_BATCH_BYTES", raw)
        with pytest.raises(ValueError, match="ZIPNN_MAX_BATCH_BYTES"):
            device_plane._batch_bytes_from_env()

    def test_entropy_stage_shares_the_cap(self):
        assert device_entropy.MAX_BATCH_BYTES is device_plane.MAX_BATCH_BYTES


# ---------------------------------------------------------------------------
# satellite: engine frame pipeline depth
# ---------------------------------------------------------------------------

class TestEnginePipelineDepth:
    def _stream(self, n=200_000, seed=0):
        rng = np.random.default_rng(seed)
        return rng.standard_normal(n // 4).astype(np.float32).tobytes()

    @pytest.mark.parametrize(
        "threads,depth", [(0, 1), (0, 2), (4, 1), (4, 2), (4, 3)]
    )
    def test_files_byte_identical_across_depths(self, threads, depth):
        raw = self._stream()
        ref = io.BytesIO()
        engine.compress_file(
            io.BytesIO(raw), ref, "float32", window_bytes=1 << 16
        )
        opts = zipnn.CodecOptions(threads=threads)
        out = io.BytesIO()
        engine.compress_file(
            io.BytesIO(raw), out, "float32", window_bytes=1 << 16,
            options=opts, pipeline_depth=depth,
        )
        assert out.getvalue() == ref.getvalue()
        back = io.BytesIO()
        engine.decompress_file(
            io.BytesIO(out.getvalue()), back,
            options=opts, pipeline_depth=depth,
        )
        assert back.getvalue() == raw

    def test_depth_validation(self):
        with pytest.raises(ValueError, match="pipeline_depth"):
            engine.CompressWriter(io.BytesIO(), "float32", pipeline_depth=0)
        raw = self._stream(n=4096)
        blob = io.BytesIO()
        engine.compress_file(io.BytesIO(raw), blob, "float32")
        with pytest.raises(ValueError, match="pipeline_depth"):
            engine.DecompressReader(
                io.BytesIO(blob.getvalue()), pipeline_depth=0
            )


# ---------------------------------------------------------------------------
# encode mirror: resident planes feed the symbol gather on device
# ---------------------------------------------------------------------------

class TestEncodeResidentGather:
    def test_device_planes_skip_symbol_upload(self):
        """With the device plane stage, HUFF symbols are sliced from the
        resident plane chunks — zero payload-sized uploads — while the host
        plane stage must upload them; blobs are identical either way."""
        # fp32 device plane stage needs chunk_bytes % 16384 == 0
        cfg = zipnn.ZipNNConfig(chunk_param_bytes=1 << 16, backend="huffman")
        rng = np.random.default_rng(7)
        arr = rng.standard_normal(1 << 15).astype(np.float32)
        device_entropy.reset_transfer_stats()
        ct_dev = zipnn.compress_array(arr, cfg, options=DEV)
        dev_stats = device_entropy.transfer_stats()
        device_entropy.reset_transfer_stats()
        ct_host = zipnn.compress_array(
            arr, cfg,
            options=zipnn.CodecOptions(backend="host", entropy_backend="device"),
        )
        host_stats = device_entropy.transfer_stats()
        assert dev_stats["payload_uploads"] == 0
        assert host_stats["payload_uploads"] > 0
        assert ct_dev.blob == ct_host.blob
        assert ct_dev.blob == zipnn.compress_array(arr, cfg).blob

    def test_plane_slices_lose_the_device_twin(self):
        """PlanedArray views/slices must not inherit a stale device twin."""
        host = np.arange(64, dtype=np.uint8).view(device_plane.PlanedArray)
        host.dev_chunks = jnp.zeros((2, 32), jnp.uint8)
        assert host[1:].dev_chunks is None
        assert host.copy().dev_chunks is None
