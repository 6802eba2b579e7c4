"""Device entropy-decode backend (core/device_entropy.decode_planes +
kernels/huffdecode.py) and the zero-bounce decode pipeline.

Contract under test: every ``HUFF`` chunk of a canonical-coder container
decodes on device **bit-identically** to ``huffman.decode_many`` / the
host codec — across tables, chunk sizes, final partial chunks, and
``STORE``/``ZERO``/expansion-guard mixes — and corrupt payloads fail
cleanly (CRC / bit-cursor / pad-bit errors, never an out-of-bounds
gather).  The device-resident path feeds kernel-decoded symbols straight
into the fused un-plane consumer so restored leaves never bounce through
host memory.
"""

import dataclasses
import io
import tempfile

import numpy as np
import pytest

from repro.core import codec, container, device_entropy, engine, huffman, zipnn
from parity import make_array

HUFF_CFG = zipnn.ZipNNConfig(chunk_param_bytes=1 << 15, backend="huffman")


def _skewed_plane(n: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    p = np.r_[np.full(16, 0.05), np.full(240, 0.2 / 240)]
    return rng.choice(256, p=p, size=n).astype(np.uint8)


def _table_for(plane: np.ndarray) -> np.ndarray:
    return huffman.code_lengths(np.bincount(plane, minlength=256) + 1)


def _chunk(plane: np.ndarray, chunk_bytes: int):
    return [
        plane[o : o + chunk_bytes] for o in range(0, plane.size, chunk_bytes)
    ]


def _pack_words(payloads, chunk_bytes: int) -> np.ndarray:
    """Payloads → the kernel's per-chunk big-endian uint32 word lanes."""
    cw = chunk_bytes // 4
    words = np.zeros(len(payloads) * cw, dtype=np.uint32)
    for k, pay in enumerate(payloads):
        pad = -len(pay) % 4
        w = np.frombuffer(bytes(pay) + b"\x00" * pad, dtype=">u4")
        words[k * cw : k * cw + w.size] = w
    return words


# ---------------------------------------------------------------------------
# kernel-level parity: fused decode vs the lockstep host decoder
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk_bytes", [4096, 16384])
@pytest.mark.parametrize(
    "n", [4096, 16384 * 3, 16384 * 2 + 5_001, 1 << 15]
)  # whole chunks, multi-chunk, final partial chunk
def test_kernel_matches_decode_many(chunk_bytes, n):
    import jax.numpy as jnp

    from repro.kernels import huffdecode

    plane = _skewed_plane(n, seed=chunk_bytes + n)
    lens = _table_for(plane)
    codes = huffman.canonical_codes(lens)
    chunks = _chunk(plane, chunk_bytes)
    counts = np.asarray([c.size for c in chunks], dtype=np.int64)
    payloads = huffman.encode_chunks(plane, counts, lens, codes)
    want = huffman.decode_many(payloads, counts, lens)

    max_l = int(lens.max(initial=1))
    lut_sym, lut_len = huffman._build_lut(lens, codes, max_l)
    luts = ((lut_sym.astype(np.int32) << 8) | lut_len.astype(np.int32))[None, :]
    syms, cursors = huffdecode.huffdecode_chunks_multi(
        jnp.asarray(_pack_words(payloads, chunk_bytes)),
        jnp.zeros(len(chunks), jnp.int32),
        jnp.asarray(counts, dtype=jnp.int32),
        jnp.asarray(luts),
        chunk_bytes=chunk_bytes,
    )
    syms = np.asarray(syms)
    cursors = np.asarray(cursors)
    for k, w in enumerate(want):
        assert np.array_equal(syms[k, : counts[k]], w)
        # the bit cursor must land inside the final payload byte
        slack = 8 * len(payloads[k]) - int(cursors[k])
        assert 0 <= slack < 8


def test_kernel_multi_table_selection():
    """Chunks of different planes gather against their own LUT row at the
    shared stacked width."""
    import jax.numpy as jnp

    from repro.kernels import huffdecode

    cb = 4096
    planes = [
        _skewed_plane(cb * 2 + 777, seed=1),
        (np.arange(cb * 3) % 7).astype(np.uint8),      # much shorter codes
    ]
    tabs = [_table_for(p) for p in planes]
    all_payloads, all_counts, pids, want = [], [], [], []
    for p, (plane, lens) in enumerate(zip(planes, tabs)):
        codes = huffman.canonical_codes(lens)
        chunks = _chunk(plane, cb)
        counts = np.asarray([c.size for c in chunks], dtype=np.int64)
        payloads = huffman.encode_chunks(plane, counts, lens, codes)
        want += huffman.decode_many(payloads, counts, lens)
        all_payloads += payloads
        all_counts += counts.tolist()
        pids += [p] * len(chunks)

    max_l = max(int(t.max(initial=1)) for t in tabs)
    luts = np.zeros((len(tabs), 1 << max_l), dtype=np.int32)
    for p, lens in enumerate(tabs):
        ls, ll = huffman._build_lut(lens, huffman.canonical_codes(lens), max_l)
        luts[p] = (ls.astype(np.int32) << 8) | ll.astype(np.int32)
    syms, _ = huffdecode.huffdecode_chunks_multi(
        jnp.asarray(_pack_words(all_payloads, cb)),
        jnp.asarray(pids, dtype=jnp.int32),
        jnp.asarray(all_counts, dtype=jnp.int32),
        jnp.asarray(luts),
        chunk_bytes=cb,
    )
    syms = np.asarray(syms)
    for k, w in enumerate(want):
        assert np.array_equal(syms[k, : len(w)], w)


def test_kernel_truncated_words_never_oob():
    """A payload cut short mis-lands the bit cursor; the clamped gathers
    keep the kernel in bounds and the driver-level check catches it."""
    import jax.numpy as jnp

    from repro.kernels import huffdecode

    cb = 4096
    plane = _skewed_plane(cb, seed=7)
    lens = _table_for(plane)
    codes = huffman.canonical_codes(lens)
    payloads = huffman.encode_chunks(plane, np.asarray([cb]), lens, codes)
    cut = payloads[0][: len(payloads[0]) // 2]     # truncate: cursor overruns
    max_l = int(lens.max(initial=1))
    ls, ll = huffman._build_lut(lens, codes, max_l)
    luts = ((ls.astype(np.int32) << 8) | ll.astype(np.int32))[None, :]
    syms, cursors = huffdecode.huffdecode_chunks_multi(
        jnp.asarray(_pack_words([cut], cb)),
        jnp.zeros(1, jnp.int32),
        jnp.asarray([cb], dtype=jnp.int32),
        jnp.asarray(luts),
        chunk_bytes=cb,
    )
    # no crash/OOB; the cursor demonstrably ran past the truncated payload
    assert int(np.asarray(cursors)[0]) > 8 * len(cut) - 8
    assert np.asarray(syms).shape == (1, cb)


# ---------------------------------------------------------------------------
# decode_many hardening (host twin of the kernel's integrity checks)
# ---------------------------------------------------------------------------

def test_decode_many_rejects_nonzero_pad_bits():
    # find a stream whose final byte has pad slack, then dirty the pad
    for n in range(2048, 2080):
        plane = _skewed_plane(n, seed=3)
        lens = _table_for(plane)
        codes = huffman.canonical_codes(lens)
        payloads = huffman.encode_chunks(
            plane, np.asarray([plane.size]), lens, codes
        )
        assert np.array_equal(
            huffman.decode_many(payloads, [plane.size], lens)[0], plane
        )
        total_bits = int(huffman.estimate_encoded_bits(
            np.bincount(plane, minlength=256), lens
        ))
        slack = 8 * len(payloads[0]) - total_bits
        if 0 < slack < 8:
            break
    else:
        pytest.fail("no padded tail found in the sweep")
    bad = payloads[0][:-1] + bytes([payloads[0][-1] | 1])
    with pytest.raises(ValueError, match="pad bits"):
        huffman.decode_many([bad], [plane.size], lens)


def test_decode_many_rejects_tampered_count():
    plane = _skewed_plane(2048, seed=4)
    lens = _table_for(plane)
    codes = huffman.canonical_codes(lens)
    payloads = huffman.encode_chunks(plane, np.asarray([plane.size]), lens, codes)
    with pytest.raises(ValueError):
        huffman.decode_many(payloads, [plane.size - 100], lens)


# ---------------------------------------------------------------------------
# decode_planes: driver parity + corruption fuzz
# ---------------------------------------------------------------------------

def _compress_plane_all(planes, params):
    outs = [codec.compress_plane(p, params) for p in planes]
    return (
        [o[0] for o in outs],
        [o[1] for o in outs],
        [o[2] for o in outs],
    )


def _mixed_planes(cb):
    """STORE (incompressible), ZERO, HUFF, and a final partial chunk."""
    rng = np.random.default_rng(11)
    return [
        np.concatenate([
            rng.integers(0, 256, cb, dtype=np.uint8).astype(np.uint8),  # STORE
            np.zeros(cb, dtype=np.uint8),                               # ZERO
            _skewed_plane(cb + cb // 3, seed=5),                        # HUFF+partial
        ]),
        _skewed_plane(2 * cb, seed=6),
    ]


@pytest.mark.parametrize("device_resident", [False, True])
def test_decode_planes_matches_host_codec(device_resident):
    cb = 4096
    params = codec.CodecParams(chunk_bytes=cb, backend="huffman")
    planes = _mixed_planes(cb)
    entries, payloads, tables = _compress_plane_all(planes, params)
    methods = {e.method for pe in entries for e in pe}
    assert codec.Method.HUFF in methods and codec.Method.STORE in methods
    got = device_entropy.decode_planes(
        entries, payloads, tables, params, device_resident=device_resident
    )
    for g, p in zip(got, planes):
        if device_resident:
            assert not isinstance(g, np.ndarray)
        assert np.array_equal(np.asarray(g), p)


def test_decode_planes_expansion_guard_mix():
    """Chunks the encoder's expansion guard stored raw splice back in."""
    cb = 4096
    params = codec.CodecParams(
        chunk_bytes=cb, backend="huffman", incompressible=1.1
    )  # force the probe to plan HUFF even on random bytes → guard trips
    rng = np.random.default_rng(12)
    plane = np.concatenate([
        rng.integers(0, 256, cb, dtype=np.uint8).astype(np.uint8),
        _skewed_plane(cb, seed=13),
    ])
    entries, payloads, tables = _compress_plane_all([plane], params)
    assert any(e.method == codec.Method.STORE for e in entries[0])
    got = device_entropy.decode_planes(entries, payloads, tables, params)
    assert np.array_equal(np.asarray(got[0]), plane)


def test_decode_planes_corruption_rejected():
    cb = 4096
    params = codec.CodecParams(chunk_bytes=cb, backend="huffman")
    plane = _skewed_plane(2 * cb, seed=8)
    entries, payloads, tables = _compress_plane_all([plane], params)
    assert entries[0][0].method == codec.Method.HUFF

    # flipped byte → CRC error (same message as the host codec)
    bad = [bytearray(p) for p in payloads[0]]
    bad[0][3] ^= 0xFF
    with pytest.raises(IOError, match="CRC mismatch"):
        device_entropy.decode_planes(
            [entries[0]], [[bytes(b) for b in bad]], tables, params
        )

    # truncated payload with a recomputed CRC → bit-cursor integrity error
    import zlib

    cut = bytes(payloads[0][0][: entries[0][0].comp_len // 2])
    e0 = dataclasses.replace(
        entries[0][0], comp_len=len(cut), crc=zlib.crc32(cut)
    )
    with pytest.raises(ValueError, match="cursor|pad bits"):
        device_entropy.decode_planes(
            [[e0] + entries[0][1:]], [[cut] + payloads[0][1:]], tables, params
        )

    # nonzero pad bits with a recomputed CRC → pad integrity error
    p0 = bytes(payloads[0][0])
    dirty = p0[:-1] + bytes([p0[-1] | 1])
    slack = -huffman.estimate_encoded_bits(
        np.bincount(plane[:cb], minlength=256),
        huffman.unpack_table(tables[0]),
    ) % 8
    if slack:                     # only meaningful when the tail is padded
        e0 = dataclasses.replace(entries[0][0], crc=zlib.crc32(dirty))
        with pytest.raises(ValueError, match="pad bits"):
            device_entropy.decode_planes(
                [[e0] + entries[0][1:]], [[dirty] + payloads[0][1:]],
                tables, params,
            )

    # missing table → same corrupt-stream error as the host codec
    with pytest.raises(IOError, match="no plane table"):
        device_entropy.decode_planes([entries[0]], [payloads[0]], [None], params)


def test_decode_envelope():
    assert device_entropy.supports_decode(4096) == device_entropy.is_available()
    assert not device_entropy.supports_decode(4097)
    assert device_entropy.resolve_decode(None, 4096) == "host"
    assert device_entropy.resolve_decode("host", 4096) == "host"
    assert device_entropy.resolve_decode("device", 4097) == "host"
    if device_entropy.is_available():
        assert device_entropy.resolve_decode("device", 4096) == "device"
    with pytest.raises(ValueError, match="unknown entropy backend"):
        device_entropy.resolve_decode("gpu", 4096)


def test_consume_payloads_zero_bounce():
    import jax

    from repro.core import bitlayout, device_unplane

    layout = bitlayout.LAYOUTS["float32"]
    arr = make_array("float32", 50_000, seed=21)
    cb = HUFF_CFG.plane_params(layout.itemsize).chunk_bytes
    params = codec.CodecParams(chunk_bytes=cb, backend="huffman")
    planes = [np.ascontiguousarray(p) for p in bitlayout.to_planes(
        np.frombuffer(arr.tobytes(), dtype=np.uint8), layout
    )]
    entries, payloads, tables = _compress_plane_all(planes, params)
    elems = device_unplane.consume_payloads(
        entries, payloads, tables, params, layout, device_resident=True
    )
    assert isinstance(elems, jax.Array)
    got = np.asarray(jax.device_get(elems)).view(np.float32)
    assert np.array_equal(got, arr.reshape(-1))


# ---------------------------------------------------------------------------
# end-to-end: the entropy_backend knob across the decode surface
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_decompress_bytes_parity(dtype):
    raw = make_array(dtype, 60_001, seed=31).tobytes()
    blob = zipnn.compress_bytes(raw, dtype, HUFF_CFG)
    for backend in (None, "device"):
        assert zipnn.decompress_bytes(
            blob, HUFF_CFG, backend=backend, entropy_backend="device"
        ) == raw
    # config-field route
    cfg = dataclasses.replace(HUFF_CFG, entropy_backend="device")
    assert zipnn.decompress_bytes(blob, cfg) == raw


def test_decompress_array_device_resident():
    arr = make_array("float32", 40_001, seed=32)
    ct = zipnn.compress_array(arr, HUFF_CFG)
    host = zipnn.decompress_array(ct, HUFF_CFG)
    dev = zipnn.decompress_array(
        ct, HUFF_CFG, backend="device", entropy_backend="device",
        device_resident=True,
    )
    assert not isinstance(dev, np.ndarray)
    assert dev.dtype == arr.dtype and dev.shape == arr.shape
    assert np.array_equal(np.asarray(dev), host)
    # host-resolved request still returns numpy (safe fallback)
    out = zipnn.decompress_array(ct, HUFF_CFG, backend="host", device_resident=True)
    assert isinstance(out, np.ndarray) and np.array_equal(out, host)


def test_delta_decompress_device_entropy():
    import jax.numpy as jnp

    base = make_array("float32", 30_000, seed=33)
    new = (base.reshape(-1) + np.float32(1e-3)).reshape(base.shape)
    ct = zipnn.delta_compress(new, base, HUFF_CFG)
    host = zipnn.delta_decompress(ct, base, HUFF_CFG)
    dev = zipnn.delta_decompress(
        ct, jnp.asarray(base), HUFF_CFG,
        backend="device", entropy_backend="device", device_resident=True,
    )
    assert not isinstance(dev, np.ndarray)
    assert np.array_equal(np.asarray(dev), host) and np.array_equal(host, new)


def test_decompress_pytree_device_entropy():
    tree = {
        "w": make_array("float32", 20_000, seed=34),
        "b": make_array("bfloat16", 7_001, seed=35),
    }
    m = zipnn.compress_pytree(tree, HUFF_CFG)
    host = zipnn.decompress_pytree(m, HUFF_CFG)
    dev = zipnn.decompress_pytree(
        m, HUFF_CFG, backend="device", entropy_backend="device"
    )
    for k in tree:
        assert np.array_equal(np.asarray(host[k]), np.asarray(dev[k]))
        assert np.array_equal(np.asarray(dev[k]), np.asarray(tree[k]))


def test_stream_reader_device_entropy():
    raw = make_array("float32", 90_000, seed=36).tobytes()
    buf = io.BytesIO()
    with engine.CompressWriter(buf, "float32", HUFF_CFG, window_bytes=1 << 17) as w:
        w.write(raw)
    buf.seek(0)
    r = engine.DecompressReader(buf, HUFF_CFG, entropy_backend="device")
    assert r.read() == raw


def test_checkpoint_device_resident_restore(tmp_path):
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.checkpoint.manager import CheckpointConfig, CheckpointManager

    mgr = CheckpointManager(CheckpointConfig(
        directory=str(tmp_path), zipnn=HUFF_CFG,
        backend="device", entropy_backend="device",
    ))
    p1 = make_array("float32", 40_000, seed=37).reshape(200, 200)
    p2 = (p1 + np.float32(1e-3)).astype(np.float32)
    mgr.save(1, {"p": p1}, blocking=True)
    mgr.save(2, {"p": p2}, blocking=True)       # delta vs the step-1 base
    s, host_tree = mgr.restore()
    assert s == 2 and np.array_equal(host_tree["p"], p2)
    s, dev_tree = mgr.restore(device_resident=True)
    assert s == 2 and isinstance(dev_tree["p"], jax.Array)
    assert np.array_equal(np.asarray(dev_tree["p"]), p2)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("x",))
    s, sharded = mgr.shard_restore(None, mesh, {"p": P()})
    assert s == 2 and np.array_equal(np.asarray(sharded["p"]), p2)


def test_grad_sync_device_entropy():
    from repro.distributed.grad_sync import GradSync

    gs = GradSync(HUFF_CFG, entropy_backend="device")
    grads = {"g": make_array("float32", 25_000, seed=38)}
    manifest, _ = gs.pack(grads)
    back = gs.unpack(manifest)
    assert np.array_equal(np.asarray(back["g"]), np.asarray(grads["g"]))


# ---------------------------------------------------------------------------
# device plane assembly: parity with the host splice, compiles, counters
# ---------------------------------------------------------------------------

ASSEMBLY_CASES = (
    "all_huff", "all_store", "mixed_methods", "short_huff", "short_store",
    "short_zero", "short_zlib", "two_windows", "empty_plane", "bfloat16",
    "float32",
)


def _sparse(n: int, seed: int) -> np.ndarray:
    """Mostly zeros: the delta path's ZLIB chunks."""
    out = np.zeros(n, dtype=np.uint8)
    rng = np.random.default_rng(seed)
    out[rng.integers(0, n, max(1, n // 50))] = 7
    return out


def assembly_stream(name: str):
    """One named stream for the plane-assembly cases.

    Returns ``(planes, entries, payloads, tables, params, methods,
    max_batch_bytes)``: the raw planes the stream decodes to (``None`` for
    the layout cases, whose reference is the host path), the stream, the
    chunk methods it must hold, and the launch-window cap to decode it at.
    """
    cb = 4096
    huff = codec.CodecParams(chunk_bytes=cb, backend="huffman")
    delta = dataclasses.replace(huff, delta_mode=True)
    rng = np.random.default_rng(sum(map(ord, name)))

    def rand(n):
        return rng.integers(0, 256, n, dtype=np.uint8).astype(np.uint8)

    def zeros(n):
        return np.zeros(n, dtype=np.uint8)

    H, S, Z, L = (codec.Method.HUFF, codec.Method.STORE, codec.Method.ZERO,
                  codec.Method.ZLIB)
    max_batch = device_entropy.MAX_BATCH_BYTES
    if name in ("bfloat16", "float32"):
        arr = make_array(name, 3 * 8192 + 1_001, seed=len(name))
        ct = zipnn.compress_array(arr, HUFF_CFG)
        meta, mv = container.unpack_stream(ct.blob)
        payloads = [
            [container.payload_view(meta, mv, p, c)
             for c in range(len(meta.entries[p]))]
            for p in range(meta.n_planes)
        ]
        params = codec.CodecParams(chunk_bytes=meta.chunk_bytes, backend="huffman")
        return (None, meta.entries, payloads, meta.tables, params, {H},
                max_batch)
    params = huff
    if name == "all_huff":
        planes, methods = [_skewed_plane(3 * cb, seed=41)], {H}
    elif name == "all_store":
        planes, methods = [rand(3 * cb)], {S}
    elif name == "mixed_methods":
        # incompressible > 1 plans HUFF on random bytes: the guard stores it
        params = dataclasses.replace(delta, incompressible=1.1)
        planes = [np.concatenate([
            zeros(cb), _sparse(cb, 42), _skewed_plane(cb, seed=43), rand(cb),
            _skewed_plane(cb, seed=44),
        ])]
        methods = {H, S, Z, L}
    elif name == "short_huff":
        planes, methods = [_skewed_plane(2 * cb + 333, seed=45)], {H}
    elif name == "short_store":
        planes, methods = [rand(2 * cb + 333)], {S}
    elif name == "short_zero":
        planes = [np.concatenate([_skewed_plane(2 * cb, seed=46), zeros(333)])]
        methods = {H, Z}
    elif name == "short_zlib":
        params = delta
        planes = [np.concatenate([_skewed_plane(2 * cb, seed=47),
                                  _sparse(333, 48)])]
        methods = {H, L}
    elif name == "two_windows":
        planes = [
            _skewed_plane(5 * cb + 100, seed=49),
            np.concatenate([rand(cb), _skewed_plane(2 * cb, seed=50)]),
        ]
        methods, max_batch = {H, S}, 2 * (2 * cb)    # two chunks a launch
    elif name == "empty_plane":
        planes = [_skewed_plane(2 * cb + 5, seed=51), zeros(0)]
        methods = {H}
    else:
        raise KeyError(name)
    entries, payloads, tables = _compress_plane_all(planes, params)
    short = {"short_huff": H, "short_store": S, "short_zero": Z,
             "short_zlib": L}.get(name)
    if short is not None:           # the plane's short last chunk is the method's
        assert entries[0][-1].method == short and entries[0][-1].raw_len < cb
    return planes, entries, payloads, tables, params, methods, max_batch


@pytest.mark.parametrize("name", ASSEMBLY_CASES)
def test_assembly_matches_host_path(name, monkeypatch):
    planes, entries, payloads, tables, params, methods, max_batch = (
        assembly_stream(name)
    )
    assert methods <= {e.method for pe in entries for e in pe}
    monkeypatch.setattr(device_entropy, "MAX_BATCH_BYTES", max_batch)
    host = device_entropy.decode_planes(entries, payloads, tables, params)
    dev = device_entropy.decode_planes(
        entries, payloads, tables, params, device_resident=True
    )
    assert len(dev) == len(host)
    for d, h in zip(dev, host):
        assert np.asarray(d).dtype == np.uint8
        assert np.array_equal(np.asarray(d), h)
    if planes is not None:
        for h, p in zip(host, planes):
            assert np.array_equal(h, p)


def test_assembly_refuses_short_chunk_before_plane_end():
    """A chunk table that cuts a plane short before its last chunk breaks
    the fixed-stride invariant: the device path refuses it as corrupt."""
    cb = 4096
    params = codec.CodecParams(chunk_bytes=cb, backend="huffman")
    zero_entries, zero_payloads, _ = codec.compress_plane(
        np.zeros(100, dtype=np.uint8), params
    )
    assert [(e.method, e.raw_len) for e in zero_entries] == [
        (codec.Method.ZERO, 100)
    ]
    entries, payloads, table = codec.compress_plane(
        _skewed_plane(2 * cb, seed=52), params
    )
    crafted = [list(zero_entries) + list(entries)]
    crafted_payloads = [list(zero_payloads) + list(payloads)]
    with pytest.raises(IOError, match="chunk_bytes strides"):
        device_entropy.decode_planes(
            crafted, crafted_payloads, [table], params, device_resident=True
        )
    with pytest.raises(IOError, match="chunk_bytes strides"):
        device_entropy.PayloadFeed(crafted, crafted_payloads, [table], params)


def _store_at(k: int, cb: int) -> np.ndarray:
    """Three chunks, the ``k``-th random (the expansion guard stores it)."""
    rng = np.random.default_rng(60 + k)
    parts = [_skewed_plane(cb, seed=61 + k + i) for i in range(3)]
    parts[k] = rng.integers(0, 256, cb, dtype=np.uint8).astype(np.uint8)
    return np.concatenate(parts)


@pytest.mark.parametrize("via", ["decode_planes", "feed"])
def test_assembly_compiles_once_per_shape(via):
    """Streams of one shape whose STORE fallbacks sit at different chunks
    (same row counts) share one compiled assembly: the row index is an
    argument, not part of the compile key."""
    cb = 6144                                   # a shape no other test uses
    params = codec.CodecParams(
        chunk_bytes=cb, backend="huffman", incompressible=1.1
    )
    assembler = device_entropy._assembler()
    before = assembler._cache_size()
    sizes = []
    for k in (0, 1):
        plane = _store_at(k, cb)
        entries, payloads, tables = _compress_plane_all([plane], params)
        assert [e.method for e in entries[0]].index(codec.Method.STORE) == k
        if via == "feed":
            got = device_entropy.PayloadFeed(
                entries, payloads, tables, params
            ).decode()
        else:
            got = device_entropy.decode_planes(
                entries, payloads, tables, params, device_resident=True
            )
        assert np.array_equal(np.asarray(got[0]), plane)
        sizes.append(assembler._cache_size())
    assert sizes == [before + 1, before + 1]


@pytest.mark.parametrize("via", ["decode_planes", "feed"])
def test_assembly_counters(via, monkeypatch):
    """One assembly per decode call, placing every chunk of the stream."""
    from repro.core import tracing

    _, entries, payloads, tables, params, _, max_batch = assembly_stream(
        "two_windows"
    )
    monkeypatch.setattr(device_entropy, "MAX_BATCH_BYTES", max_batch)
    n_chunks = sum(len(pe) for pe in entries)
    feed = (
        device_entropy.PayloadFeed(entries, payloads, tables, params)
        if via == "feed" else None
    )
    for _ in range(2):
        c0 = tracing.counters()
        if feed is None:
            device_entropy.decode_planes(
                entries, payloads, tables, params, device_resident=True
            )
        else:
            feed.decode()
        c1 = tracing.counters()
        assert c1["assemblies"] - c0["assemblies"] == 1
        assert c1["assembled_chunks"] - c0["assembled_chunks"] == n_chunks
    # the host path assembles nothing on the device
    c0 = tracing.counters()
    device_entropy.decode_planes(entries, payloads, tables, params)
    assert tracing.counters()["assemblies"] == c0["assemblies"]
