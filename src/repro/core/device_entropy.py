"""Device entropy-stage backend: fused Huffman bit-packing on accelerator.

PR 2/3 moved the compression *front half* (rotate + byte-group + probe) and
the decompression back half on device; the Huffman encode loop stayed the
last GIL-bound host pass on the compress path.  This module closes it:

* the probe histograms (host ``hist256`` or the device plane-producer's
  :class:`~repro.core.codec.ProbeStats`) feed the **canonical table build on
  host** — table construction is a 256-entry package-merge, microseconds,
  and keeping it host-side preserves the canonical-code contract that makes
  blobs testable;
* every (plane, chunk) work item the codec planned as ``HUFF`` then packs
  symbols→bits in **one fused Pallas dispatch**
  (:func:`repro.kernels.bitpack.bitpack_encode_chunks_multi` — per-chunk
  table selection, so all planes of a tensor ride one launch) followed by a
  **single device→host transfer** of packed words + true bit counts;
* the host does only container framing and the expansion guard: chunks
  whose packed size would reach their raw size are stored raw by
  :meth:`~repro.core.codec.PlaneCodec.finalize`, exactly as on the host
  path, so the metadata map is unchanged.

Output blobs are **byte-identical** to the host encoder for every thread
count and plane backend: the kernel packs MSB-first canonical codes with
per-chunk byte alignment — the same bitstream ``huffman.encode_chunks``
emits — and the method plan (probe + probe-skip) runs through the one
shared :meth:`~repro.core.codec.PlaneCodec.plan` implementation.

Backend selection mirrors :mod:`.device_plane`:

* ``"host"``   — the numpy/vectorized host encoder (default);
* ``"device"`` — the fused bit-pack dispatch whenever supported (canonical
  ``huffman`` coder, 4-byte-aligned chunks); silent host fallback
  otherwise, so the knob is always safe to set;
* ``"auto"``   — device only for accelerator-resident leaves.

Support envelope: the codec's ``backend == "huffman"`` coder only — the
``hufflib`` (zlib) coder's DEFLATE bitstream has no device formulation —
with ``chunk_bytes % 4 == 0`` (the uint32 word reduce).  ``ZERO`` /
``STORE`` / ``ZLIB`` chunks and the §4.2 delta LZ path stay host work
items, as does everything on fallback.

**Decode twin** (:func:`decode_planes`): every ``HUFF`` chunk of a parsed
container decodes in one fused Pallas dispatch
(:func:`repro.kernels.huffdecode.huffdecode_chunks_multi` — per-chunk LUT
row selection over stacked canonical tables, grid over chunks, serial bit
cursor per chunk).  The *compressed* payload words + stacked LUTs upload
once; decoded symbols can stay device-resident
(``device_resident=True``) so the fused un-plane consumer never re-uploads
them — the zero-bounce restore path.  CRC verification, the
``decode_many``-equivalent bit-cursor + pad-bit integrity checks, and
``ZERO``/``STORE``/``ZLIB`` chunk decode stay host-side; those chunks ride
one additional upload on the device-resident path, as rows that one
compiled assembly (:func:`_assemble`) gathers with the kernel's symbol
rows into planes.  The decode
envelope (:func:`supports_decode`) keys off the *container's* chunk
geometry, not the config's coder: the stream records which chunks are
``HUFF``, so any blob the canonical coder produced decodes on device
regardless of the configured encode backend.
"""

from __future__ import annotations

import functools
import zlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import bitlayout, codec, huffman, tracing

__all__ = [
    "BACKENDS",
    "LUT_CACHE_SIZE",
    "PayloadFeed",
    "is_available",
    "supports",
    "supports_decode",
    "resolve",
    "resolve_decode",
    "encode_planes",
    "decode_planes",
    "transfer_stats",
    "reset_transfer_stats",
]

BACKENDS = ("host", "device", "auto")

# One fused dispatch is capped so symbols + packed words (2× the HUFF chunk
# bytes) stay comfortably in device memory; larger jobs split into several
# launches (payload bytes are per-chunk, so splitting never changes them).
# Shares device_plane's env-tunable cap (ZIPNN_MAX_BATCH_BYTES) — window
# size changes wall-clock and peak memory only, never bytes.
from .device_plane import MAX_BATCH_BYTES  # noqa: E402

# _stacked_luts_cached's lru_cache bound.  The cache is keyed on raw table
# bytes, so a long-lived serving session decoding many *distinct* stores
# would grow host memory without limit if unbounded; 64 entries cover every
# plane-table combination a realistic ring re-decodes while still evicting
# dead stores.  Asserted by tests (cache_info().maxsize).
LUT_CACHE_SIZE = 64


# ---------------------------------------------------------------------------
# transfer instrumentation
# ---------------------------------------------------------------------------
#
# Every payload-sized host→device upload on this module's encode/decode
# paths is tallied in the program's counters (core/tracing.py): HUFF symbol
# uploads (_pack_jobs host path), packed word uploads (_unpack_jobs /
# PayloadFeed build) and the non-HUFF splice upload.  The counters are the
# test hook behind the device-resident feed's headline contract — zero
# per-token payload uploads after warmup — and count bookkeeping only:
# they never touch the data path.

_PAYLOAD_COUNTERS = ("payload_uploads", "payload_bytes")


def transfer_stats() -> Dict[str, int]:
    """Snapshot of payload host→device upload counters (test hook)."""
    now = tracing.counters()
    return {k: int(now[k]) for k in _PAYLOAD_COUNTERS}


def reset_transfer_stats() -> None:
    tracing.reset_counters(_PAYLOAD_COUNTERS)


def is_available() -> bool:
    """True when jax (and therefore the Pallas kernels) can be imported."""
    from . import device_plane

    return device_plane.is_available()


def supports(layout: Optional[bitlayout.BitLayout], params: codec.CodecParams) -> bool:
    """Can the fused bit-pack path reproduce the host encoder's bytes?

    Requires the canonical ``huffman`` coder (``hufflib`` emits a DEFLATE
    stream we do not reproduce on device) and chunks that are whole uint32
    words.
    """
    if params.backend != "huffman":
        return False
    if params.chunk_bytes % 4 != 0:
        return False
    return is_available()


def resolve(
    requested: Optional[str],
    layout: Optional[bitlayout.BitLayout],
    params: codec.CodecParams,
    leaf=None,
) -> str:
    """Collapse a backend request to the concrete path: 'host' or 'device'."""
    if requested is None or requested == "host":
        return "host"
    if requested == "device":
        return "device" if supports(layout, params) else "host"
    if requested == "auto":
        from . import device_plane

        return (
            "device"
            if supports(layout, params) and device_plane._on_accelerator(leaf)
            else "host"
        )
    raise ValueError(
        f"unknown entropy backend {requested!r}; expected one of {BACKENDS}"
    )


def supports_decode(chunk_bytes: int) -> bool:
    """Can the fused decode path reproduce the host decoder's bytes?

    Decode keys off the *container*, not the config: the stream records
    which chunks are ``HUFF`` (only the canonical coder emits them), so the
    envelope is just whole-uint32-word chunks plus jax availability.
    """
    return chunk_bytes % 4 == 0 and is_available()


def resolve_decode(
    requested: Optional[str], chunk_bytes: int, base=None
) -> str:
    """Decode twin of :func:`resolve`.

    ``auto`` keys off accelerator attachment (or an accelerator-resident
    delta ``base``) — decoded symbols land on device, so residence of the
    hardware is the signal, mirroring ``device_unplane.resolve``.
    """
    if requested is None or requested == "host":
        return "host"
    if requested == "device":
        return "device" if supports_decode(chunk_bytes) else "host"
    if requested == "auto":
        from . import device_plane, device_unplane

        return (
            "device"
            if supports_decode(chunk_bytes)
            and (
                device_unplane._accelerator_attached()
                or device_plane._on_accelerator(base)
            )
            else "host"
        )
    raise ValueError(
        f"unknown entropy backend {requested!r}; expected one of {BACKENDS}"
    )


# ---------------------------------------------------------------------------
# fused encode
# ---------------------------------------------------------------------------

PlaneResult = Tuple[List[codec.ChunkEntry], List[bytes], Optional[bytes]]


def _gather_syms_device(
    planes: Sequence[np.ndarray],
    jobs: Sequence[Tuple[int, int, int]],
    chunk_bytes: int,
):
    """HUFF symbols for ``jobs`` gathered from device-resident plane rows.

    Returns a flat ``(len(jobs) * chunk_bytes,)`` device uint8 array, or
    ``None`` when any referenced plane lacks its device twin (host-planed
    leaves, mismatched chunk geometry) or the jobs are not plane-major —
    the caller then builds the symbols host-side as before.  Only the
    chunk-id index vectors cross host→device (metadata-sized); the symbol
    bytes themselves never leave the device.
    """
    import jax.numpy as jnp

    if not jobs:
        return None
    for k in range(1, len(jobs)):
        if jobs[k][0] < jobs[k - 1][0]:
            return None                     # per-plane grouping would reorder
    parts = []
    i = 0
    while i < len(jobs):
        p = jobs[i][0]
        j = i
        while j < len(jobs) and jobs[j][0] == p:
            j += 1
        dev = getattr(planes[p], "dev_chunks", None)
        if dev is None or dev.ndim != 2 or dev.shape[1] != chunk_bytes:
            return None
        ids = np.asarray([ch for (_, ch, _) in jobs[i:j]], dtype=np.int32)
        if ids.size and int(ids.max()) >= dev.shape[0]:
            return None
        parts.append(dev[jnp.asarray(ids)])
        i = j
    mat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return mat.reshape(-1)


def _pack_jobs(
    planes: Sequence[np.ndarray],
    jobs: Sequence[Tuple[int, int, int]],
    len_tables: np.ndarray,
    code_tables: np.ndarray,
    chunk_bytes: int,
) -> List[bytes]:
    """Run one fused bit-pack dispatch over ``jobs`` and slice payloads.

    ``jobs`` is ``(plane_idx, chunk_idx, size)`` per HUFF chunk; the final
    partial chunk (``size < chunk_bytes``) is zero-padded on the symbol side
    and its pad bits are subtracted/masked on the host side — byte-identical
    to encoding exactly ``size`` symbols.

    When the planes are the device producer's :class:`~repro.core.
    device_plane.PlanedArray` twins, the HUFF symbols are **gathered on
    device** from the still-resident chunk rows instead of re-uploaded from
    host — the rows carry the identical zero padding, so the packed bits
    cannot differ.
    """
    import jax.numpy as jnp

    from repro.kernels import bitpack, ops

    c = len(jobs)
    pids = np.empty(c, dtype=np.int32)
    for k, (p, ch, size) in enumerate(jobs):
        pids[k] = p
    syms_dev = _gather_syms_device(planes, jobs, chunk_bytes)
    if syms_dev is None:
        syms = np.zeros(c * chunk_bytes, dtype=np.uint8)
        for k, (p, ch, size) in enumerate(jobs):
            start = ch * chunk_bytes
            syms[k * chunk_bytes : k * chunk_bytes + size] = (
                planes[p][start : start + size]
            )
        tracing.count_payload_upload(syms.nbytes)
        syms_dev = jnp.asarray(syms)
    with tracing.span("znn.codec.launch"):
        words, nbits = bitpack.bitpack_encode_chunks_multi(
            syms_dev,
            jnp.asarray(pids),
            jnp.asarray(len_tables),
            jnp.asarray(code_tables),
            chunk_syms=chunk_bytes,
            interpret=ops.interpret_mode(),
        )
    tracing.count("launches.bitpack")
    # The one device→host transfer: packed words + true bit counts together.
    words_h, nbits_h = tracing.fetch((words, nbits))
    # uint32 words hold bit j of the chunk at word bit 31-j: big-endian byte
    # order recovers exactly the np.packbits stream the host encoder emits.
    stream = np.ascontiguousarray(words_h).byteswap().view(np.uint8).reshape(-1)

    out: List[bytes] = []
    for k, (p, ch, size) in enumerate(jobs):
        pad = chunk_bytes - size
        true_bits = int(nbits_h[k]) - pad * int(len_tables[p, 0])
        nbytes = (true_bits + 7) >> 3
        if nbytes > chunk_bytes:
            # Expanded past the kernel's raw-size capacity: bits were
            # truncated on device, but finalize() stores this chunk raw
            # (len >= raw_len) — only the payload *length* matters here.
            out.append(bytes(nbytes))
            continue
        blob = bytearray(stream[k * chunk_bytes : k * chunk_bytes + nbytes])
        slack = nbytes * 8 - true_bits
        if slack and nbytes:
            blob[-1] &= (0xFF << slack) & 0xFF  # zero pad-symbol bits
        out.append(bytes(blob))
    return out


def encode_planes(
    planes: Sequence[np.ndarray],
    probes: Sequence[Optional[codec.ProbeStats]],
    params: codec.CodecParams,
    pool=None,
) -> Tuple[List[List[codec.ChunkEntry]], List[List[bytes]], List[Optional[bytes]]]:
    """Device-backed equivalent of the per-plane host compress loop.

    Pass 1 (plan: probe + probe-skip + table build) runs host-side through
    the shared :meth:`~repro.core.codec.PlaneCodec.plan`; every planned
    ``HUFF`` chunk across *all* planes then packs in one fused device
    dispatch (split only at :data:`MAX_BATCH_BYTES`), while ``ZERO`` /
    ``STORE`` / ``ZLIB`` chunks encode as host work items on ``pool``.
    Pass 3 (expansion guard + metadata map) is the shared ``finalize``.

    Returns per-plane ``(entries, payloads, table_blob)`` lists matching
    :func:`repro.core.codec.compress_plane` byte-for-byte.
    """
    codecs = [codec.PlaneCodec(params) for _ in planes]
    methods_all: List[List[int]] = []
    for pc, plane, probe in zip(codecs, planes, probes):
        methods_all.append(pc.plan(plane, pool=pool, probe=probe))

    cb = params.chunk_bytes
    jobs: List[Tuple[int, int, int]] = []
    for p, (plane, methods) in enumerate(zip(planes, methods_all)):
        for ch, m in enumerate(methods):
            if m == codec.Method.HUFF:
                jobs.append((p, ch, min(cb, plane.size - ch * cb)))

    huff_payloads: dict = {}
    if jobs:
        len_tables = np.stack(
            [np.asarray(pc.table, dtype=np.int32) for pc in codecs]
        )
        code_tables = np.stack(
            [np.asarray(pc.codes, dtype=np.int32) for pc in codecs]
        )
        per_launch = max(1, MAX_BATCH_BYTES // (2 * cb))
        for lo in range(0, len(jobs), per_launch):
            batch = jobs[lo : lo + per_launch]
            for (p, ch, _), blob in zip(
                batch, _pack_jobs(planes, batch, len_tables, code_tables, cb)
            ):
                huff_payloads[(p, ch)] = blob

    entries_all: List[List[codec.ChunkEntry]] = []
    payloads_all: List[List[bytes]] = []
    tables_all: List[Optional[bytes]] = []
    for p, (pc, plane, methods) in enumerate(zip(codecs, planes, methods_all)):
        other = [ch for ch in range(len(methods)) if methods[ch] != codec.Method.HUFF]
        other_blobs = codec._fan_out(
            pool,
            len(other),
            lambda ids, plane=plane, methods=methods, other=other, pc=pc: (
                pc.encode_ids(plane, methods, [other[i] for i in ids])
            ),
        )
        payloads: List[bytes] = [b""] * len(methods)
        for ch, blob in zip(other, other_blobs):
            payloads[ch] = blob
        for ch, m in enumerate(methods):
            if m == codec.Method.HUFF:
                payloads[ch] = huff_payloads[(p, ch)]
        entries = pc.finalize(plane, methods, payloads)
        needs_table = any(e.method == codec.Method.HUFF for e in entries)
        entries_all.append(entries)
        payloads_all.append(payloads)
        tables_all.append(pc.table_blob() if needs_table else None)
    return entries_all, payloads_all, tables_all


# ---------------------------------------------------------------------------
# fused decode
# ---------------------------------------------------------------------------

def _stacked_luts(
    tables_all: Sequence[Optional[bytes]],
) -> Tuple[np.ndarray, int]:
    """Fused ``(sym << 8) | len`` LUTs, one row per plane, at a shared width.

    The shared width is the max code length across every plane's table —
    canonical prefixes stay valid at any LUT width ≥ their own max length,
    so one kernel launch can gather against any plane's row.  Planes
    without a table (no HUFF chunks) get an all-zero row that is never
    selected.

    Memoized on the table bytes: the compressed-resident serving ring
    (``repro.serve.compressed``) decodes the *same* payloads every token,
    so the table unpack + LUT expansion is paid once per blob, not once
    per step.  The cached array is only ever read (it feeds the kernel's
    host→device upload), and the LUT is a pure function of the tables, so
    memoization cannot change decoded bytes.
    """
    return _stacked_luts_cached(tuple(tables_all))


@functools.lru_cache(maxsize=LUT_CACHE_SIZE)
def _stacked_luts_cached(
    tables_all: Tuple[Optional[bytes], ...],
) -> Tuple[np.ndarray, int]:
    lens_all: List[Optional[np.ndarray]] = []
    max_l = 1
    for tb in tables_all:
        if tb is None:
            lens_all.append(None)
            continue
        lens = huffman.unpack_table(tb)
        lens_all.append(lens)
        max_l = max(max_l, int(lens.max(initial=1)))
    luts = np.zeros((len(tables_all), 1 << max_l), dtype=np.int32)
    for p, lens in enumerate(lens_all):
        if lens is None:
            continue
        codes = huffman.canonical_codes(lens)
        lut_sym, lut_len = huffman._build_lut(lens, codes, max_l)
        luts[p] = (lut_sym.astype(np.int32) << 8) | lut_len.astype(np.int32)
    return luts, max_l


def _pack_words(
    jobs: Sequence[Tuple[int, int]],
    entries_all: Sequence[Sequence[codec.ChunkEntry]],
    payloads_all: Sequence[Sequence[bytes]],
    chunk_bytes: int,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Pack each job's payload bytes into the decode kernel's word layout.

    Payload bytes pack into big-endian uint32 words (the encode kernel's
    bit convention), zero-padded to the ``chunk_bytes`` capacity — valid
    payloads are always shorter (expansion guard), and oversized ones are
    rejected up front so corrupt metadata can never drive an out-of-range
    copy.  Returns ``(words, plane_ids, counts, payload_sizes)``.
    """
    c = len(jobs)
    cw = chunk_bytes // 4
    words = np.zeros(c * cw, dtype=np.uint32)
    pids = np.empty(c, dtype=np.int32)
    counts = np.empty(c, dtype=np.int32)
    sizes = np.empty(c, dtype=np.int64)
    for k, (p, ch) in enumerate(jobs):
        payload = payloads_all[p][ch]
        if len(payload) > chunk_bytes:
            raise ValueError(
                "corrupt Huffman payload: payload larger than its chunk"
            )
        pad = -len(payload) % 4
        w = np.frombuffer(bytes(payload) + b"\x00" * pad, dtype=">u4")
        words[k * cw : k * cw + w.size] = w
        pids[k] = p
        counts[k] = entries_all[p][ch].raw_len
        sizes[k] = len(payload)
    return words, pids, counts, sizes


def _check_cursors(
    jobs: Sequence[Tuple[int, int]],
    payloads_all: Sequence[Sequence[bytes]],
    sizes: np.ndarray,
    cursors_h: np.ndarray,
) -> None:
    """The ``decode_many``-equivalent integrity checks on kernel cursors.

    Each chunk's final bit cursor must land inside its payload's final byte
    and the 0-7 pad bits must be zero — truncated or flipped words fail
    cleanly, never silently.
    """
    slack = sizes * 8 - cursors_h
    if np.any((slack < 0) | (slack >= 8)):
        raise ValueError(
            "corrupt Huffman payload: bit cursor did not land on the "
            "chunk's final byte"
        )
    for k, (p, ch) in enumerate(jobs):
        s = int(slack[k])
        payload = payloads_all[p][ch]
        if s and payload and payload[-1] & ((1 << s) - 1):
            raise ValueError(
                "corrupt Huffman payload: nonzero pad bits in the chunk's "
                "final byte"
            )


def _unpack_jobs(
    jobs: Sequence[Tuple[int, int]],
    entries_all: Sequence[Sequence[codec.ChunkEntry]],
    payloads_all: Sequence[Sequence[bytes]],
    luts: np.ndarray,
    chunk_bytes: int,
):
    """Run one fused decode dispatch over ``jobs``; return device symbols.

    ``jobs`` is ``(plane_idx, chunk_idx)`` per HUFF chunk.  The packed
    words are uploaded for this launch only (the :class:`PayloadFeed` path
    instead uploads them once and re-decodes from device memory); after the
    launch the per-chunk bit cursors (a metadata-sized transfer) feed the
    same integrity checks as ``huffman.decode_many``.
    """
    import jax.numpy as jnp

    from repro.kernels import huffdecode, ops

    with tracing.span("znn.codec.pack_words"):
        words, pids, counts, sizes = _pack_words(
            jobs, entries_all, payloads_all, chunk_bytes
        )
    tracing.count_payload_upload(words.nbytes)
    with tracing.span("znn.codec.launch"):
        syms, cursors = huffdecode.huffdecode_chunks_multi(
            jnp.asarray(words),
            jnp.asarray(pids),
            jnp.asarray(counts),
            jnp.asarray(luts),
            chunk_bytes=chunk_bytes,
            interpret=ops.interpret_mode(),
        )
    tracing.count("launches.huffdecode")
    cursors_h = np.asarray(tracing.fetch(cursors), dtype=np.int64)
    with tracing.span("znn.codec.cursor_check"):
        _check_cursors(jobs, payloads_all, sizes, cursors_h)
    return syms


def _verify_payload_crcs(
    flat: Sequence[Tuple[int, int]],
    entries_all: Sequence[Sequence[codec.ChunkEntry]],
    payloads_all: Sequence[Sequence[bytes]],
    pool=None,
) -> None:
    """CRC-verify every chunk payload (same errors and order as
    :meth:`~repro.core.codec.PlaneCodec.decode_into`), fanned across
    ``pool``."""

    def verify(ids):
        for k in ids:
            p, c = flat[k]
            e = entries_all[p][c]
            if e.method == codec.Method.ZERO:
                if e.comp_len or e.crc:
                    raise IOError(
                        "corrupt chunk entry: ZERO chunk with a payload"
                    )
            elif zlib.crc32(payloads_all[p][c]) != e.crc:
                raise IOError(f"chunk payload CRC mismatch (chunk {c})")
        return [None] * len(ids)

    codec._fan_out(pool, len(flat), verify)


def _huff_jobs(
    flat: Sequence[Tuple[int, int]],
    entries_all: Sequence[Sequence[codec.ChunkEntry]],
    payloads_all: Sequence[Sequence[bytes]],
    tables_all: Sequence[Optional[bytes]],
) -> List[Tuple[int, int]]:
    """The stream's HUFF ``(plane, chunk)`` jobs, validated against its
    tables (a HUFF chunk without a plane table, or with an empty non-empty
    payload, is corrupt metadata)."""
    jobs = [
        (p, c) for (p, c) in flat
        if entries_all[p][c].method == codec.Method.HUFF
    ]
    for p in sorted({p for (p, _) in jobs}):
        if tables_all[p] is None:
            raise IOError("corrupt stream: HUFF chunks but no plane table")
    if any(
        not payloads_all[p][c] and entries_all[p][c].raw_len for (p, c) in jobs
    ):
        raise IOError("corrupt chunk entry: empty HUFF payload")
    return jobs


def _decode_other_chunks(
    others: Sequence[Tuple[int, int]],
    entries_all: Sequence[Sequence[codec.ChunkEntry]],
    payloads_all: Sequence[Sequence[bytes]],
    pool=None,
) -> Dict[Tuple[int, int], np.ndarray]:
    """Host-decode every non-HUFF chunk (identical decode + integrity
    checks to ``PlaneCodec.decode_into``), fanned across ``pool``."""

    def decode_other(ids):
        out = []
        for k in ids:
            p, c = others[k]
            e = entries_all[p][c]
            payload = payloads_all[p][c]
            if e.method == codec.Method.ZERO:
                out.append(np.zeros(e.raw_len, dtype=np.uint8))
            elif e.method == codec.Method.STORE:
                if e.comp_len != e.raw_len:
                    raise IOError(
                        "corrupt chunk entry: STORE length != raw length"
                    )
                out.append(np.frombuffer(payload, dtype=np.uint8))
            elif e.method in (codec.Method.ZLIB, codec.Method.HUFFLIB):
                blob = codec._unzlib(payload, e.raw_len)
                if len(blob) != e.raw_len:
                    raise IOError(
                        "corrupt zlib chunk payload: wrong decoded length"
                    )
                out.append(np.frombuffer(blob, dtype=np.uint8))
            else:
                raise ValueError(f"unknown method {e.method}")
        return out

    return dict(zip(others, codec._fan_out(pool, len(others), decode_other)))


def decode_planes(
    entries_all: Sequence[Sequence[codec.ChunkEntry]],
    payloads_all: Sequence[Sequence[bytes]],
    tables_all: Sequence[Optional[bytes]],
    params: codec.CodecParams,
    pool=None,
    device_resident: bool = False,
) -> List[Any]:
    """Device-backed equivalent of the per-plane host decompress loop.

    Every payload's CRC is verified first (same errors, same order as
    :meth:`~repro.core.codec.PlaneCodec.decode_into`), then every ``HUFF``
    chunk across *all* planes decodes in one fused device dispatch (split
    only at :data:`MAX_BATCH_BYTES`) — the compressed words + stacked LUTs
    are the only data-sized host→device transfer.  ``ZERO`` / ``STORE`` /
    ``ZLIB`` chunks decode as host work items on ``pool`` and are spliced
    back in.

    Returns per-plane flat uint8 arrays matching
    :func:`repro.core.codec.decompress_plane` byte-for-byte — numpy by
    default (one device→host transfer of decoded symbols), or
    device-resident ``jax.Array`` planes with ``device_resident=True``
    (put together on device by one compiled assembly, :func:`_assemble`;
    no symbol download), ready for
    :func:`repro.core.device_unplane.consume_planes` to consume in place.
    """
    cb = params.chunk_bytes
    flat = [
        (p, c)
        for p in range(len(entries_all))
        for c in range(len(entries_all[p]))
    ]
    with tracing.span("znn.codec.chunk_crc"):
        _verify_payload_crcs(flat, entries_all, payloads_all, pool)
    jobs = _huff_jobs(flat, entries_all, payloads_all, tables_all)
    others = [
        (p, c) for (p, c) in flat
        if entries_all[p][c].method != codec.Method.HUFF
    ]
    if device_resident:
        index, lengths = _assembly_plan(entries_all, jobs, others, cb)

    windows: List[Any] = []
    huff_syms: dict = {}
    if jobs:
        with tracing.span("znn.codec.luts"):
            luts, _ = _stacked_luts(tables_all)
        per_launch = max(1, MAX_BATCH_BYTES // (2 * cb))
        for lo in range(0, len(jobs), per_launch):
            batch = jobs[lo : lo + per_launch]
            syms = _unpack_jobs(batch, entries_all, payloads_all, luts, cb)
            if device_resident:
                windows.append(syms)
                continue
            # one transfer per launch window
            syms = np.asarray(tracing.fetch(syms))
            with tracing.span("znn.codec.splice"):
                for k, (p, ch) in enumerate(batch):
                    huff_syms[(p, ch)] = syms[k]

    # Host work items: every non-HUFF chunk (identical decode + integrity
    # checks to PlaneCodec.decode_into).
    with tracing.span("znn.codec.host_chunks"):
        other_chunks = _decode_other_chunks(
            others, entries_all, payloads_all, pool
        )

    with tracing.span("znn.codec.splice"):
        if not device_resident:
            return _splice_host(entries_all, huff_syms, other_chunks)
        import jax.numpy as jnp

        if others:
            rows = _other_rows(others, other_chunks, cb)
            tracing.count_payload_upload(rows.nbytes)
            windows.append(jnp.asarray(rows))
        return _assemble(windows, index, lengths)


def _splice_host(
    entries_all: Sequence[Sequence[codec.ChunkEntry]],
    huff_syms: Dict[Tuple[int, int], np.ndarray],
    other_chunks: Dict[Tuple[int, int], np.ndarray],
) -> List[np.ndarray]:
    """Put each plane together on the host from its kernel-decoded and
    host-decoded chunks (the tail of :func:`decode_planes` with
    ``device_resident=False``)."""
    planes: List[np.ndarray] = []
    for p in range(len(entries_all)):
        entries = entries_all[p]
        total = sum(e.raw_len for e in entries)
        out = np.empty(total, dtype=np.uint8)
        off = 0
        for c, e in enumerate(entries):
            piece = (
                huff_syms[(p, c)][: e.raw_len]
                if e.method == codec.Method.HUFF
                else other_chunks[(p, c)]
            )
            out[off : off + e.raw_len] = piece
            off += e.raw_len
        planes.append(out)
    return planes


# ---------------------------------------------------------------------------
# plane assembly on the device
# ---------------------------------------------------------------------------
#
# A device-resident decode ends with its planes' chunks in rows: the launch
# windows' ``(c, chunk_bytes)`` symbol arrays, one row per HUFF chunk in
# ``jobs`` order, then the host-decoded chunks' ``(n_other, chunk_bytes)``
# rows in ``others`` order.  The container cuts every plane at
# ``chunk_bytes`` strides, so a plane is its chunks' rows in order,
# flattened and cut to its length: one gather per plane, all of a stream's
# planes in one compiled dispatch.  The compile key is shapes only (the
# sources' row counts and the planes' lengths); which row goes where is a
# runtime argument, so streams of one shape never recompile because a
# different chunk fell back to STORE.


def _assembly_plan(
    entries_all: Sequence[Sequence[codec.ChunkEntry]],
    jobs: Sequence[Tuple[int, int]],
    others: Sequence[Tuple[int, int]],
    chunk_bytes: int,
) -> Tuple[np.ndarray, Tuple[int, ...]]:
    """Where each chunk's bytes lie in the joined rows, from the chunk
    table alone.

    Returns the int32 row of every chunk, plane-major, and the planes'
    lengths.  A plane whose chunks are not cut at ``chunk_bytes`` strides
    (a chunk longer than a row, an empty chunk, a short chunk before the
    plane's last) is corrupt.
    """
    row = {key: i for i, key in enumerate(jobs)}
    row.update((key, len(jobs) + i) for i, key in enumerate(others))
    index = np.empty(len(row), dtype=np.int32)
    lengths = []
    k = 0
    for p, entries in enumerate(entries_all):
        last = len(entries) - 1
        for c, e in enumerate(entries):
            n = e.raw_len
            if not 0 < n <= chunk_bytes or (c < last and n != chunk_bytes):
                raise IOError(
                    "corrupt chunk table: plane not cut at chunk_bytes "
                    f"strides (plane {p}, chunk {c}, {n} bytes)"
                )
            index[k] = row[(p, c)]
            k += 1
        lengths.append(sum(e.raw_len for e in entries))
    return index, tuple(lengths)


def _other_rows(
    others: Sequence[Tuple[int, int]],
    other_chunks: Dict[Tuple[int, int], np.ndarray],
    chunk_bytes: int,
) -> np.ndarray:
    """The host-decoded chunks as ``(n_other, chunk_bytes)`` rows, a
    plane's short last chunk zero-padded."""
    rows = np.zeros((len(others), chunk_bytes), dtype=np.uint8)
    for k, key in enumerate(others):
        piece = other_chunks[key]
        rows[k, : piece.size] = piece
    return rows


@functools.cache
def _assembler():
    """The jitted assembly; ``lengths`` is its one static argument."""
    import jax
    import jax.numpy as jnp

    def assemble(sources, index, lengths):
        rows = sources[0] if len(sources) == 1 else jnp.concatenate(sources)
        cb = rows.shape[1]
        planes, lo = [], 0
        for n in lengths:
            k = -(-n // cb)
            planes.append(rows[index[lo : lo + k]].reshape(-1)[:n])
            lo += k
        return planes

    return jax.jit(assemble, static_argnums=2)


def _assemble(
    sources: Sequence[Any], index: Any, lengths: Tuple[int, ...]
) -> List[Any]:
    """Device planes of ``lengths`` bytes from the rows of ``sources``
    (launch windows, then the host-decoded rows), placed by ``index`` from
    :func:`_assembly_plan`: one compiled dispatch, or none for a stream
    without chunks."""
    n = int(index.shape[0])
    if not n:
        return [np.empty(0, dtype=np.uint8) for _ in lengths]
    planes = _assembler()(tuple(sources), index, lengths)
    tracing.count("assemblies")
    tracing.count("assembled_chunks", n)
    return planes


# ---------------------------------------------------------------------------
# device-resident payload feed
# ---------------------------------------------------------------------------

class PayloadFeed:
    """Device-resident decode plan for one parsed ZNN1 stream.

    :func:`decode_planes` re-reads host payload bytes, re-packs kernel words
    and re-uploads them on *every* call — fine for one-shot restores, wasted
    work for the serving ring, which decodes the same immutable payloads
    every token.  A feed front-loads all of that exactly once:

    * payload CRCs, the ``decode_many``-equivalent bit-cursor / pad-bit
      checks, and the HUFF metadata validation run **at build time** (the
      payloads are immutable once parsed, so one verification covers every
      later decode — and the warmup launch that produces the cursors also
      compiles the dispatch);
    * the packed HUFF words, stacked LUTs, the host-decoded
      ``ZERO``/``STORE``/``ZLIB`` chunk rows and the assembly's row index
      upload **once** and stay resident in device memory;
    * :meth:`decode` then re-runs the fused kernel directly from those
      resident buffers and puts the planes together in one compiled
      assembly (:func:`_assemble`, shared with :func:`decode_planes`) —
      **zero host→device payload traffic per decode** (asserted via
      :func:`transfer_stats`), returning device planes byte-identical to
      ``decode_planes(..., device_resident=True)``.

    Residency and caching change wall-clock and memory only, never bytes:
    the kernel consumes the exact words ``_pack_words`` would rebuild, so
    decoded planes cannot differ from the per-call path.
    """

    def __init__(
        self,
        entries_all: Sequence[Sequence[codec.ChunkEntry]],
        payloads_all: Sequence[Sequence[bytes]],
        tables_all: Sequence[Optional[bytes]],
        params: codec.CodecParams,
        pool=None,
    ):
        import jax.numpy as jnp

        from repro.kernels import huffdecode, ops

        cb = params.chunk_bytes
        if not supports_decode(cb):
            raise ValueError(
                "device payload feed requires whole-uint32-word chunks "
                f"(chunk_bytes % 4 == 0, got {cb}) and an importable jax"
            )
        self.chunk_bytes = cb
        self._interpret = ops.interpret_mode()

        flat = [
            (p, c)
            for p in range(len(entries_all))
            for c in range(len(entries_all[p]))
        ]
        with tracing.span("znn.codec.chunk_crc"):
            _verify_payload_crcs(flat, entries_all, payloads_all, pool)
        jobs = _huff_jobs(flat, entries_all, payloads_all, tables_all)
        others = [
            (p, c) for (p, c) in flat
            if entries_all[p][c].method != codec.Method.HUFF
        ]
        # Decode-time assembly needs only the row index and the planes'
        # lengths; the payload bytes themselves are not retained host-side.
        index, self._lengths = _assembly_plan(entries_all, jobs, others, cb)

        self._luts = None
        self._windows: List[Tuple[Any, Any, Any]] = []
        if jobs:
            with tracing.span("znn.codec.luts"):
                luts, _ = _stacked_luts(tables_all)
            self._luts = jnp.asarray(luts)
            per_launch = max(1, MAX_BATCH_BYTES // (2 * cb))
            for lo in range(0, len(jobs), per_launch):
                batch = jobs[lo : lo + per_launch]
                with tracing.span("znn.codec.pack_words"):
                    words, pids, counts, sizes = _pack_words(
                        batch, entries_all, payloads_all, cb
                    )
                tracing.count_payload_upload(words.nbytes)
                with tracing.span("znn.codec.launch"):
                    wd = jnp.asarray(words)
                    pd = jnp.asarray(pids)
                    cd = jnp.asarray(counts)
                    # Warmup launch: compiles the dispatch and runs the
                    # cursor / pad-bit integrity checks once for the feed's
                    # lifetime.
                    _syms, cursors = huffdecode.huffdecode_chunks_multi(
                        wd, pd, cd, self._luts,
                        chunk_bytes=cb,
                        interpret=self._interpret,
                    )
                tracing.count("launches.huffdecode")
                cursors_h = np.asarray(tracing.fetch(cursors), dtype=np.int64)
                with tracing.span("znn.codec.cursor_check"):
                    _check_cursors(batch, payloads_all, sizes, cursors_h)
                self._windows.append((wd, pd, cd))

        with tracing.span("znn.codec.host_chunks"):
            other_chunks = _decode_other_chunks(
                others, entries_all, payloads_all, pool
            )
        self._rows = None
        if others:
            with tracing.span("znn.codec.splice"):
                rows = _other_rows(others, other_chunks, cb)
                tracing.count_payload_upload(rows.nbytes)
                self._rows = jnp.asarray(rows)
        self._index = jnp.asarray(index)
        self.dispatches = self._count_dispatches()

    @property
    def n_planes(self) -> int:
        return len(self._lengths)

    def _count_dispatches(self) -> int:
        """Device dispatches one :meth:`decode` issues: a launch per window
        and the assembly, unless the stream has no chunks."""
        return len(self._windows) + (int(self._index.shape[0]) > 0)

    @property
    def device_bytes(self) -> int:
        """Resident HBM footprint of the feed's payload buffers."""
        total = sum(int(wd.nbytes) for (wd, _, _) in self._windows)
        if self._rows is not None:
            total += int(self._rows.nbytes)
        return total

    def decode(self) -> List[Any]:
        """Device planes for this stream, straight from resident buffers.

        Byte-identical to ``decode_planes(..., device_resident=True)`` on
        the same parsed stream; no host payload bytes are touched and no
        payload-sized host→device transfer occurs.
        """
        with tracing.span("znn.feed.decode"):
            planes = self._decode()
        tracing.count("launches.huffdecode", len(self._windows))
        tracing.count("feed_dispatches", self.dispatches)
        return planes

    def _decode(self) -> List[Any]:
        from repro.kernels import huffdecode

        sources = []
        for wd, pd, cd in self._windows:
            # Cursors were integrity-checked at build; the payload words are
            # immutable, so re-checking per decode would re-verify the same
            # bits — drop them without a device→host transfer.
            syms, _cursors = huffdecode.huffdecode_chunks_multi(
                wd, pd, cd, self._luts,
                chunk_bytes=self.chunk_bytes,
                interpret=self._interpret,
            )
            sources.append(syms)
        if self._rows is not None:
            sources.append(self._rows)
        return _assemble(sources, self._index, self._lengths)
