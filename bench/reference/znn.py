"""Plain reader of ZipNN checkpoints, written from the format alone.

It imports nothing of the program under test.  It parses a checkpoint
step's ``manifest.json`` and the ZNN1 containers in its ``data.bin``, and
rebuilds any chunk of any leaf: canonical Huffman (MSB-first, byte-aligned
chunks), raw, all-zero and raw-deflate payloads; byte planes (plane 0 the
most significant byte of the rotated word); the inverse rotate-left-1 of
float layouts; and the XOR chains of ``delta`` (against the step's base)
and ``delta_prev`` (against the previous save) entries.

ZNN1 layout (little-endian): magic ``ZNN1``, u16 version, u16 flags,
16-byte layout name, u64 body bytes, u32 per-plane chunk bytes, u8 plane
count, 3 pad bytes; per plane a u8 table flag (+128 bytes of 4-bit code
lengths); a chunk-major map of (u8 method, u32 length, u32 crc) records;
then the payloads in the same order.
"""

from __future__ import annotations

import json
import struct
import zlib
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

STORE, ZERO, HUFF, ZLIB, HUFFLIB = 0, 1, 2, 3, 4
_HDR = struct.Struct("<4sHH16sQIB3x")
_REC = struct.Struct("<BII")

# layout name -> (bytes per element, planes, rotated)
LAYOUTS = {"bf16": (2, 2, True), "fp16": (2, 2, True), "fp32": (4, 4, True)}
_UINT = {2: np.uint16, 4: np.uint32}


@dataclass
class Chunk:
    method: int
    comp_len: int
    raw_len: int
    crc: int
    offset: int            # payload offset inside the blob


@dataclass
class Stream:
    layout: str
    n_bytes: int
    chunk_bytes: int       # per plane
    tables: List[Optional[np.ndarray]]     # per plane: 256 code lengths
    chunks: List[List[Chunk]]              # [chunk][plane]

    @property
    def itemsize(self) -> int:
        return LAYOUTS[self.layout][0]

    @property
    def n_planes(self) -> int:
        return len(self.tables)



def parse(blob: bytes) -> Stream:
    magic, version, flags, name, n_bytes, chunk_bytes, n_planes = _HDR.unpack_from(blob, 0)
    if magic != b"ZNN1" or version != 1:
        raise ValueError("not a ZNN1 v1 stream")
    layout = name.rstrip(b"\0").decode()
    if layout not in LAYOUTS or LAYOUTS[layout][1] != n_planes:
        raise ValueError(f"layout {layout!r} with {n_planes} planes is not read here")
    off = _HDR.size
    tables: List[Optional[np.ndarray]] = []
    for _ in range(n_planes):
        has = blob[off]
        off += 1
        if has:
            nib = np.frombuffer(blob, np.uint8, 128, off)
            lens = np.empty(256, np.int64)
            lens[0::2], lens[1::2] = nib >> 4, nib & 15
            tables.append(lens)
            off += 128
        else:
            tables.append(None)
    per_plane = n_bytes // n_planes
    n_chunks = -(-per_plane // chunk_bytes)
    recs = []
    for c in range(n_chunks):
        row = []
        for _ in range(n_planes):
            m, n, crc = _REC.unpack_from(blob, off)
            off += _REC.size
            row.append([m, n, min(chunk_bytes, per_plane - c * chunk_bytes), crc])
        recs.append(row)
    chunks = []
    for row in recs:
        out = []
        for m, n, raw, crc in row:
            out.append(Chunk(m, n, raw, crc, off))
            off += n
        chunks.append(out)
    if blob[off:off + 4] == b"TAIL":
        raise ValueError("streams with a TAIL are not read here")
    return Stream(layout, n_bytes, chunk_bytes, tables, chunks)


def _canonical(lens: np.ndarray) -> Tuple[List[int], Dict[Tuple[int, int], int]]:
    """Canonical code table as {(length, code): symbol}."""
    code, prev, table = 0, 0, {}
    for s in sorted(np.nonzero(lens)[0], key=lambda s: (lens[s], s)):
        code <<= int(lens[s]) - prev
        prev = int(lens[s])
        table[(prev, code)] = int(s)
        code += 1
    return table


def huffman_decode(payloads: List[bytes], counts: List[int], tables: List[np.ndarray]) -> List[np.ndarray]:
    """Decode chunks in lockstep, one symbol of every chunk per step, each
    chunk against its own table of code lengths (a full 2**L lookup table
    per chunk, built from the canonical codes)."""
    L = max(int(t.max()) for t in tables)
    k = len(payloads)
    lut_sym = np.zeros((k, 1 << L), np.uint8)
    lut_len = np.zeros((k, 1 << L), np.int64)
    built: Dict[bytes, Tuple[np.ndarray, np.ndarray]] = {}
    for i, lens in enumerate(tables):
        key = lens.tobytes()
        if key not in built:
            sym = np.zeros(1 << L, np.uint8)
            ln = np.zeros(1 << L, np.int64)
            for (n, code), s in _canonical(lens).items():
                lo = code << (L - n)
                sym[lo:lo + (1 << (L - n))] = s
                ln[lo:lo + (1 << (L - n))] = n
            built[key] = (sym, ln)
        lut_sym[i], lut_len[i] = built[key]
    lut_sym, lut_len = lut_sym.reshape(-1), lut_len.reshape(-1)
    width = max(len(p) for p in payloads) + 4
    buf = np.zeros((k, width), np.uint8)
    for i, p in enumerate(payloads):
        buf[i, :len(p)] = np.frombuffer(p, np.uint8)
    flat = buf.reshape(-1).astype(np.uint32)
    win = np.zeros(flat.size, np.uint32)
    win[:-3] = (flat[:-3] << 24) | (flat[1:-2] << 16) | (flat[2:-1] << 8) | flat[3:]
    base = np.arange(k, dtype=np.int64) * width
    lut_base = np.arange(k, dtype=np.int64) << L
    counts_a = np.asarray(counts, np.int64)
    out = np.zeros((k, int(counts_a.max())), np.uint8)
    bit = np.zeros(k, np.int64)
    mask = np.uint32((1 << L) - 1)
    for i in range(out.shape[1]):
        w = (win[base + (bit >> 3)] >> (32 - L - (bit & 7)).astype(np.uint32)) & mask
        idx = lut_base + w
        out[:, i] = lut_sym[idx]
        bit += np.where(i < counts_a, lut_len[idx], 0)
    for i, p in enumerate(payloads):
        if -(-int(bit[i]) // 8) != len(p):
            raise ValueError("Huffman payload length does not match its symbols")
    return [out[i, :counts[i]] for i in range(k)]


class Checkpoint:
    """One checkpoint directory with many steps; rebuilds element ranges."""

    def __init__(self, directory: Path):
        self.dir = Path(directory)
        self._manifests: Dict[int, dict] = {}
        self._data: Dict[int, bytes] = {}
        self._streams: Dict[Tuple[int, str], Stream] = {}

    def steps(self) -> List[int]:
        return sorted(int(p.name[5:]) for p in self.dir.glob("step_*"))

    def manifest(self, step: int) -> dict:
        if step not in self._manifests:
            self._manifests[step] = json.loads((self.dir / f"step_{step}" / "manifest.json").read_text())
        return self._manifests[step]

    def entry(self, step: int, key: str) -> dict:
        for e in self.manifest(step)["entries"]:
            if e["key"] == key:
                return e
        raise KeyError(f"step {step} has no leaf {key}")

    def blob(self, step: int, key: str) -> bytes:
        if step not in self._data:
            self._data[step] = (self.dir / f"step_{step}" / "data.bin").read_bytes()
        e = self.entry(step, key)
        return self._data[step][e["offset"]:e["offset"] + e["size"]]

    def stream(self, step: int, key: str) -> Stream:
        if (step, key) not in self._streams:
            self._streams[(step, key)] = parse(self.blob(step, key))
        return self._streams[(step, key)]

    def stored_bytes(self, step: int) -> int:
        return (self.dir / f"step_{step}" / "data.bin").stat().st_size

    def chunk_planes(self, step: int, key: str, c: int) -> List[Tuple[Chunk, bytes, Optional[np.ndarray]]]:
        st = self.stream(step, key)
        blob = self.blob(step, key)
        return [(ch, blob[ch.offset:ch.offset + ch.comp_len], st.tables[p])
                for p, ch in enumerate(st.chunks[c])]

    def read_chunks(self, wants: List[Tuple[int, str, int]]) -> Dict[Tuple[int, str, int], np.ndarray]:
        """Element words (uint16/uint32) of chunk ``c`` of leaf ``key`` at
        ``step``, for every (step, key, c) asked, delta chains resolved."""
        need: Dict[Tuple[int, str, int], None] = {}

        def walk(step, key, c):
            if (step, key, c) in need:
                return
            need[(step, key, c)] = None
            kind = self.entry(step, key)["kind"]
            m = self.manifest(step)
            if kind == "delta":
                walk(m["base_step"], key, c)
            elif kind == "delta_prev":
                walk(m["prev_step"], key, c)

        for w in wants:
            walk(*w)
        # Every HUFF payload needed decodes in one lockstep batch.
        planes: Dict[Tuple[int, str, int, int], np.ndarray] = {}
        huff: List = []
        for (step, key, c) in need:
            for p, (ch, payload, lens) in enumerate(self.chunk_planes(step, key, c)):
                if zlib.crc32(payload) != ch.crc and ch.method != ZERO:
                    raise ValueError(f"CRC mismatch at step {step} {key} chunk {c}")
                tag = (step, key, c, p)
                if ch.method == ZERO:
                    planes[tag] = np.zeros(ch.raw_len, np.uint8)
                elif ch.method == STORE:
                    planes[tag] = np.frombuffer(payload, np.uint8)
                elif ch.method in (ZLIB, HUFFLIB):
                    planes[tag] = np.frombuffer(zlib.decompress(payload, -15), np.uint8)
                elif ch.method == HUFF:
                    huff.append((tag, payload, ch.raw_len, lens))
                else:
                    raise ValueError(f"unknown chunk method {ch.method}")
        if huff:
            outs = huffman_decode([h[1] for h in huff], [h[2] for h in huff], [h[3] for h in huff])
            for (tag, *_), out in zip(huff, outs):
                planes[tag] = out
        words: Dict[Tuple[int, str, int], np.ndarray] = {}
        for step, key, c in sorted(need, key=lambda t: t[0]):
            st = self.stream(step, key)
            size = st.itemsize
            cols = [planes[(step, key, c, p)] for p in range(st.n_planes)]
            if any(x.size != cols[0].size for x in cols):
                raise ValueError("planes of one chunk differ in length")
            u = np.zeros(cols[0].size, np.uint64)
            for p, col in enumerate(cols):              # plane 0 = top byte
                u |= col.astype(np.uint64) << np.uint64(8 * (size - 1 - p))
            bits = 8 * size
            u = ((u >> np.uint64(1)) | ((u & np.uint64(1)) << np.uint64(bits - 1)))
            u = u.astype(_UINT[size])
            kind = self.entry(step, key)["kind"]
            m = self.manifest(step)
            if kind == "delta":
                u = u ^ words[(m["base_step"], key, c)]
            elif kind == "delta_prev":
                u = u ^ words[(m["prev_step"], key, c)]
            words[(step, key, c)] = u
        return {w: words[w] for w in wants}
