"""Work each codec kernel must do, from the container's own sizes.

Every number here comes from a ZNN1 stream's chunk table (payload bytes
and raw lengths of its chunks) or from raw tensor bytes, never from how a
kernel pads or tiles, so a kernel's roofline share reads the same work
whatever implements it.  All of these kernels are bound by HBM bandwidth:
they do a handful of integer operations per byte.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable, Tuple

from bench.reference.znn import HUFF, Stream

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """Peaks of one chip; a device missing from the table is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS.name}")
    return table[device_kind]


def huff_chunks(streams: Iterable[Stream]):
    for st in streams:
        for row in st.chunks:
            for ch in row:
                if ch.method == HUFF:
                    yield ch


def huffdecode_bytes(streams: Iterable[Stream]) -> int:
    """Huffman decode: read each HUFF payload, write its symbols."""
    return sum(ch.comp_len + ch.raw_len for ch in huff_chunks(streams))


def bitpack_bytes(streams: Iterable[Stream]) -> int:
    """Huffman bit-pack: read each HUFF chunk's symbols, write its payload."""
    return sum(ch.raw_len + ch.comp_len for ch in huff_chunks(streams))


def raw_bytes(st: Stream) -> int:
    """A stream's raw bytes: its chunks' lengths over every plane."""
    return sum(ch.raw_len for row in st.chunks for ch in row)


def plane_bytes(streams: Iterable[Tuple[Stream, bool]]) -> int:
    """Plane producer or consumer: each leaf's raw words on one side and its
    byte planes on the other, and its base's words where it is an XOR delta
    (``True`` beside its stream)."""
    return sum(raw_bytes(st) * (3 if xor else 2) for st, xor in streams)


HIST_BINS_BYTES = 256 * 4        # one chunk of one plane: 256 int32 bins


def histogram_bytes(streams: Iterable[Stream]) -> int:
    """Probe histograms: read every byte of every plane, write 256 bins for
    each chunk of each plane."""
    return sum(raw_bytes(st) + HIST_BINS_BYTES * sum(len(row) for row in st.chunks)
               for st in streams)
