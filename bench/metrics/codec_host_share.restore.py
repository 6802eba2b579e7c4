"""Share of the traced window the restoring thread spent in the codec's
host phases: self time of the program's ``znn.codec.parse`` (container
parse), ``.chunk_crc`` (per-chunk CRCs), ``.luts`` (decode tables),
``.pack_words`` (kernel word packing), ``.cursor_check`` (bit-cursor
checks), ``.host_chunks`` (non-Huffman chunks) and ``.splice`` (plane
assembly) spans, from ``repro.core.tracing.snapshot()``.  ``None`` where
the program records no spans."""

SPANS = ("znn.codec.parse", "znn.codec.chunk_crc", "znn.codec.luts",
         "znn.codec.pack_words", "znn.codec.cursor_check",
         "znn.codec.host_chunks", "znn.codec.splice")


def read(m):
    try:
        from repro.core import tracing
    except ImportError:
        return None
    spans = tracing.snapshot()["spans"]
    if not spans or m["window_s"] <= 0:
        return None
    s = sum(spans.get(n, {}).get("caller", {}).get("self_s", 0.0) for n in SPANS)
    return 100.0 * s / m["window_s"]
