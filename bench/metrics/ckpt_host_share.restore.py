"""Share of the traced window the restoring thread spent in the checkpoint
layer's own host work: self time of the program's ``znn.ckpt.scan``
(manifest scan), ``znn.ckpt.read`` (manifest and ``data.bin`` read) and
``znn.ckpt.entry_crc`` (CRC of each entry blob) spans, from
``repro.core.tracing.snapshot()``.  ``None`` where the program records no
spans."""

SPANS = ("znn.ckpt.scan", "znn.ckpt.read", "znn.ckpt.entry_crc")


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
