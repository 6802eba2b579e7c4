"""Share of the traced window the serving thread waited for a layer's
weights: time in the program's ``znn.ring.wait`` spans (blocked on the
ring's decode job, or decoding inline), from
``repro.core.tracing.snapshot()``.  ``None`` where the program records no
spans."""


def read(m):
    try:
        from repro.core import tracing
    except ImportError:
        return None
    spans = tracing.snapshot()["spans"]
    if not spans or m["window_s"] <= 0:
        return None
    s = spans.get("znn.ring.wait", {}).get("caller", {}).get("total_s", 0.0)
    return 100.0 * s / m["window_s"]
