"""Share of the traced window the restoring thread sat in blocking
device-to-host fetches (the program's ``znn.codec.fetch`` spans, from
``repro.core.tracing.snapshot()``): the device's decode that the host waits
out instead of overlapping.  ``None`` where the program records no
spans."""


def read(m):
    try:
        from repro.core import tracing
    except ImportError:
        return None
    spans = tracing.snapshot()["spans"]
    if not spans or m["window_s"] <= 0:
        return None
    s = spans.get("znn.codec.fetch", {}).get("caller", {}).get("total_s", 0.0)
    return 100.0 * s / m["window_s"]
