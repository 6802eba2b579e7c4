"""Share of the traced window the ring's decode worker spent decoding
expert layers: time in the program's ``znn.ring.experts`` spans (the decode
job of one ``moe_layers`` layer, nested in ``znn.ring.decode``) on the
worker thread, from ``repro.core.tracing.snapshot()``.  ``None`` where the
program records no such span."""


def read(m):
    try:
        from repro.core import tracing
    except ImportError:
        return None
    s = tracing.snapshot()["spans"].get("znn.ring.experts", {}).get("worker", {}).get("total_s")
    if s is None or m["window_s"] <= 0:
        return None
    return 100.0 * s / m["window_s"]
