"""Rate at which the serving ring's decode worker makes layers' weights
ready: raw bytes it decoded in the traced window (the program's
``ring_raw_bytes.*`` counters, one per block kind, from
``repro.core.tracing.snapshot()["traced"]``) over the worker's time in
``znn.ring.decode`` spans, in GB/s.  ``None`` where the program keeps no
such counters."""


def read(m):
    try:
        from repro.core import tracing
    except ImportError:
        return None
    snap = tracing.snapshot()
    raw = sum(v for k, v in snap["traced"].items() if k.startswith("ring_raw_bytes."))
    s = snap["spans"].get("znn.ring.decode", {}).get("worker", {}).get("total_s")
    if not raw or not s:
        return None
    return raw / s / 1e9
