"""Share of the traced window the saving thread spent taking the state's
snapshot: time in the program's ``znn.ckpt.snapshot`` spans, the blocking
device-to-host copy of every leaf before compression starts, from
``repro.core.tracing.snapshot()``.  ``None`` where the program records no
such span."""


def read(m):
    try:
        from repro.core import tracing
    except ImportError:
        return None
    span = tracing.snapshot()["spans"].get("znn.ckpt.snapshot", {}).get("caller")
    if not span or m["window_s"] <= 0:
        return None
    return 100.0 * span["total_s"] / m["window_s"]
