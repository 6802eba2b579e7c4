"""Share of the traced window the saving thread spent writing: self time of
the program's ``znn.ckpt.write`` spans (each leaf's blob written to
``data.bin``, its flush and fsync, the manifest and the atomic rename), from
``repro.core.tracing.snapshot()``.  ``None`` where the program records no
such span."""


def read(m):
    try:
        from repro.core import tracing
    except ImportError:
        return None
    span = tracing.snapshot()["spans"].get("znn.ckpt.write", {}).get("caller")
    if not span or m["window_s"] <= 0:
        return None
    return 100.0 * span["self_s"] / m["window_s"]
