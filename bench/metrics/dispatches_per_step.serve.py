"""Eager device ops the payload feeds issue per ring step: the program's
``feed_dispatches`` counter over the traced window
(``repro.core.tracing.snapshot()["traced"]``) over the window's steps.
``None`` where the program records no spans."""


def read(m):
    try:
        from repro.core import tracing
    except ImportError:
        return None
    traced = tracing.snapshot()["traced"]
    if not traced or not m["ops"]:
        return None
    return traced["feed_dispatches"] / m["ops"]
