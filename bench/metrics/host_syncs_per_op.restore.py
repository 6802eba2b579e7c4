"""Blocking device-to-host fetches per restore: the program's
``d2h_fetches`` counter over the traced window
(``repro.core.tracing.snapshot()["traced"]``) over the window's restores.
``None`` where the program records no spans."""


def read(m):
    try:
        from repro.core import tracing
    except ImportError:
        return None
    traced = tracing.snapshot()["traced"]
    if not traced or not m["ops"]:
        return None
    return traced["d2h_fetches"] / m["ops"]
