"""Whole restore path's share of the chip's HBM bandwidth: per restore the
stored bytes it reads (the checkpoint steps it decodes) and the raw bytes it
writes into HBM, times restores, over peak bandwidth times the window.  It
stays when a kernel is fused away, and bounds every kernel's share."""


def read(m):
    x = m["run"].extra
    moved = (x["read_stored"] + x["raw"]) * m["ops"]
    return 100.0 * moved / (m["peaks"]["hbm_bytes_per_s"] * m["window_s"])
