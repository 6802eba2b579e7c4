"""Whole save path's share of the chip's HBM bandwidth: raw state bytes read
and stored bytes written by the saves of the window, over peak bandwidth
times the window."""


def read(m):
    x = m["run"].extra
    moved = x["raw"] * m["ops"] + x["written"]
    return 100.0 * moved / (m["peaks"]["hbm_bytes_per_s"] * m["window_s"])
