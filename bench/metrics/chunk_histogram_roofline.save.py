"""Probe histogram kernel's share of its HBM roofline in the save cell: every
byte of every plane read and 256 four-byte bins written for each chunk of
each plane by the window's saves (``work.py``, from the checkpoint's chunk
tables), over the peak bandwidth, over its summed device time in the trace
(op ``chunk_histogram_2d``)."""


def read(m):
    seconds = m["trace"].kernel_s("chunk_histogram")
    moved = m["run"].extra.get("chunk_histogram")
    if not seconds or not moved:
        return None
    return 100.0 * moved / m["peaks"]["hbm_bytes_per_s"] / seconds
