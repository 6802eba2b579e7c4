"""Huffman bit-pack kernel's share of its HBM roofline in the save cell: the symbol bytes read and payload bytes written by the saves of the window (``work.py``, from the checkpoint's chunk tables), over the peak bandwidth, over its summed device time in the trace."""


def read(m):
    seconds = m["trace"].kernel_s("bitpack")
    moved = m["run"].extra.get("bitpack")
    if not seconds or not moved:
        return None
    total = moved
    return 100.0 * total / m["peaks"]["hbm_bytes_per_s"] / seconds
