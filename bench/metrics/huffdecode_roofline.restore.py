"""Huffman decode kernel's share of its HBM roofline in the restore cells: the bytes it must move (HUFF payloads read, symbols written; ``work.py``) per restore, times restores, over the peak bandwidth, over its summed device time in the trace."""


def read(m):
    seconds = m["trace"].kernel_s("huffdecode")
    moved = m["run"].extra.get("huffdecode")
    if not seconds or not moved:
        return None
    total = moved * m["ops"]
    return 100.0 * total / m["peaks"]["hbm_bytes_per_s"] / seconds
