"""Huffman payload bytes uploaded host -> device per raw byte restored, from
the program's counter (``device_entropy.transfer_stats()["payload_bytes"]``),
over the window's restores.  It counts only packed Huffman words."""


def read(m):
    x = m["run"].extra
    if "uploads" not in x:
        return None
    return x["uploads"] / (x["raw"] * m["ops"])
