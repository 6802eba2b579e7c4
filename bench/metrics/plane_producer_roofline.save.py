"""Plane producer's share of its HBM roofline in the save cell: the bytes it
must move in the window's saves (raw words read, and the base's words for an
XOR delta; byte planes written; ``work.py``, from the checkpoint's chunk
tables), over the peak bandwidth, over the summed device time of the two
kernels that make the planes in ``kernels/fused_plane.py``'s dispatch: the
XOR (``xor_elems_2d``) and the byte grouping (``bytegroup_*``).  The probe
histograms of the same dispatch have a reader of their own
(``chunk_histogram_roofline.save``)."""

KERNELS = ("xor_elems", "bytegroup")


def read(m):
    seconds = sum(m["trace"].kernel_s(k) for k in KERNELS)
    moved = m["run"].extra.get("plane_producer")
    if not seconds or not moved:
        return None
    return 100.0 * moved / m["peaks"]["hbm_bytes_per_s"] / seconds
