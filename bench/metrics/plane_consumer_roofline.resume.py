"""Plane consumer's share of its HBM roofline in the resume cell: per
restore the bytes it must move (byte planes read, and the base's words for
an XOR delta; raw words written; ``work.py``, from the chunk tables of the
checkpoints a restore decodes), times restores, over the peak bandwidth,
over its summed device time in the trace (op ``plane_consumer``)."""


def read(m):
    seconds = m["trace"].kernel_s("plane_consumer")
    moved = m["run"].extra.get("plane_consumer")
    if not seconds or not moved:
        return None
    return 100.0 * moved * m["ops"] / m["peaks"]["hbm_bytes_per_s"] / seconds
