"""Mean ring step time over the mean time of plain ``decode_step``, layer at a
time, on the same weights uncompressed (timed after the window)."""


def read(m):
    ring = m["run"].per_op
    plain = m["run"].extra.get("control_step_s")
    if not ring or not plain:
        return None
    return (sum(ring) / len(ring)) / (sum(plain) / len(plain))
