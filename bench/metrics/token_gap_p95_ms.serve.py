"""95th percentile of the host-clock time of one ring step (one token for
every sequence), each ended by the next token reaching the host."""

import statistics


def read(m):
    steps = m["run"].per_op
    if len(steps) < 2:
        return None
    return 1000.0 * statistics.quantiles(steps, n=20, method="inclusive")[18]
