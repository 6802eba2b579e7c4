"""Share of the traced window the ring's decode worker spent issuing the
payload feed's device work: self time of the program's ``znn.feed.decode``
spans on the worker thread (the Huffman launch of each window and one
compiled assembly of the leaf's planes), from
``repro.core.tracing.snapshot()``.  ``None`` where the program records no
spans."""


def read(m):
    try:
        from repro.core import tracing
    except ImportError:
        return None
    spans = tracing.snapshot()["spans"]
    if not spans or m["window_s"] <= 0:
        return None
    s = spans.get("znn.feed.decode", {}).get("worker", {}).get("self_s", 0.0)
    return 100.0 * s / m["window_s"]
