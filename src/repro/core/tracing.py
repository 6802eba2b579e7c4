"""Spans and counters of the codec, checkpoint and serving paths.

Counters are always on: plain numbers under one lock, bumped where the
program moves payload bytes, fetches from the device, launches a kernel or
compiles.  :func:`counters` reads them; ``device_entropy.transfer_stats()``
is a view of the two payload counters.

=====================  ====================================================
``payload_uploads``    payload-sized host→device uploads on the entropy
``payload_bytes``      paths (HUFF symbols, packed words, non-HUFF splice)
``d2h_fetches``        blocking device→host fetches on the codec and
``d2h_bytes``          checkpoint paths, and the bytes they bring back
``launches.<kernel>``  kernel launches: ``huffdecode``, ``bitpack``,
                       ``plane_producer``, ``plane_consumer``
``feed_dispatches``    device dispatches issued by payload-feed decodes,
                       added once per decode from its piece counts
``assemblies``         compiled plane assemblies of device-resident
``assembled_chunks``   decodes, and the chunks they placed
``compiles``           XLA compilations, and the seconds spent lowering
``compile_s``          and compiling, from JAX's compile-duration events
``ring_raw_bytes.*``   raw bytes the serving ring decoded, by block kind
                       (``dense``, ``moe``, ``ssm``), added once per job
=====================  ====================================================

The serving ring's spans: ``znn.ring.step`` (root of one decode step),
``znn.ring.wait`` (the caller blocked on a layer's weights),
``znn.ring.layer`` (one layer's compute dispatched), ``znn.ring.tail``
(cache write, final norm, head), ``znn.ring.decode`` (one decode job) and,
nested in it, ``znn.ring.experts`` (the job of a ``moe_layers`` layer).

Spans are on exactly while a profiler session is active
(``jax.profiler.trace`` / ``start_trace``, or a profiler server capturing).
With no session :func:`span` costs one check and records nothing.  Inside
one, each span is emitted as a ``jax.profiler.TraceAnnotation`` (the
profiler's host plane, on the clock of the device's ops) and kept in a
bounded buffer that :func:`snapshot` reduces.  Every span carries the id
of the operation it belongs to: :func:`operation` opens a root span (one
restore call, one ring step) and a new id; :func:`current_op` and
:func:`joined` carry the id into background jobs, so a worker's spans
belong to the operation that caused them.

Span names start with ``znn.``; spans go per phase, leaf or launch window,
never per chunk, and never inside pool workers.  Nothing here touches the
data path: spans and counters only observe calls that run either way.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any, Dict, Optional

__all__ = [
    "COUNTERS",
    "span",
    "operation",
    "current_op",
    "joined",
    "fetch",
    "count",
    "count_payload_upload",
    "counters",
    "reset_counters",
    "snapshot",
    "records",
    "reset",
]

COUNTERS = (
    "payload_uploads",
    "payload_bytes",
    "d2h_fetches",
    "d2h_bytes",
    "launches.huffdecode",
    "launches.bitpack",
    "launches.plane_producer",
    "launches.plane_consumer",
    "feed_dispatches",
    "assemblies",
    "assembled_chunks",
    "compiles",
    "compile_s",
    "ring_raw_bytes.dense",
    "ring_raw_bytes.moe",
    "ring_raw_bytes.ssm",
)

# Span records kept per profiler session; past the bound the oldest
# records drop, while the per-name totals of snapshot() keep counting.
MAX_RECORDS = 1 << 16

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)

_lock = threading.Lock()
_counters: Dict[str, float] = {k: 0 for k in COUNTERS}
_counters["compile_s"] = 0.0
_tls = threading.local()
_op_ids = itertools.count(1)


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------

def count(key: str, n: float = 1) -> None:
    """Add ``n`` to counter ``key``."""
    with _lock:
        _counters[key] += n


def count_payload_upload(nbytes: int) -> None:
    """One payload-sized host→device upload of ``nbytes``."""
    with _lock:
        _counters["payload_uploads"] += 1
        _counters["payload_bytes"] += int(nbytes)


def counters() -> Dict[str, float]:
    """The counters as they stand."""
    _started()
    with _lock:
        return dict(_counters)


def reset_counters(keys=COUNTERS) -> None:
    """Zero ``keys`` (every counter by default)."""
    with _lock:
        for k in keys:
            _counters[k] = 0.0 if k == "compile_s" else 0


def _on_compile(event: str, duration: float, **_: Any) -> None:
    if event in _COMPILE_EVENTS:
        with _lock:
            _counters["compile_s"] += duration
            if event == _COMPILE_EVENTS[1]:
                _counters["compiles"] += 1


# ---------------------------------------------------------------------------
# session state
# ---------------------------------------------------------------------------

class _Session:
    """What one profiler session recorded."""

    def __init__(self, token: Any):
        self.token = token
        self.records: collections.deque = collections.deque(maxlen=MAX_RECORDS)
        # name -> role -> [count, total_s, self_s]
        self.totals: Dict[str, Dict[str, list]] = {}
        self.first_counters: Optional[Dict[str, float]] = None
        self.last_counters: Optional[Dict[str, float]] = None


_session = _Session(None)


def _bootstrap() -> bool:
    """First use: bind the profiler's on/off check and register the
    compile listener; later calls go straight to the check."""
    global _enabled, _annotation
    import jax
    from jax._src.lib import _profiler

    with _lock:
        if _enabled is _bootstrap:
            jax.monitoring.register_event_duration_secs_listener(_on_compile)
            _annotation = jax.profiler.TraceAnnotation
            _enabled = _profiler.TraceMe.is_enabled
    return _enabled()


_enabled = _bootstrap
_annotation: Any = None


def _started() -> None:
    if _enabled is _bootstrap:
        _bootstrap()


def _session_token() -> Any:
    """The active ``start_trace`` session object (``None`` for a session
    captured through a profiler server, so such sessions share a buffer
    until the next ``start_trace`` or :func:`reset`)."""
    from jax._src import profiler as _jprof

    state = getattr(_jprof, "_profile_state", None)
    return getattr(state, "profile_session", None)


def _live_session() -> _Session:
    """The session spans record into now: a fresh one when the profiler
    session changed."""
    global _session
    token = _session_token()
    with _lock:
        if token is not _session.token:
            _session = _Session(token)
        return _session


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

class _Null:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL = _Null()


class _Span:
    __slots__ = ("name", "root", "ann", "t0", "child_s", "parent", "sess",
                 "prev_op")

    def __init__(self, name: str, root: bool):
        self.name = name
        self.root = root

    def __enter__(self):
        sess = _live_session()
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        self.sess = sess
        self.parent = stack[-1] if stack else None
        self.child_s = 0.0
        self.prev_op = getattr(_tls, "op", None)
        if self.root:
            _tls.op = next(_op_ids)
        stack.append(self)
        self.ann = _annotation(self.name)
        self.ann.__enter__()
        t0 = time.perf_counter()
        if sess.first_counters is None:
            with _lock:
                if sess.first_counters is None:
                    sess.first_counters = dict(_counters)
        self.t0 = t0
        return None

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.ann.__exit__(*exc)
        stack = _tls.stack
        stack.pop()
        dur = t1 - self.t0
        if self.parent is not None:
            self.parent.child_s += dur
        op = getattr(_tls, "op", None)
        role = getattr(_tls, "role", "caller")
        if self.root:
            _tls.op = self.prev_op
        sess = self.sess
        rec = (self.name, self.t0, t1, threading.get_ident(), role,
               None if self.parent is None else self.parent.name, op)
        with _lock:
            sess.records.append(rec)
            by_role = sess.totals.setdefault(self.name, {})
            acc = by_role.get(role)
            if acc is None:
                acc = by_role[role] = [0, 0.0, 0.0]
            acc[0] += 1
            acc[1] += dur
            acc[2] += dur - self.child_s
            sess.last_counters = dict(_counters)
        return False


def span(name: str):
    """Context manager timing one phase of the calling thread."""
    if _enabled():
        return _Span(name, False)
    return _NULL


def operation(name: str):
    """Root span of one operation (a restore call, a ring step): its spans,
    and those of jobs it hands to :func:`joined`, share a new id."""
    if _enabled():
        return _Span(name, True)
    return _NULL


def current_op() -> Optional[int]:
    """The id of the operation open on this thread (``None`` off a span)."""
    return getattr(_tls, "op", None)


class joined:
    """Run a background job's spans under operation ``op`` as a worker."""

    __slots__ = ("op", "prev")

    def __init__(self, op: Optional[int]):
        self.op = op

    def __enter__(self):
        self.prev = (getattr(_tls, "op", None), getattr(_tls, "role", "caller"))
        _tls.op = self.op
        _tls.role = "worker"
        return None

    def __exit__(self, *exc):
        _tls.op, _tls.role = self.prev
        return False


def fetch(x: Any) -> Any:
    """``jax.device_get(x)``, as one blocking device→host fetch.

    Inside a ``znn.codec.fetch`` span and counted in ``d2h_fetches`` /
    ``d2h_bytes`` when ``x`` holds device arrays; host arrays pass through
    uncounted.
    """
    import jax

    nbytes = 0
    on_device = False
    for leaf in jax.tree_util.tree_leaves(x):
        if isinstance(leaf, jax.Array):
            on_device = True
            nbytes += int(leaf.nbytes)
    if not on_device:
        return jax.device_get(x)
    with span("znn.codec.fetch"):
        out = jax.device_get(x)
    with _lock:
        _counters["d2h_fetches"] += 1
        _counters["d2h_bytes"] += nbytes
    return out


# ---------------------------------------------------------------------------
# reading
# ---------------------------------------------------------------------------

def snapshot() -> Dict[str, Any]:
    """What the latest profiler session recorded.

    ``spans``: per span name, per thread role (``"caller"``, or
    ``"worker"`` for jobs run under :func:`joined`), ``count``,
    ``total_s`` and ``self_s`` (time not covered by child spans on the same
    thread).  ``counters``: as they stand.  ``traced``: each counter's
    change from the start of the session's first span to the end of its
    last, so the traced window and nothing else.  ``records``: how many
    spans the buffer holds.  ``spans`` and ``traced`` are empty when no
    span was recorded.
    """
    _started()
    with _lock:
        sess = _session
        spans = {
            name: {
                role: {"count": a[0], "total_s": a[1], "self_s": a[2]}
                for role, a in by_role.items()
            }
            for name, by_role in sess.totals.items()
        }
        now = dict(_counters)
        first, last = sess.first_counters, sess.last_counters
        n = len(sess.records)
    traced = (
        {k: last[k] - first[k] for k in COUNTERS}
        if first is not None and last is not None
        else {}
    )
    return {"spans": spans, "counters": now, "traced": traced, "records": n}


def records() -> list:
    """The latest session's span records, oldest first:
    ``(name, start_s, end_s, thread, role, parent, op)``."""
    with _lock:
        return list(_session.records)


def reset() -> None:
    """Clear the span buffer and zero every counter."""
    global _session
    with _lock:
        _session = _Session(None)
    reset_counters()
