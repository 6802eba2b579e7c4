"""Device plane-producer backend for the compression engine.

The host compression path runs three pre-entropy passes in numpy — rotate +
byte-group split (:mod:`.bitlayout`), optional XOR delta, and the per-chunk
``np.bincount`` probe — before the (plane, chunk) entropy work items start.
For device-resident pytrees that means a device→host transfer of the *raw*
tensor followed by three more host passes, with the GIL-bound probe
serializing ~15 % of compress time across engine workers.

This module instead runs all three stages **on device in one fused
dispatch** (:func:`repro.kernels.fused_plane.plane_producer`) and performs a
single device→host transfer of the already-planed uint8 buffers plus the
per-chunk probe histograms.  The planes and :class:`~repro.core.codec.ProbeStats`
feed straight into :func:`repro.core.codec.compress_plane`; pass 1 of the
codec then never histograms anything.  Output blobs are **byte-identical**
to the host path for every thread count — the backend knob changes
wall-clock only.

Backend selection (the ``backend`` knob on :class:`repro.core.zipnn.ZipNNConfig`
(``plane_backend``) and on ``compress_array`` / ``compress_pytree`` /
``delta_compress``):

* ``"host"``   — always the numpy path (default).
* ``"device"`` — the fused Pallas path whenever the (layout, chunk-size)
  combination is supported; silent host fallback otherwise, so the knob is
  always safe to set.
* ``"auto"``   — device only for leaves that are already accelerator-
  resident ``jax.Array``\\ s (no upload is ever *added*); host otherwise.

Support envelope: 2- and 4-byte rotated layouts (bf16 / fp16 / fp32) with a
per-plane chunk size that is a whole number of histogram blocks
(``chunk_bytes % 16384 == 0`` — the paper-default 256 KiB parameter chunks
qualify).  Everything else falls back to the host path.

Batched multi-leaf dispatch: real pytrees are dominated by *small* tensors
(biases, norms, embeddings rows) whose per-leaf kernel launch + transfer
latency would swamp the fused win.  :func:`produce_planes_batched` packs
many same-dtype leaves into one padded element grid, launches once, and
slices per-leaf planes/histograms out of the single transferred buffer.
Leaves are padded to whole codec chunks so chunk boundaries never straddle
two leaves; zero padding is invariant under rotate/XOR, so the only
correction is subtracting the pad count from bin 0 of each leaf's final
chunk histogram.
"""

from __future__ import annotations

import math
import os
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from . import bitlayout, codec, tracing

__all__ = [
    "BACKENDS",
    "DEFAULT_BATCH_BYTES",
    "PlanedArray",
    "is_available",
    "supports",
    "resolve",
    "produce_planes",
    "produce_planes_batched",
]

BACKENDS = ("host", "device", "auto")

DEFAULT_BATCH_BYTES = 256 << 20


def _batch_bytes_from_env(default: int = DEFAULT_BATCH_BYTES) -> int:
    """Resolve the launch-window cap, honoring ``ZIPNN_MAX_BATCH_BYTES``.

    Real-TPU tuning runs sweep the window without editing source.  The env
    var is read once at import and must be a positive integer (plain or
    ``0x``-prefixed).  Window size is exempt from the determinism rules by
    construction: launches split on per-chunk boundaries, and payload bytes
    are per-chunk, so the cap changes wall-clock and peak memory only —
    never bytes (the same reason the ``threads`` knob is byte-safe).
    """
    raw = os.environ.get("ZIPNN_MAX_BATCH_BYTES")
    if raw is None:
        return default
    try:
        value = int(raw, 0)
    except ValueError:
        raise ValueError(
            f"ZIPNN_MAX_BATCH_BYTES={raw!r} is not an integer byte count"
        ) from None
    if value <= 0:
        raise ValueError(
            f"ZIPNN_MAX_BATCH_BYTES must be positive, got {value}"
        )
    return value


# One batched dispatch is capped so the packed element grid (+ its planes)
# stays comfortably in device memory; larger groups split into several
# launches.  Env-tunable — see _batch_bytes_from_env.
MAX_BATCH_BYTES = _batch_bytes_from_env()


class PlanedArray(np.ndarray):
    """Host plane bytes that also carry their device-resident twin.

    The fused plane producer computes every plane ON DEVICE and downloads a
    host copy for the codec's plan/finalize passes.  Historically the device
    buffer was then dropped, and the entropy stage re-uploaded HUFF-chunk
    symbols it had just downloaded.  ``PlanedArray`` keeps the device copy
    reachable: ``dev_chunks`` is the same plane as a ``(n_chunks,
    chunk_bytes)`` device array (zero-padded final chunk — the exact symbol
    rows the bit-pack kernel consumes), so ``device_entropy._pack_jobs``
    gathers symbols on device instead of re-uploading them.

    Any slice / view / ufunc result drops the device reference
    (``__array_finalize__``): the pairing is only valid for the whole plane.
    """

    def __array_finalize__(self, obj) -> None:
        self.dev_chunks = None


def is_available() -> bool:
    """True when jax (and therefore the Pallas kernels) can be imported."""
    try:
        import jax  # noqa: F401

        return True
    except Exception:  # pragma: no cover - jax is baked into the image
        return False


def supports(layout: bitlayout.BitLayout, params: codec.CodecParams) -> bool:
    """Can the fused device path produce byte-identical planes/probes?

    Requires a rotated 2- or 4-byte layout (the byte-group kernels always
    rotate) and codec chunks that are whole histogram blocks.
    """
    if not layout.rotate or layout.itemsize not in (2, 4):
        return False
    if not is_available():
        return False
    from repro.kernels import fused_plane

    return params.chunk_bytes % fused_plane.CHUNK_ALIGN_BYTES == 0


def _on_accelerator(leaf: Any) -> bool:
    """True when ``leaf`` is a jax.Array living on a non-CPU device."""
    if not is_available():
        return False
    import jax

    if not isinstance(leaf, jax.Array):
        return False
    return any(d.platform != "cpu" for d in leaf.devices())


def resolve(
    requested: Optional[str],
    layout: bitlayout.BitLayout,
    params: codec.CodecParams,
    leaf: Any = None,
) -> str:
    """Collapse a backend request to the concrete path: 'host' or 'device'."""
    if requested is None or requested == "host":
        return "host"
    if requested == "device":
        return "device" if supports(layout, params) else "host"
    if requested == "auto":
        return (
            "device"
            if supports(layout, params) and _on_accelerator(leaf)
            else "host"
        )
    raise ValueError(
        f"unknown plane backend {requested!r}; expected one of {BACKENDS}"
    )


# ---------------------------------------------------------------------------
# element marshalling
# ---------------------------------------------------------------------------


def _dev_elems(buf: Any, layout: bitlayout.BitLayout):
    """``buf`` → flat device array of the layout's uint element dtype.

    Accepts host uint8 byte views, host arrays of a same-width dtype, and
    jax.Arrays (bitcast on device — device-resident leaves are never pulled
    to the host as raw values).
    """
    import jax
    import jax.numpy as jnp

    target = jnp.uint16 if layout.itemsize == 2 else jnp.uint32
    if isinstance(buf, np.ndarray):
        if buf.dtype == np.uint8:
            if buf.size % layout.itemsize:
                raise ValueError(
                    f"byte buffer of {buf.size} is not a multiple of "
                    f"itemsize {layout.itemsize}"
                )
            return jnp.asarray(
                np.ascontiguousarray(buf).view(layout.uint_dtype)
            )
        if buf.dtype.itemsize != layout.itemsize:
            raise TypeError(
                f"dtype {buf.dtype} does not match layout itemsize "
                f"{layout.itemsize}"
            )
        return jnp.asarray(
            np.ascontiguousarray(buf).reshape(-1).view(layout.uint_dtype)
        )
    x = buf.reshape(-1)
    if x.dtype.itemsize != layout.itemsize:
        raise TypeError(
            f"dtype {x.dtype} does not match layout itemsize {layout.itemsize}"
        )
    if x.dtype == target:
        return x
    return jax.lax.bitcast_convert_type(x, target)


# ---------------------------------------------------------------------------
# fused production
# ---------------------------------------------------------------------------

PlanesAndProbes = Tuple[List[np.ndarray], List[Optional[codec.ProbeStats]]]


def produce_planes(
    buf: Any,
    layout: bitlayout.BitLayout,
    params: codec.CodecParams,
    base: Any = None,
) -> PlanesAndProbes:
    """Single-leaf convenience wrapper around :func:`produce_planes_batched`.

    ``base`` enables the fused §4.2 XOR-delta path (``buf ^ base`` is planed
    instead of ``buf``; rotation is a bit permutation, hence XOR-compatible).
    """
    return produce_planes_batched(
        [buf], layout, params, bases=None if base is None else [base]
    )[0]


def produce_planes_batched(
    bufs: Sequence[Any],
    layout: bitlayout.BitLayout,
    params: codec.CodecParams,
    bases: Optional[Sequence[Any]] = None,
) -> List[PlanesAndProbes]:
    """Pack ``bufs`` into one fused dispatch; return per-leaf (planes, probes).

    All leaves must share ``layout``.  Each leaf is zero-padded to a whole
    number of codec chunks, the concatenation is zero-padded to the kernels'
    row-block alignment, and a single ``plane_producer`` launch + a single
    ``jax.device_get`` produce every leaf's uint8 planes and exact per-chunk
    probe histograms.  Oversized batches split at :data:`MAX_BATCH_BYTES`.
    """
    if bases is not None and len(bases) != len(bufs):
        raise ValueError("bases must pair 1:1 with bufs")
    if not bufs:
        return []
    if not supports(layout, params):
        raise ValueError(
            f"device plane backend does not support layout {layout.name!r} "
            f"with chunk_bytes={params.chunk_bytes}"
        )
    # Split oversized batches up front; recursion depth is 1.
    sizes_bytes = [_leaf_nbytes(b, layout) for b in bufs]
    if len(bufs) > 1 and sum(sizes_bytes) > MAX_BATCH_BYTES:
        out: List[PlanesAndProbes] = []
        start, acc = 0, 0
        for i, nb in enumerate(sizes_bytes):
            if acc and acc + nb > MAX_BATCH_BYTES:
                out.extend(
                    produce_planes_batched(
                        bufs[start:i], layout, params,
                        None if bases is None else bases[start:i],
                    )
                )
                start, acc = i, 0
            acc += nb
        out.extend(
            produce_planes_batched(
                bufs[start:], layout, params,
                None if bases is None else bases[start:],
            )
        )
        return out

    import jax.numpy as jnp

    from repro.kernels import fused_plane, ops

    cb = params.chunk_bytes                      # elements per (plane) chunk
    align = (
        fused_plane.ALIGN_ELEMS_U16
        if layout.itemsize == 2
        else fused_plane.ALIGN_ELEMS_U32
    )
    total_align = cb * align // math.gcd(cb, align)

    us = [_dev_elems(b, layout) for b in bufs]
    bs = (
        [None if b is None else _dev_elems(b, layout) for b in bases]
        if bases is not None
        else [None] * len(us)
    )
    use_delta = any(b is not None for b in bs)
    sizes = [int(u.shape[0]) for u in us]
    pads = [-s % cb for s in sizes]
    for u, b in zip(us, bs):
        if b is not None and b.shape != u.shape:
            raise ValueError("delta base must match the leaf's element count")

    parts, bparts = [], []
    for u, b, pad in zip(us, bs, pads):
        parts.append(u if pad == 0 else jnp.pad(u, (0, pad)))
        if use_delta:
            if b is None:
                b = jnp.zeros_like(u)            # XOR identity
            bparts.append(b if pad == 0 else jnp.pad(b, (0, pad)))
    total = sum(s + p for s, p in zip(sizes, pads))
    if total == 0:                               # every leaf empty: no dispatch
        return [
            (
                [np.empty(0, np.uint8) for _ in range(layout.n_planes)],
                [None] * layout.n_planes,
            )
            for _ in sizes
        ]
    tail = -total % total_align
    if tail:
        parts.append(jnp.zeros((tail,), dtype=us[0].dtype))
        if use_delta:
            bparts.append(jnp.zeros((tail,), dtype=us[0].dtype))
    x2 = jnp.concatenate(parts).reshape(-1, fused_plane.LANES)
    base2 = (
        jnp.concatenate(bparts).reshape(-1, fused_plane.LANES)
        if use_delta
        else None
    )

    with tracing.span("znn.codec.launch"):
        planes2d, hists_dev = fused_plane.plane_producer(
            x2, base2, itemsize=layout.itemsize, chunk_elems=cb,
            interpret=ops.interpret_mode(),
        )
    tracing.count("launches.plane_producer")
    # The one device→host transfer of the whole batch: planed uint8 buffers
    # + probe histograms together.
    planes_host, hists_host = tracing.fetch((planes2d, hists_dev))
    flat = [np.asarray(p).reshape(-1) for p in planes_host]
    flat_dev = [p.reshape(-1) for p in planes2d]   # stays resident on device
    hists = np.asarray(hists_host).astype(np.int64)  # (chunks, n_planes, 256)

    out = []
    off = choff = 0
    for s, pad in zip(sizes, pads):
        if s == 0:
            out.append(
                (
                    [np.empty(0, np.uint8) for _ in range(layout.n_planes)],
                    [None] * layout.n_planes,
                )
            )
            continue
        n_chunks = (s + pad) // cb
        # Host copy drives plan/probe/finalize; the device twin rides along
        # chunk-rowed so the entropy stage never re-uploads HUFF symbols.
        leaf_planes: List[np.ndarray] = []
        for f, fd in zip(flat, flat_dev):
            host = f[off : off + s].view(PlanedArray)
            host.dev_chunks = fd[off : off + s + pad].reshape(n_chunks, cb)
            leaf_planes.append(host)
        leaf_h = hists[choff : choff + n_chunks].copy()
        if pad:
            leaf_h[-1, :, 0] -= pad              # padding is all-zero bytes
        probes: List[Optional[codec.ProbeStats]] = [
            codec.ProbeStats(
                chunk_hists=leaf_h[:, p, :],
                table_hist=codec.table_probe_hist(leaf_planes[p]),
            )
            for p in range(layout.n_planes)
        ]
        out.append((leaf_planes, probes))
        off += s + pad
        choff += n_chunks
    return out


def _leaf_nbytes(buf: Any, layout: bitlayout.BitLayout) -> int:
    if isinstance(buf, np.ndarray) and buf.dtype == np.uint8:
        return buf.size
    size = 1
    for d in np.shape(buf):
        size *= int(d)
    return size * layout.itemsize
