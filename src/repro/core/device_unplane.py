"""Device plane-consumer backend for the decompression engine.

Mirror of :mod:`.device_plane`.  The host decompression path rebuilds each
byte-group plane from the entropy stage, then runs two more host passes —
the per-plane byte scatter + inverse rotate (:func:`repro.core.bitlayout.
from_planes`) and, for §4.2 delta streams, the XOR against the base tensor.
For device-bound restores that means the planed uint8 buffers are
materialized, scattered and rotated on the host before the result is
uploaded anyway.

This module instead uploads the entropy-decoded planes **once** and runs
un-byte-group, inverse rotate and inverse XOR-delta in one fused Pallas
dispatch (:func:`repro.kernels.fused_unplane.plane_consumer`), followed by
a single device→host transfer of the reconstructed bytes.  Decoded bytes
are **bit-identical** to the host path for every thread count — the
backend knob changes wall-clock only.

Backend selection (the ``backend`` knob on every decompression entry
point, defaulting to :class:`repro.core.zipnn.ZipNNConfig` ``plane_backend``):

* ``"host"``   — always the numpy path (default).
* ``"device"`` — the fused Pallas path whenever the layout is supported;
  silent host fallback otherwise, so the knob is always safe to set.
* ``"auto"``   — device only when it can pay for the plane upload: a
  non-CPU accelerator is attached, or the delta base is already
  accelerator-resident.  (Encode-side ``auto`` keys off the *leaf*
  residence; decode planes always start host-side after the entropy
  stage, so residence of the hardware/base is the signal here.)

Support envelope: 2- and 4-byte rotated layouts (bf16 / fp16 / fp32).  The
decode side has no histogram stage, so — unlike the producer — there is no
chunk-size constraint.  Everything else falls back to the host path.

Batched multi-leaf dispatch: :func:`consume_planes_batched` concatenates
many same-layout leaves' planes into one padded ``(M, 128)`` grid per
plane index, launches once, and slices per-leaf bytes out of the single
transferred element buffer — per-leaf kernel-launch latency never
dominates real model trees.  Decode needs no chunk alignment between
leaves, only the total row-block pad; zero pad bytes reconstruct to zero
elements and are sliced off.

Zero-bounce composition with the device entropy stage: plane arrays may
already be **device-resident** ``jax.Array``\\ s (the output of
:func:`repro.core.device_entropy.decode_planes` with
``device_resident=True``) — they are concatenated and padded on device
instead of re-uploaded.  :func:`consume_payloads` is the compressed-payload
entry point that chains the two: kernel-decoded symbols feed straight into
the fused un-byte-group/rotate/XOR dispatch, so the only data-sized
host→device transfer is the compressed payload itself.  With
``device_resident=True`` the *output* also stays on device (per-leaf flat
uint16/uint32 element arrays, no ``device_get``), which is what
``CheckpointManager.shard_restore`` consumes for restores that never
round-trip through host memory.
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence

import numpy as np

from . import bitlayout, tracing
from .device_plane import (
    MAX_BATCH_BYTES,
    _dev_elems,
    _on_accelerator,
    is_available,
)

__all__ = [
    "BACKENDS",
    "is_available",
    "supports",
    "resolve",
    "consume_planes",
    "consume_planes_batched",
    "consume_payloads",
    "resident_dispatches",
]

BACKENDS = ("host", "device", "auto")


def supports(layout: bitlayout.BitLayout) -> bool:
    """Can the fused device path reconstruct bit-identical bytes?

    Requires a rotated 2- or 4-byte layout (the un-group kernels always
    inverse-rotate); no chunk constraint — decode has no histogram stage.
    """
    if not layout.rotate or layout.itemsize not in (2, 4):
        return False
    return is_available()


def _accelerator_attached() -> bool:
    if not is_available():
        return False
    import jax

    return jax.default_backend() != "cpu"


def resolve(
    requested: Optional[str],
    layout: bitlayout.BitLayout,
    base: Any = None,
) -> str:
    """Collapse a decode-backend request to the concrete path."""
    if requested is None or requested == "host":
        return "host"
    if requested == "device":
        return "device" if supports(layout) else "host"
    if requested == "auto":
        return (
            "device"
            if supports(layout)
            and (_accelerator_attached() or _on_accelerator(base))
            else "host"
        )
    raise ValueError(
        f"unknown plane backend {requested!r}; expected one of {BACKENDS}"
    )


def consume_planes(
    planes: Sequence[Any],
    layout: bitlayout.BitLayout,
    base: Any = None,
    device_resident: bool = False,
) -> Any:
    """Single-leaf convenience wrapper around :func:`consume_planes_batched`.

    ``base`` enables the fused §4.2 inverse XOR-delta path (the
    reconstructed delta is XORed with ``base`` on device, so the delta
    stream never materializes host-side).  Returns the flat uint8 byte
    view — the exact inverse of :func:`repro.core.bitlayout.to_planes` —
    or, with ``device_resident=True``, the flat device-resident
    uint16/uint32 element array (no device→host transfer).
    """
    return consume_planes_batched(
        [planes], layout, bases=None if base is None else [base],
        device_resident=device_resident,
    )[0]


def consume_payloads(
    entries_all: Sequence[Sequence[Any]],
    payloads_all: Sequence[Sequence[bytes]],
    tables_all: Sequence[Optional[bytes]],
    params: Any,
    layout: bitlayout.BitLayout,
    base: Any = None,
    pool=None,
    device_resident: bool = False,
) -> Any:
    """Compressed-payload entry point: decode + consume without a bounce.

    The parsed container's ``HUFF`` payloads decode on device
    (:func:`repro.core.device_entropy.decode_planes`,
    ``device_resident=True``) and the kernel-decoded symbol planes feed
    straight into the fused un-byte-group/rotate/XOR dispatch — the
    compressed payload is the only data-sized host→device transfer
    (STORE/expansion-guard chunks splice in via one side upload).  Returns
    the leaf's flat uint8 bytes, or the device-resident element array with
    ``device_resident=True``.
    """
    from . import device_entropy

    planes = device_entropy.decode_planes(
        entries_all, payloads_all, tables_all, params,
        pool=pool, device_resident=True,
    )
    return consume_planes(
        planes, layout, base=base, device_resident=device_resident
    )


def consume_planes_batched(
    planes_list: Sequence[Sequence[Any]],
    layout: bitlayout.BitLayout,
    bases: Optional[Sequence[Any]] = None,
    device_resident: bool = False,
) -> List[Any]:
    """Pack many leaves' planes into one fused dispatch; return per-leaf bytes.

    All leaves must share ``layout``.  Each plane index is concatenated
    across leaves, the total is zero-padded to the kernel's row-block
    alignment, and a single ``plane_consumer`` launch + a single
    ``jax.device_get`` reconstruct every leaf's raw bytes.  Oversized
    batches split at :data:`~repro.core.device_plane.MAX_BATCH_BYTES`.

    Plane arrays may be host numpy or device-resident ``jax.Array``\\ s
    (the device entropy stage's output) — device planes concatenate on
    device instead of re-uploading.  With ``device_resident=True`` the
    per-leaf results stay on device as flat uint16/uint32 element arrays
    and no ``device_get`` happens at all.
    """
    if bases is not None and len(bases) != len(planes_list):
        raise ValueError("bases must pair 1:1 with planes_list")
    if not planes_list:
        return []
    if not supports(layout):
        raise ValueError(
            f"device plane-consumer backend does not support layout "
            f"{layout.name!r}"
        )
    for planes in planes_list:
        if len(planes) != layout.n_planes:
            raise ValueError(
                f"expected {layout.n_planes} planes, got {len(planes)}"
            )
    sizes = [int(planes[0].size) for planes in planes_list]
    # Split oversized batches up front; recursion depth is 1.
    if len(planes_list) > 1 and sum(sizes) * layout.itemsize > MAX_BATCH_BYTES:
        out: List[np.ndarray] = []
        start, acc = 0, 0
        for i, s in enumerate(sizes):
            nb = s * layout.itemsize
            if acc and acc + nb > MAX_BATCH_BYTES:
                out.extend(
                    consume_planes_batched(
                        planes_list[start:i], layout,
                        None if bases is None else bases[start:i],
                        device_resident=device_resident,
                    )
                )
                start, acc = i, 0
            acc += nb
        out.extend(
            consume_planes_batched(
                planes_list[start:], layout,
                None if bases is None else bases[start:],
                device_resident=device_resident,
            )
        )
        return out

    with tracing.span("znn.codec.unplane"):
        return _consume(planes_list, layout, sizes, bases, device_resident)


def _tail(total: int, layout: bitlayout.BitLayout) -> int:
    """Zero elements that pad ``total`` to the kernel's row-block alignment."""
    from repro.kernels import fused_unplane

    align = (
        fused_unplane.ALIGN_ELEMS_U16
        if layout.itemsize == 2
        else fused_unplane.ALIGN_ELEMS_U32
    )
    return -total % align


def resident_dispatches(size: int, layout: bitlayout.BitLayout) -> int:
    """Eager device ops of :func:`consume_planes` on one leaf of ``size``
    elements whose planes are device arrays, with ``device_resident=True``:
    per plane a reshape, plus a pad and a concatenate where the leaf needs
    a tail; the launch; the flattening reshape; the trim of the tail."""
    tail = _tail(size, layout) > 0
    return layout.n_planes * (1 + 2 * tail) + 2 + tail


def _consume(
    planes_list: Sequence[Sequence[Any]],
    layout: bitlayout.BitLayout,
    sizes: List[int],
    bases: Optional[Sequence[Any]],
    device_resident: bool,
) -> List[Any]:
    import jax.numpy as jnp

    from repro.kernels import fused_unplane, ops

    total = sum(sizes)
    if total == 0:                               # every leaf empty: no dispatch
        return [np.empty(0, np.uint8) for _ in sizes]
    tail = _tail(total, layout)

    # One upload per plane index: the concatenation of every leaf's plane.
    # Device-resident planes (the fused entropy decoder's output) stay on
    # device — concatenation/padding happen there, never a re-upload.
    dev_planes = []
    for p in range(layout.n_planes):
        parts = [planes[p] for planes in planes_list]
        if any(not isinstance(x, np.ndarray) for x in parts):
            jparts = [
                x if not isinstance(x, np.ndarray)
                else jnp.asarray(np.ascontiguousarray(x))
                for x in parts
            ]
            if tail:
                jparts.append(jnp.zeros(tail, jnp.uint8))
            cat = jparts[0] if len(jparts) == 1 else jnp.concatenate(jparts)
        else:
            nparts = [np.ascontiguousarray(x) for x in parts]
            if tail:
                nparts.append(np.zeros(tail, np.uint8))
            cat = nparts[0] if len(nparts) == 1 else np.concatenate(nparts)
        dev_planes.append(
            jnp.asarray(cat).reshape(-1, fused_unplane.LANES)
        )

    base2 = None
    if bases is not None and any(b is not None for b in bases):
        bparts = []
        for b, s in zip(bases, sizes):
            if s == 0:
                continue
            e = (
                jnp.zeros((s,), dtype=jnp.dtype(layout.uint_dtype))
                if b is None                    # XOR identity
                else _dev_elems(b, layout)
            )
            if e.shape[0] != s:
                raise ValueError("delta base must match the leaf's element count")
            bparts.append(e)
        if tail:
            bparts.append(
                jnp.zeros((tail,), dtype=jnp.dtype(layout.uint_dtype))
            )
        base2 = jnp.concatenate(bparts).reshape(-1, fused_unplane.LANES)

    x2 = fused_unplane.plane_consumer(
        tuple(dev_planes), base2, itemsize=layout.itemsize,
        interpret=ops.interpret_mode(),
    )
    tracing.count("launches.plane_consumer")
    if device_resident:
        # Zero-bounce: per-leaf element slices stay on device for the
        # caller (bitcast to the real dtype / device_put re-shard there).
        elems_dev = x2.reshape(-1)
        out = []
        off = 0
        for s in sizes:
            out.append(elems_dev[off : off + s])
            off += s
        return out
    # The one device→host transfer: reconstructed elements for the batch.
    elems = np.asarray(tracing.fetch(x2)).reshape(-1)

    out = []
    off = 0
    for s in sizes:
        if s == 0:
            out.append(np.empty(0, np.uint8))
            continue
        out.append(np.ascontiguousarray(elems[off : off + s]).view(np.uint8))
        off += s
    return out
