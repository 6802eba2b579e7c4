"""Fused device plane consumer: the decompression back half in ONE dispatch.

Mirror of :mod:`.fused_plane`.  After the entropy stage rebuilds the uint8
byte-group planes, the host decompression path still runs two more numpy
passes — the per-plane byte scatter + inverse rotate (``from_planes``) and,
for §4.2 delta streams, the XOR with the base tensor.  Both serialize on
the GIL and round-trip the planed bytes through host memory.

This module instead runs un-byte-group, inverse rotate-left-1 and the
optional inverse XOR-delta as **one Pallas kernel per dispatch**: uint8
planes in, reconstructed uint16/uint32 elements out, with the base tensor
(when delta-decoding) read directly at its device residence.  The caller
uploads the entropy-decoded planes once, launches once, and does a single
device→host transfer of the reconstructed elements (or leaves them on
device for a shard restore).

Alignment contract (enforced by ``core.device_unplane``): every plane is a
flat uint8 array zero-padded and reshaped to ``(M, 128)`` with ``M`` a
multiple of the kernel's row block.  Zero plane bytes reconstruct to zero
elements (``rotr1(0) == 0``) and XOR against a zero-padded base leaves the
pad region irrelevant — pad elements are sliced off host-side.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
# Row blocks sized like the forward kernels (in+out VMEM blocks ≲ 384 KiB
# with the delta base resident): u8 plane blocks are small, the element
# output block dominates.
BF16_ROWS = 512            # 2 × u8 64 KiB + u16 base/out 128 KiB each
FP32_ROWS = 256            # 4 × u8 32 KiB + u32 base/out 128 KiB each

# Row alignment (in elements) the padded planes must satisfy.
ALIGN_ELEMS_U16 = BF16_ROWS * LANES
ALIGN_ELEMS_U32 = FP32_ROWS * LANES


def _bf16_unplane_kernel(exp_ref, frac_ref, x_ref):
    rot = (exp_ref[...].astype(jnp.int32) << 8) | frac_ref[...].astype(jnp.int32)
    x = ((rot >> 1) | ((rot & 1) << 15)) & 0xFFFF
    x_ref[...] = x.astype(jnp.uint16)


def _bf16_unplane_delta_kernel(exp_ref, frac_ref, base_ref, x_ref):
    rot = (exp_ref[...].astype(jnp.int32) << 8) | frac_ref[...].astype(jnp.int32)
    x = ((rot >> 1) | ((rot & 1) << 15)) & 0xFFFF
    b = base_ref[...].astype(jnp.int32) & 0xFFFF
    x_ref[...] = (x ^ b).astype(jnp.uint16)


def _fp32_rot_inv(p0_ref, p1_ref, p2_ref, p3_ref):
    rot = (
        (p0_ref[...].astype(jnp.uint32) << 24)
        | (p1_ref[...].astype(jnp.uint32) << 16)
        | (p2_ref[...].astype(jnp.uint32) << 8)
        | p3_ref[...].astype(jnp.uint32)
    )
    return (rot >> 1) | (rot << 31)


def _fp32_unplane_kernel(p0_ref, p1_ref, p2_ref, p3_ref, x_ref):
    x_ref[...] = _fp32_rot_inv(p0_ref, p1_ref, p2_ref, p3_ref)


def _fp32_unplane_delta_kernel(p0_ref, p1_ref, p2_ref, p3_ref, base_ref, x_ref):
    x_ref[...] = _fp32_rot_inv(p0_ref, p1_ref, p2_ref, p3_ref) ^ base_ref[
        ...
    ].astype(jnp.uint32)


def _spec(rows):
    return pl.BlockSpec((rows, LANES), lambda i: (i, 0))


@functools.partial(jax.jit, static_argnames=("itemsize", "interpret"))
def plane_consumer(
    planes: Sequence[jax.Array],
    base: Optional[jax.Array] = None,
    *,
    itemsize: int,
    interpret: bool = True,
) -> jax.Array:
    """un-byte-group → inverse rotate → (optional XOR with ``base``).

    Args:
      planes: ``itemsize`` uint8 ``(M, 128)`` byte-group planes, plane 0 the
        exponent byte (most significant after the forward rotation).
      base: uint16/uint32 ``(M, 128)`` base elements for the §4.2
        delta-decode path, or None.
      itemsize: 2 or 4 — selects the kernel.

    Returns:
      uint16/uint32 ``(M, 128)`` reconstructed elements.
    """
    planes = tuple(planes)
    m = planes[0].shape[0]
    if itemsize == 2:
        rows, out_dtype = BF16_ROWS, jnp.uint16
        kern = _bf16_unplane_kernel if base is None else _bf16_unplane_delta_kernel
    elif itemsize == 4:
        rows, out_dtype = FP32_ROWS, jnp.uint32
        kern = _fp32_unplane_kernel if base is None else _fp32_unplane_delta_kernel
    else:
        raise ValueError(f"fused plane consumer: unsupported itemsize {itemsize}")
    operands: Tuple[jax.Array, ...] = planes if base is None else planes + (base,)
    return pl.pallas_call(
        kern,
        grid=(m // rows,),
        in_specs=[_spec(rows)] * len(operands),
        out_specs=_spec(rows),
        out_shape=jax.ShapeDtypeStruct((m, LANES), out_dtype),
        name="plane_consumer",
        interpret=interpret,
    )(*operands)
