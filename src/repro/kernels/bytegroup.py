"""Pallas TPU kernels: byte-group / exponent-extraction transform.

The compression hot path starts with a pure data-movement transform
(paper Fig. 3/5): rotate each parameter's uint image left by one bit and
split it into byte planes.  On TPU this is an elementwise VPU op — the
design decisions are the uint lane width (16/32-bit ops on native lanes,
8-bit only at the final downcast) and the VMEM block shape (rows × 128
lanes, rows sized so in+out blocks stay ≲ 256 KiB for double buffering).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# 2-D layout: (rows, 128) — the TPU-native lane count.
LANES = 128
BF16_ROWS = 512            # u16 in: 128 KiB; u8 outs: 2×64 KiB
FP32_ROWS = 256            # u32 in: 128 KiB; u8 outs: 4×32 KiB


def _bf16_fwd_kernel(x_ref, exp_ref, frac_ref):
    # Work in int32 lanes (TPU-native); keep values in the low 16 bits.
    x = x_ref[...].astype(jnp.int32) & 0xFFFF
    rot = ((x << 1) | (x >> 15)) & 0xFFFF
    exp_ref[...] = (rot >> 8).astype(jnp.uint8)
    frac_ref[...] = (rot & 0xFF).astype(jnp.uint8)


def _bf16_inv_kernel(exp_ref, frac_ref, x_ref):
    rot = (exp_ref[...].astype(jnp.int32) << 8) | frac_ref[...].astype(jnp.int32)
    x = ((rot >> 1) | ((rot & 1) << 15)) & 0xFFFF
    x_ref[...] = x.astype(jnp.uint16)


def _fp32_fwd_kernel(x_ref, p0_ref, p1_ref, p2_ref, p3_ref):
    x = x_ref[...].astype(jnp.uint32)
    rot = (x << 1) | (x >> 31)
    p0_ref[...] = (rot >> 24).astype(jnp.uint8)
    p1_ref[...] = ((rot >> 16) & 0xFF).astype(jnp.uint8)
    p2_ref[...] = ((rot >> 8) & 0xFF).astype(jnp.uint8)
    p3_ref[...] = (rot & 0xFF).astype(jnp.uint8)


def _fp32_inv_kernel(p0_ref, p1_ref, p2_ref, p3_ref, x_ref):
    rot = (
        (p0_ref[...].astype(jnp.uint32) << 24)
        | (p1_ref[...].astype(jnp.uint32) << 16)
        | (p2_ref[...].astype(jnp.uint32) << 8)
        | p3_ref[...].astype(jnp.uint32)
    )
    x_ref[...] = (rot >> 1) | (rot << 31)


def _spec(rows):
    return pl.BlockSpec((rows, LANES), lambda i: (i, 0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def bytegroup_bf16_2d(x: jax.Array, *, interpret: bool = True):
    """uint16[M, 128] (M % BF16_ROWS == 0) → (exp, frac) uint8[M, 128]."""
    m = x.shape[0]
    return pl.pallas_call(
        _bf16_fwd_kernel,
        grid=(m // BF16_ROWS,),
        in_specs=[_spec(BF16_ROWS)],
        out_specs=[_spec(BF16_ROWS)] * 2,
        out_shape=[jax.ShapeDtypeStruct((m, LANES), jnp.uint8)] * 2,
        name="bytegroup_bf16_2d",
        interpret=interpret,
    )(x)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ungroup_bf16_2d(exp: jax.Array, frac: jax.Array, *, interpret: bool = True):
    m = exp.shape[0]
    return pl.pallas_call(
        _bf16_inv_kernel,
        grid=(m // BF16_ROWS,),
        in_specs=[_spec(BF16_ROWS)] * 2,
        out_specs=_spec(BF16_ROWS),
        out_shape=jax.ShapeDtypeStruct((m, LANES), jnp.uint16),
        name="ungroup_bf16_2d",
        interpret=interpret,
    )(exp, frac)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bytegroup_fp32_2d(x: jax.Array, *, interpret: bool = True):
    """uint32[M, 128] (M % FP32_ROWS == 0) → 4 × uint8[M, 128] planes."""
    m = x.shape[0]
    return pl.pallas_call(
        _fp32_fwd_kernel,
        grid=(m // FP32_ROWS,),
        in_specs=[_spec(FP32_ROWS)],
        out_specs=[_spec(FP32_ROWS)] * 4,
        out_shape=[jax.ShapeDtypeStruct((m, LANES), jnp.uint8)] * 4,
        name="bytegroup_fp32_2d",
        interpret=interpret,
    )(x)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ungroup_fp32_2d(p0, p1, p2, p3, *, interpret: bool = True):
    m = p0.shape[0]
    return pl.pallas_call(
        _fp32_inv_kernel,
        grid=(m // FP32_ROWS,),
        in_specs=[_spec(FP32_ROWS)] * 4,
        out_specs=_spec(FP32_ROWS),
        out_shape=jax.ShapeDtypeStruct((m, LANES), jnp.uint32),
        name="ungroup_fp32_2d",
        interpret=interpret,
    )(p0, p1, p2, p3)
