"""Pallas TPU kernel: XOR delta of two checkpoints + changed-byte count.

Paper §4.2: checkpoint deltas are XORs (exactly reversible, no carry bits).
The kernel fuses the delta with the changed-byte statistic that drives both
the Fig. 8(a) analysis and the Huffman-vs-LZ auto-selection's zero counting,
saving one full pass over HBM relative to delta-then-count.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
XOR_ROWS = 256             # 2 × u32 in + u32 out = 384 KiB per step


def _xor_kernel(a_ref, b_ref, d_ref, cnt_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    d = jnp.bitwise_xor(a_ref[...].astype(jnp.uint32), b_ref[...].astype(jnp.uint32))
    d_ref[...] = d
    changed = jnp.zeros((), jnp.int32)
    for k in range(4):
        changed = changed + jnp.sum(((d >> (8 * k)) & 0xFF) != 0, dtype=jnp.int32)
    cnt_ref[0] += changed


def _xor_elems_kernel(a_ref, b_ref, d_ref):
    d_ref[...] = jnp.bitwise_xor(a_ref[...], b_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def xor_elems_2d(a: jax.Array, b: jax.Array, *, interpret: bool = True):
    """Elementwise XOR at the operand dtype width (uint16/uint32).

    The counting variant below serves the Fig. 8(a) statistic; this plain
    variant feeds the fused plane producer (``kernels.fused_plane``), where
    the per-chunk zero counts come from the chunk histograms instead — no
    second reduction needed.  ``a.shape[0] % XOR_ROWS == 0`` required.
    """
    m = a.shape[0]
    return pl.pallas_call(
        _xor_elems_kernel,
        grid=(m // XOR_ROWS,),
        in_specs=[pl.BlockSpec((XOR_ROWS, LANES), lambda i: (i, 0))] * 2,
        out_specs=pl.BlockSpec((XOR_ROWS, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        name="xor_elems_2d",
        interpret=interpret,
    )(a, b)


@functools.partial(jax.jit, static_argnames=("interpret",))
def xor_delta_2d(a: jax.Array, b: jax.Array, *, interpret: bool = True):
    """(uint32[M,128], uint32[M,128]) → (delta uint32[M,128], int32[1])."""
    m = a.shape[0]
    return pl.pallas_call(
        _xor_kernel,
        grid=(m // XOR_ROWS,),
        in_specs=[pl.BlockSpec((XOR_ROWS, LANES), lambda i: (i, 0))] * 2,
        out_specs=[
            pl.BlockSpec((XOR_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, LANES), jnp.uint32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ],
        name="xor_delta_2d",
        interpret=interpret,
    )(a, b)
