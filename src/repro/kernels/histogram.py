"""Pallas TPU kernel: 256-bin byte histograms, per codec chunk.

Histograms drive ZipNN's table building and compressibility probes.  CUDA
would use atomic scatter-adds; TPU has no atomics and no vector scatter, so
the TPU-native formulation is *compare-and-reduce*: each grid step compares
its (HIST_ROWS, 128) block against every bin and adds the per-lane counts
(a sum over rows) into a (256, 128) scratch accumulator.  On the chunk's
last block the accumulator is transposed and summed over lanes into the
chunk's 256-bin row.  Output blocks are (1, 256) rows of a
(chunks, 1, 256) array, which the TPU's (8, 128) tiling accepts because the
block spans the array's last two dimensions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
HIST_ROWS = 128            # u8 block: 16 KiB


def _chunk_hist_kernel(x_ref, out_ref, acc_ref):
    # Grid (chunk, block-within-chunk): the accumulator is reset on a
    # chunk's first block and emitted on its last.
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.int32)

    def body(b, carry):
        acc_ref[pl.ds(b, 1), :] += jnp.sum(
            (x == b).astype(jnp.int32), axis=0, keepdims=True
        )
        return carry

    jax.lax.fori_loop(0, 256, body, 0)

    @pl.when(j == pl.num_programs(1) - 1)
    def _emit():
        counts = jnp.sum(acc_ref[...].T, axis=0, keepdims=True)
        out_ref[...] = counts.reshape(out_ref.shape)


@functools.partial(jax.jit, static_argnames=("chunk_rows", "interpret"))
def chunk_histogram_2d(
    x: jax.Array, *, chunk_rows: int, interpret: bool = True
) -> jax.Array:
    """uint8[M, 128] → int32[M // chunk_rows, 256] per-chunk counts.

    Requires ``M % chunk_rows == 0`` and ``chunk_rows % HIST_ROWS == 0`` —
    codec chunks (128 KiB per plane by default) are whole multiples of the
    16 KiB histogram block, so one grid row of blocks reduces into one
    chunk's 256-bin row.  This is the device-side replacement for the
    codec's per-chunk ``np.bincount`` probe (the GIL-bound ~15 % of host
    compress time): every chunk's probe histogram comes back in a single
    fused dispatch alongside the byte-group planes.
    """
    m = x.shape[0]
    n_chunks = m // chunk_rows
    blocks = chunk_rows // HIST_ROWS
    out = pl.pallas_call(
        _chunk_hist_kernel,
        grid=(n_chunks, blocks),
        in_specs=[
            pl.BlockSpec((HIST_ROWS, LANES), lambda i, j: (i * blocks + j, 0))
        ],
        out_specs=pl.BlockSpec((1, 1, 256), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_chunks, 1, 256), jnp.int32),
        scratch_shapes=[pltpu.VMEM((256, LANES), jnp.int32)],
        name="chunk_histogram_2d",
        interpret=interpret,
    )(x)
    return out.reshape(n_chunks, 256)


def histogram_2d(x: jax.Array, *, interpret: bool = True) -> jax.Array:
    """uint8[M, 128] (M % HIST_ROWS == 0) → int32[256] counts."""
    return chunk_histogram_2d(x, chunk_rows=x.shape[0], interpret=interpret)[0]
