"""Pallas TPU kernel: Huffman bit-packing (the encode hot loop).

Every (plane, chunk) work item the codec planned as ``HUFF`` packs its
symbols into MSB-first canonical codes: bit ``j`` of the chunk's bitstream
lands at bit ``31 - j`` of uint32 word ``j // 32``, so big-endian word
bytes are exactly the ``np.packbits`` stream ``core.huffman.encode``
emits.  The grid runs one program per chunk, so the kernel's parallelism
matches the container's parallel-decode metadata map.

Within a chunk the packing is a serial append, and it runs on the
TensorCore's scalar unit: the chunk's symbols are DMA'd from HBM into
scalar memory (SMEM), each symbol loads its ``(code << 4) | length`` entry
from the chunk's plane table (a data-dependent load, which only the scalar
unit can issue), and the code is shifted into the word being filled.  A
filled word is stored and the next one starts with the code's spill bits.
The packed words DMA back to HBM.

Output capacity per chunk equals the raw size: chunks that would expand
are stored raw by the host (the codec's expansion guard), so no dynamic
shapes are needed.  Bits past the capacity go to a trash word and are
dropped; words past the last packed bit are zero.

SMEM budget per grid step: ``chunk_syms`` bytes of symbols and as many
bytes of words, plus 1 KiB of table per plane — 256 KiB at the default
128 KiB plane chunks (v5e has 1 MiB).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MAXL = 15


def _srl(x, n):
    return jax.lax.shift_right_logical(x, n)


def _bitpack_kernel(pid_ref, tab_ref, syms_hbm, words_hbm, nbits_ref,
                    syms_s, out_s, sem):
    """Pack chunk ``program_id(0)`` under table row ``pid_ref[i]``.

    ``syms_s`` holds the chunk's ``4 * n`` symbols four to an int32 word:
    symbol ``k`` in byte ``k // n`` of word ``k % n``.  ``out_s`` holds the
    ``n`` capacity words plus one trash word.
    """
    i = pl.program_id(0)
    n = syms_s.shape[0]
    load = pltpu.make_async_copy(
        syms_hbm.at[pl.ds(i * n, n)], syms_s, sem.at[0]
    )
    load.start()
    load.wait()
    base = pid_ref[i] * 256

    def body(k, carry):
        cur, fill, widx, total = carry
        sym = _srl(syms_s[k % n], 8 * (k // n)) & 0xFF
        entry = tab_ref[base + sym]
        code, length = entry >> 4, entry & 15
        end = fill + length
        # Left-align the code after the ``fill`` bits already in ``cur``;
        # when it crosses the word boundary, its low ``end - 32`` bits spill.
        cur = cur | jnp.where(
            end <= 32,
            code << jnp.clip(32 - end, 0, 31),
            _srl(code, jnp.maximum(end - 32, 0)),
        )
        out_s[jnp.minimum(widx, n)] = cur
        full = end >= 32
        spill = end - 32
        cur = jnp.where(
            full,
            jnp.where(spill > 0, code << jnp.clip(32 - spill, 0, 31), 0),
            cur,
        )
        return (
            cur,
            jnp.where(full, spill, end),
            widx + full.astype(jnp.int32),
            total + length,
        )

    zero = jnp.int32(0)
    cur, _, widx, total = jax.lax.fori_loop(
        0, 4 * n, body, (zero, zero, zero, zero)
    )
    out_s[jnp.minimum(widx, n)] = cur          # spill bits of the last code

    def clear(k, carry):
        out_s[k] = zero
        return carry

    jax.lax.fori_loop(widx + 1, n, clear, 0)
    nbits_ref[i] = total
    store = pltpu.make_async_copy(
        out_s.at[pl.ds(0, n)], words_hbm.at[pl.ds(i * n, n)], sem.at[1]
    )
    store.start()
    store.wait()


@functools.partial(jax.jit, static_argnames=("chunk_syms", "interpret"))
def bitpack_encode_chunks_multi(
    syms: jax.Array,
    plane_ids: jax.Array,
    len_tables: jax.Array,
    code_tables: jax.Array,
    *,
    chunk_syms: int = 1 << 13,
    interpret: bool = True,
):
    """Multi-table bit-pack: chunk ``i`` packs under table ``plane_ids[i]``.

    ``syms`` is uint8[C*chunk_syms] (chunks from *different planes*
    concatenated), ``plane_ids`` int32[C] selects a row of the stacked
    ``(P, 256)`` length/code tables per chunk.  One dispatch covers every
    (plane, chunk) Huffman work item of a tensor.  Returns packed words
    ``uint32[C, chunk_syms/4]`` (raw-size capacity) and the true bit count
    ``int32[C]`` of each chunk.
    """
    total = syms.shape[0]
    if total % chunk_syms or chunk_syms % 4:
        raise ValueError(
            f"{total} symbols do not split into whole chunks of "
            f"{chunk_syms} (a multiple of 4): pad to whole chunks on the host"
        )
    c = total // chunk_syms
    n = chunk_syms // 4
    # Symbol k of a chunk goes to byte k // n of word k % n.
    s = syms.reshape(c, 4, n).astype(jnp.uint32)
    packed = s[:, 0] | (s[:, 1] << 8) | (s[:, 2] << 16) | (s[:, 3] << 24)
    table = (code_tables.astype(jnp.int32) << 4) | len_tables.astype(jnp.int32)
    words, nbits = pl.pallas_call(
        _bitpack_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(c,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            scratch_shapes=[
                pltpu.SMEM((n,), jnp.int32),
                pltpu.SMEM((n + 1,), jnp.int32),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((c * n,), jnp.int32),
            jax.ShapeDtypeStruct((c,), jnp.int32),
        ],
        name="bitpack_encode_chunks_multi",
        interpret=interpret,
    )(
        plane_ids.astype(jnp.int32),
        table.reshape(-1),
        jax.lax.bitcast_convert_type(packed, jnp.int32).reshape(-1),
    )
    return jax.lax.bitcast_convert_type(words, jnp.uint32).reshape(c, n), nbits


def bitpack_encode_chunks(
    syms: jax.Array,
    len_table: jax.Array,
    code_table: jax.Array,
    *,
    chunk_syms: int = 1 << 13,
    interpret: bool = True,
):
    """Single-table form of :func:`bitpack_encode_chunks_multi`.

    uint8[C*chunk_syms] → (uint32[C, chunk_syms/4], int32[C]).
    """
    c = syms.shape[0] // chunk_syms
    return bitpack_encode_chunks_multi(
        syms,
        jnp.zeros((c,), jnp.int32),
        jnp.asarray(len_table)[None, :],
        jnp.asarray(code_table)[None, :],
        chunk_syms=chunk_syms,
        interpret=interpret,
    )
