"""Multi-table canonical Huffman *decode* kernel (device entropy stage).

Inverse of :mod:`repro.kernels.bitpack`: the encode kernel packs MSB-first
canonical codes into uint32 words (bit ``j`` of the chunk at word bit
``31 - j``); this kernel walks that bitstream back to symbols.  The
schedule is the paper's §5.1 chunk-level parallelism exactly as
``huffman.decode_many`` expresses it on the host — chunks are mutually
independent, so the grid runs one program per HUFF chunk, while *within*
a chunk the decode is inherently serial (symbol ``i+1``'s bit position
depends on symbol ``i``'s code length).

The serial walk runs on the TensorCore's scalar unit, which is the only
unit that can load from a data-dependent address: the stacked LUTs sit in
scalar memory (SMEM) for the whole launch, each chunk's packed words are
DMA'd from HBM into SMEM, and a ``fori_loop`` over the chunk's symbol
count does

* one fused ``(symbol << 8) | length`` LUT load per symbol (the same
  16-bit trick as the host decoder's ``lut16``), from the row of the
  stacked per-plane tables that the chunk's plane id selects — all planes
  of a tensor decode in one launch;
* a bit cursor advanced by the loaded code length; the final cursor is
  emitted so the host can apply the same integrity check as
  ``decode_many`` (a valid chunk's cursor lands inside its final byte,
  0-7 zero pad bits of slack);
* word loads index-clamped to the chunk's word block, so corrupt or
  truncated payloads decode garbage that the host-side cursor check then
  rejects — never an out-of-bounds load.

Decoded symbols are stored four to an int32 word (SMEM holds 32-bit words
only): symbol ``k`` of a chunk of ``4 * n`` symbols in byte ``k // n`` of
word ``k % n``, so the wrapper recovers the byte order on device with four
shifts and one concatenation, and the words DMA back to HBM as they are.  Symbols land device-resident: the driver
(:func:`repro.core.device_entropy.decode_planes`) can feed them straight
into the fused un-byte-group dispatch without a host bounce.

SMEM budget (v5e has 1 MiB): ``chunk_bytes`` of packed words,
``chunk_bytes`` of decoded symbols and every plane's LUT row of at most
``4 << MAXL`` bytes — 512 KiB at the default 128 KiB bf16 plane chunks
with the widest tables, 640 KiB for fp32's four planes of 64 KiB chunks.
Chunk words DMA in slices of ``chunk_bytes // 4`` words, which the
compiled kernel needs aligned to the 1-D tiling (1024 words): the
default chunks are.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["MAXL", "huffdecode_chunks_multi"]

MAXL = 15                      # same cap as the encoder / length-limited tables


def _srl(x, n):
    return jax.lax.shift_right_logical(x, n)


def _huffdecode_kernel(pid_ref, count_ref, words_hbm, luts_ref,
                       syms_hbm, cursor_ref, words_s, out_s, sem, *, lut_bits):
    """Serial bit-cursor decode of chunk ``program_id(0)``.

    Encode-kernel bit convention: bit ``j`` of the chunk at word bit
    ``31 - j``.  Writes ``count_ref[i]`` symbols and the final cursor.
    ``luts_ref`` holds every plane's ``1 << lut_bits`` LUT row in SMEM.
    """
    i = pl.program_id(0)
    nwords = words_s.shape[0]
    lut_base = pid_ref[i] << lut_bits
    load_words = pltpu.make_async_copy(
        words_hbm.at[pl.ds(i * nwords, nwords)], words_s, sem.at[0]
    )
    load_words.start()
    load_words.wait()

    def body(k, bitpos):
        # Bits [bitpos, bitpos + lut_bits) straddle at most two words.  The
        # indices are clamped so a runaway cursor (corrupt payload) reads
        # in-range garbage; the host rejects it via the cursor check.
        w0 = jnp.minimum(bitpos >> 5, nwords - 1)
        w1 = jnp.minimum(w0 + 1, nwords - 1)
        o = bitpos & 31
        # (a << o) keeps the window's first bit at the MSB; the second word
        # contributes its top o bits.  The double shift (>> 1 >> (31 - o))
        # stays defined at o == 0, where a single >> 32 would not be.
        win = (words_s[w0] << o) | _srl(_srl(words_s[w1], 1), 31 - o)
        v = luts_ref[lut_base + _srl(win, 32 - lut_bits)]
        # Symbol k goes to byte k // nwords of word k % nwords; the first
        # quarter of the chunk starts each word afresh.
        m = k % nwords
        prev = jnp.where(k < nwords, 0, out_s[m])
        out_s[m] = prev | ((v >> 8) << (8 * (k // nwords)))
        return bitpos + (v & 0xFF)

    final = jax.lax.fori_loop(0, count_ref[i], body, jnp.int32(0))
    # Clamp for reporting only: a live cursor never exceeds the block (the
    # expansion guard keeps valid payloads under chunk_bytes), so the clamp
    # only tames corrupt streams — which the host then rejects.
    cursor_ref[i] = jnp.minimum(final, nwords * 32)
    store = pltpu.make_async_copy(
        out_s, syms_hbm.at[pl.ds(i * nwords, nwords)], sem.at[1]
    )
    store.start()
    store.wait()


@functools.partial(jax.jit, static_argnames=("chunk_bytes", "interpret"))
def huffdecode_chunks_multi(
    words: jax.Array,
    plane_ids: jax.Array,
    counts: jax.Array,
    lut16_tables: jax.Array,
    *,
    chunk_bytes: int,
    interpret: bool = True,
):
    """Decode many packed HUFF chunks against stacked per-plane LUTs.

    ``words``        — ``(c * (chunk_bytes // 4),)`` uint32: each chunk's
                       payload bytes as big-endian words, zero-padded to the
                       ``chunk_bytes`` capacity (valid HUFF payloads are
                       always shorter — the expansion guard stores larger
                       chunks raw).
    ``plane_ids``    — ``(c,)`` row of ``lut16_tables`` per chunk.
    ``counts``       — ``(c,)`` symbols to decode per chunk (its raw length).
    ``lut16_tables`` — ``(p, 1 << lut_bits)`` fused ``(sym << 8) | len``
                       canonical LUTs, one row per plane, built at a shared
                       ``lut_bits`` ≥ every table's max code length.

    Returns ``(syms, cursors)``: ``(c, chunk_bytes)`` uint8 decoded symbols
    (entries past ``counts[k]`` are unspecified) and ``(c,)`` int32 final
    bit cursors for the host-side integrity check.
    """
    cw = chunk_bytes // 4
    c = words.shape[0] // cw
    lut_bits = lut16_tables.shape[1].bit_length() - 1
    packed, cursors = pl.pallas_call(
        functools.partial(_huffdecode_kernel, lut_bits=lut_bits),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(c,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            scratch_shapes=[
                pltpu.SMEM((cw,), jnp.int32),
                pltpu.SMEM((cw,), jnp.int32),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((c * cw,), jnp.int32),
            jax.ShapeDtypeStruct((c,), jnp.int32),
        ],
        name="huffdecode_chunks_multi",
        interpret=interpret,
    )(
        plane_ids.astype(jnp.int32),
        counts.astype(jnp.int32),
        jax.lax.bitcast_convert_type(words, jnp.int32),
        lut16_tables.astype(jnp.int32).reshape(-1),
    )
    # Symbol k of a chunk sits in byte k // cw of word k % cw.
    packed = jax.lax.bitcast_convert_type(packed, jnp.uint32).reshape(c, cw)
    syms = jnp.concatenate(
        [(packed >> s) & 0xFF for s in (0, 8, 16, 24)], axis=1
    ).astype(jnp.uint8)
    return syms, cursors
