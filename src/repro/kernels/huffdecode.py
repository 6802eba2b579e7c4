"""Multi-table canonical Huffman *decode* kernel (device entropy stage).

Inverse of :mod:`repro.kernels.bitpack`: the encode kernel packs MSB-first
canonical codes into uint32 words (bit ``j`` of the chunk at word bit
``31 - j``); this kernel walks that bitstream back to symbols.  Chunks are
mutually independent (the paper's §5.1 chunk-level parallelism, as
``huffman.decode_many`` expresses it on the host), while *within* a chunk
the decode is inherently serial: symbol ``i+1``'s bit position depends on
symbol ``i``'s code length.

The serial walk runs on the TensorCore's scalar unit, which is the only
unit that can load from a data-dependent address.  Per symbol it does

* two word loads for the window at the bit cursor, and one fused
  ``(symbol << 8) | length`` LUT load (the same 16-bit trick as the host
  decoder's ``lut16``) from the row of the stacked per-plane tables that
  the chunk's plane id selects — all planes of a tensor decode in one
  launch;
* a bit cursor advanced by the loaded code length; the final cursor is
  emitted so the host can apply the same integrity check as
  ``decode_many`` (a valid chunk's cursor lands inside its final byte,
  0-7 zero pad bits of slack).

Schedule: lanes.  One chunk's walk is a single dependency chain (cursor →
word load → window → LUT load → length → cursor), so one chunk alone
leaves the scalar unit waiting on its loads.  Each grid step therefore
decodes :func:`lanes` chunks at once: step ``i`` owns chunks
``i*G … i*G+G-1``, each lane with its own cursor and its own slice of the
word buffer, and the loop body advances every lane by two symbols in
straight-line code, so the compiler bundles ``G`` independent chains.  A
lane past the launch's last chunk loads, stores and reports nothing.
Blocks of the loop in which every live lane still has symbols left run
unmasked; the rest add a code length only while the lane's symbol index
is below its count, so each reported cursor is exactly the cursor after
``counts[k]`` symbols.  The cursor is clamped to the chunk's bit capacity
once per two symbols; with the buffer's few pad words behind the last
lane, a corrupt or truncated payload reads in-range garbage (its cursor
then fails the host check), never out of bounds.

Output layout: one symbol per int32 word (SMEM holds 32-bit words only),
symbol ``k`` of chunk ``i`` at word ``i * chunk_bytes + k``, so the wrapper
gets its bytes with one elementwise narrowing.  Packing four symbols a word
would save stores, but turning packed words back into bytes makes XLA
relayout them on the TPU, which compiles for minutes at some chunk counts.
Each lane's words go through a double-buffered block of
:data:`OUT_BLOCK` words that DMAs to HBM as it fills; symbols land
device-resident, and the driver
(:func:`repro.core.device_entropy.decode_planes`) feeds them straight into
the fused un-byte-group dispatch without a host bounce.

SMEM budget (:data:`SMEM_BUDGET` of v5e's 1 MiB): every plane's LUT row of
``4 << lut_bits`` bytes, ``chunk_bytes`` of packed words and two output
blocks per lane, and the launch's plane ids, counts and cursors.  ``G`` is
the largest lane count up to :data:`MAX_LANES`, and up to the launch's
chunk count, that fits (:func:`lanes`, a function of the launch's static
shapes alone): four for bf16 (two rows of 15-bit LUTs, 128 KiB chunks) and
for fp32 (four rows, 64 KiB chunks); a one-chunk launch runs one lane.
Chunk words DMA in slices of ``chunk_bytes // 4`` words, which the compiled
kernel needs aligned to the 1-D tiling (1024 words): the default chunks
are.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "MAXL",
    "MAX_LANES",
    "OUT_BLOCK",
    "SMEM_BUDGET",
    "huffdecode_chunks_multi",
    "lane_slots",
    "lanes",
]

MAXL = 15                      # same cap as the encoder / length-limited tables
MAX_LANES = 4                  # chunks interleaved in one loop body, at most
OUT_BLOCK = 2048               # symbols per lane per output DMA (two 1-D tiles)
# v5e's 1 MiB of scalar memory, less what the compiler keeps for itself and
# the rounding of each buffer to whole 1024-word tiles (about 8 KiB).
SMEM_BUDGET = (1 << 20) - (32 << 10)
# Words behind the last lane's buffer: a cursor clamped to the chunk's
# capacity moves at most one 255-bit length before the next clamp's last read.
_PAD_WORDS = 32
# Symbols a lane decodes per loop iteration.  Two keep the body at 2 * G
# scalar stores: the Pallas interpreter's loop (the CPU tests) slows
# twentyfold past about eight stores an iteration.
_SYMS_PER_ITER = 2


def _srl(x, n):
    return jax.lax.shift_right_logical(x, n)


def _block_syms(chunk_bytes: int) -> int:
    """Output block length: a divisor of the chunk's symbol count (even,
    since ``chunk_bytes`` is a multiple of four)."""
    if chunk_bytes <= OUT_BLOCK:
        return chunk_bytes
    return math.gcd(chunk_bytes, OUT_BLOCK)


def lanes(c: int, chunk_bytes: int, lut_words: int) -> int:
    """Chunks a grid step interleaves, from a launch's static shapes.

    ``c`` chunks of ``chunk_bytes``, ``lut_words`` int32 LUT entries over
    all plane rows.  The largest ``G`` ≤ :data:`MAX_LANES` and ≤ ``c``
    whose scalar-memory footprint fits :data:`SMEM_BUDGET`; at least 1.
    """
    cw = chunk_bytes // 4
    fixed = lut_words + 3 * c + _PAD_WORDS        # LUTs, ids, counts, cursors
    per_lane = cw + 2 * _block_syms(chunk_bytes)
    g = min(MAX_LANES, c)
    while g > 1 and 4 * (fixed + g * per_lane) > SMEM_BUDGET:
        g -= 1
    return max(g, 1)


def lane_slots(c: int, chunk_bytes: int, lut_words: int) -> int:
    """Lanes a launch of ``c`` chunks runs: ``G`` per grid step, idle
    ones included; ``c`` over this is the launch's lane fill."""
    g = lanes(c, chunk_bytes, lut_words)
    return g * -(-c // g)


def _huffdecode_kernel(pid_ref, count_ref, words_hbm, luts_ref,
                       syms_hbm, cursor_ref, words_s, out_s, sem, *,
                       lut_bits, g_lanes, blk):
    """Interleaved bit-cursor decode of chunks ``i*G … i*G+G-1``.

    Encode-kernel bit convention: bit ``j`` of the chunk at word bit
    ``31 - j``.  Writes ``count_ref[k]`` symbols and the final cursor of
    every live chunk ``k``.  ``luts_ref`` holds every plane's
    ``1 << lut_bits`` LUT row in SMEM.
    """
    i = pl.program_id(0)
    c = cursor_ref.shape[0]
    cb = syms_hbm.shape[0] // c
    cw = cb // 4
    cap = cb * 8
    chunk = [i * g_lanes + g for g in range(g_lanes)]
    live = [k < c for k in chunk]
    ids = [jnp.minimum(k, c - 1) for k in chunk]   # in-range prefetch reads
    # A count past the chunk's capacity (corrupt metadata) must not carry
    # the output DMAs past the chunk's row.
    counts = [jnp.where(lv, jnp.minimum(count_ref[k], cb), 0)
              for lv, k in zip(live, ids)]
    lut_base = [pid_ref[k] << lut_bits for k in ids]

    def word_copy(g):
        return pltpu.make_async_copy(
            words_hbm.at[pl.ds(chunk[g] * cw, cw)],
            words_s.at[pl.ds(g * cw, cw)], sem.at[g],
        )

    def out_copy(g, b):
        slot = b % 2
        return pltpu.make_async_copy(
            out_s.at[pl.ds((slot * g_lanes + g) * blk, blk)],
            syms_hbm.at[pl.ds(chunk[g] * cb + b * blk, blk)],
            sem.at[g_lanes + slot * g_lanes + g],
        )

    def each_live(fn):
        for g in range(g_lanes):
            pl.when(live[g])(functools.partial(fn, g))

    each_live(lambda g: word_copy(g).start())
    each_live(lambda g: word_copy(g).wait())

    def block(masked, b, pos):
        # The slot's previous block must have left before it is refilled.
        @pl.when(b >= 2)
        def _():
            each_live(lambda g: out_copy(g, b - 2).wait())

        row = (b % 2) * g_lanes * blk

        def step(it, pos):
            pos = list(pos)
            first = b * blk + it * _SYMS_PER_ITER
            at = row + it * _SYMS_PER_ITER
            for g in range(g_lanes):
                p = jnp.minimum(pos[g], cap)
                for j in range(_SYMS_PER_ITER):
                    # Bits [p, p + lut_bits) straddle at most two words.
                    # (a << o) keeps the window's first bit at the MSB; the
                    # second word gives its top o bits, through a double
                    # shift that stays defined at o == 0.
                    w = g * cw + (p >> 5)
                    o = p & 31
                    win = (words_s[w] << o) | _srl(_srl(words_s[w + 1], 1), 31 - o)
                    v = luts_ref[lut_base[g] + _srl(win, 32 - lut_bits)]
                    n = v & 0xFF
                    if masked:
                        n = jnp.where(first + j < counts[g], n, 0)
                    p = p + n
                    out_s[at + g * blk + j] = v >> 8
                pos[g] = p
            return tuple(pos)

        pos = jax.lax.fori_loop(0, blk // _SYMS_PER_ITER, step, pos)
        each_live(lambda g: out_copy(g, b).start())
        return pos

    n_blocks = (functools.reduce(jnp.maximum, counts) + blk - 1) // blk
    # Blocks that lie inside every live lane's count skip the count mask.
    least = functools.reduce(
        jnp.minimum, [jnp.where(lv, n, cb) for lv, n in zip(live, counts)]
    )
    n_full = jnp.minimum(least // blk, n_blocks)
    pos = tuple(jnp.int32(0) for _ in range(g_lanes))
    pos = jax.lax.fori_loop(0, n_full, functools.partial(block, False), pos)
    pos = jax.lax.fori_loop(n_full, n_blocks, functools.partial(block, True), pos)

    @pl.when(n_blocks >= 2)
    def _():
        each_live(lambda g: out_copy(g, n_blocks - 2).wait())

    @pl.when(n_blocks >= 1)
    def _():
        each_live(lambda g: out_copy(g, n_blocks - 1).wait())

    # Clamp for reporting only: a live cursor never exceeds the block (the
    # expansion guard keeps valid payloads under chunk_bytes), so the clamp
    # only tames corrupt streams — which the host then rejects.
    def report(g):
        cursor_ref[chunk[g]] = jnp.minimum(pos[g], cap)

    each_live(report)


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
    g = lanes(c, chunk_bytes, lut16_tables.size)
    blk = _block_syms(chunk_bytes)
    syms, cursors = pl.pallas_call(
        functools.partial(
            _huffdecode_kernel, lut_bits=lut_bits, g_lanes=g, blk=blk
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(-(-c // g),),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            scratch_shapes=[
                pltpu.SMEM((g * cw + _PAD_WORDS,), jnp.int32),
                pltpu.SMEM((2 * g * blk,), jnp.int32),
                pltpu.SemaphoreType.DMA((3 * g,)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((c * chunk_bytes,), jnp.int32),
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
    # One symbol a word: an elementwise narrowing, cheap to run and to compile
    # (a bitcast of packed bytes makes XLA relayout them, which compiles for
    # minutes at some chunk counts).
    return syms.reshape(c, chunk_bytes).astype(jnp.uint8), cursors
