"""Public ops over the ZipNN Pallas kernels.

Handles 1-D↔2-D reshaping, padding to block multiples, interpret-mode
selection (CPU validation vs TPU execution), and byte-exact equivalence
with the host codec (``core.huffman`` / ``core.bitlayout``).
"""

from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import bitpack, bytegroup, histogram, xor_delta

LANES = 128


def interpret_mode() -> bool:
    """Whether the kernels run in the Pallas interpreter.

    They compile for the chip only where JAX's default backend is a TPU;
    on any other platform they run interpreted, which checks bytes but says
    nothing about speed.  Every kernel launch in the package asks here.
    """
    return jax.default_backend() != "tpu"


def _pad_2d(x: jnp.ndarray, rows: int) -> Tuple[jnp.ndarray, int]:
    """Pad flat array to a (M, 128) grid with M % rows == 0."""
    n = x.shape[0]
    block = rows * LANES
    m = -(-max(n, 1) // block) * block
    if m != n:
        x = jnp.pad(x, (0, m - n))
    return x.reshape(-1, LANES), n


def bytegroup_bf16(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """uint16[N] → (exponent uint8[N], frac|sign uint8[N])."""
    x2, n = _pad_2d(x, bytegroup.BF16_ROWS)
    exp, frac = bytegroup.bytegroup_bf16_2d(x2, interpret=interpret_mode())
    return exp.reshape(-1)[:n], frac.reshape(-1)[:n]


def ungroup_bf16(exp: jax.Array, frac: jax.Array) -> jax.Array:
    e2, n = _pad_2d(exp, bytegroup.BF16_ROWS)
    f2, _ = _pad_2d(frac, bytegroup.BF16_ROWS)
    x = bytegroup.ungroup_bf16_2d(e2, f2, interpret=interpret_mode())
    return x.reshape(-1)[:n]


def bytegroup_fp32(x: jax.Array) -> Tuple[jax.Array, ...]:
    """uint32[N] → 4 × uint8[N] planes (plane 0 = exponent)."""
    x2, n = _pad_2d(x, bytegroup.FP32_ROWS)
    planes = bytegroup.bytegroup_fp32_2d(x2, interpret=interpret_mode())
    return tuple(p.reshape(-1)[:n] for p in planes)


def ungroup_fp32(*planes: jax.Array) -> jax.Array:
    padded = [_pad_2d(p, bytegroup.FP32_ROWS)[0] for p in planes]
    n = planes[0].shape[0]
    x = bytegroup.ungroup_fp32_2d(*padded, interpret=interpret_mode())
    return x.reshape(-1)[:n]


def byte_histogram(x: jax.Array) -> jax.Array:
    """uint8[N] → int32[256].  Padding bytes (zeros) are subtracted out."""
    x2, n = _pad_2d(x, histogram.HIST_ROWS)
    hist = histogram.histogram_2d(x2, interpret=interpret_mode())
    pad = x2.size - n
    return hist.at[0].add(-pad)


def xor_delta_u32(a: jax.Array, b: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(uint32[N],)² → (delta uint32[N], changed-byte count int32[])."""
    a2, n = _pad_2d(a, xor_delta.XOR_ROWS)
    b2, _ = _pad_2d(b, xor_delta.XOR_ROWS)
    d, cnt = xor_delta.xor_delta_2d(a2, b2, interpret=interpret_mode())
    return d.reshape(-1)[:n], cnt[0]


def huffman_encode_chunks(
    syms: np.ndarray,
    lens: np.ndarray,
    codes: np.ndarray,
    chunk_syms: int = 1 << 13,
) -> List[bytes]:
    """Byte-exact TPU-kernel counterpart of ``core.huffman.encode_chunks``.

    Splits ``syms`` into fixed ``chunk_syms`` chunks (last chunk padded; its
    true bit count is recomputed from the table so the padding never leaks
    into the output), runs the bit-pack kernel, and serializes each chunk's
    words big-endian — byte-identical to ``np.packbits`` order.
    """
    n = int(syms.shape[0])
    if n == 0:
        return []
    n_chunks = -(-n // chunk_syms)
    padded = np.zeros(n_chunks * chunk_syms, dtype=np.uint8)
    padded[:n] = syms
    if n % chunk_syms:
        # Pad with the symbol whose canonical code is all-zero bits (code 0
        # always exists): its bits land *after* the true payload and leave
        # the trailing partial byte zero-filled, matching np.packbits.
        lens_arr = np.asarray(lens)
        codes_arr = np.asarray(codes)
        pad_sym = int(np.flatnonzero((lens_arr > 0) & (codes_arr == 0))[0])
        padded[n:] = pad_sym

    words, nbits = bitpack.bitpack_encode_chunks(
        jnp.asarray(padded),
        jnp.asarray(lens, dtype=jnp.int32),
        jnp.asarray(codes, dtype=jnp.int32),
        chunk_syms=chunk_syms,
        interpret=interpret_mode(),
    )
    words = np.asarray(words)
    nbits = np.asarray(nbits)

    out: List[bytes] = []
    lens_np = np.asarray(lens, dtype=np.int64)
    for c in range(n_chunks):
        lo, hi = c * chunk_syms, min((c + 1) * chunk_syms, n)
        true_bits = int(lens_np[syms[lo:hi]].sum())
        raw = words[c].astype(">u4").tobytes()
        out.append(raw[: -(-true_bits // 8)])
    return out
