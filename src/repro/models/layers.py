"""Shared primitive layers: norms, projections, RoPE/M-RoPE, MLPs.

Conventions:
  * params are plain nested dicts of jnp arrays;
  * every ``init_*`` has a matching ``*_spec`` entry in sharding.py via
    path-name rules (wq/wk/... names are load-bearing);
  * compute dtype is bf16 (fp32 for norms/softmax/logits), param dtype per
    config.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


def _he(key, shape, scale, dtype):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale / (fan_in ** 0.5)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def init_dense(key, d_in: int, d_out: int, dtype, *, bias: bool = False, scale=1.0):
    p = {"w": _he(key, (d_in, d_out), scale, dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p, x: Array, compute_dtype=jnp.bfloat16) -> Array:
    y = x.astype(compute_dtype) @ p["w"].astype(compute_dtype)
    if "b" in p:
        y = y + p["b"].astype(compute_dtype)
    return y


def init_rmsnorm(d: int, dtype):
    return {"g": jnp.ones((d,), dtype)}


def rmsnorm(p, x: Array, eps: float = 1e-5) -> Array:
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale * p["g"].astype(jnp.float32)).astype(x.dtype)


def init_layernorm(d: int, dtype):
    return {"g": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)}


def layernorm(p, x: Array, eps: float = 1e-5) -> Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["g"].astype(jnp.float32) + p["b"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE — standard and multimodal (M-RoPE, Qwen2-VL §2.1)
# ---------------------------------------------------------------------------

# YaRN's correction range as DeepSeek-V2 publishes it: the blend of
# frequencies ramps from the pair that turns YARN_BETA_FAST times within
# YARN_ORIGINAL_MAX positions to the pair that turns YARN_BETA_SLOW times.
YARN_ORIGINAL_MAX = 4096
YARN_BETA_FAST = 32
YARN_BETA_SLOW = 1


def rope_freqs(head_dim: int, theta: float, yarn_factor: float = 0.0) -> Array:
    """Inverse frequencies of the ``head_dim / 2`` rotation pairs.

    A ``yarn_factor`` applies YaRN (arXiv:2309.00071, as DeepSeek-V2
    configures it): each pair's frequency blends the interpolated
    ``1 / (factor · theta^(2i/d))`` and the extrapolated ``1 / theta^(2i/d)``
    by a linear ramp over the pairs, extrapolated below the correction
    range and interpolated above it.
    """
    pos_freqs = theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    if not yarn_factor:
        return 1.0 / pos_freqs

    def pair(rotations):
        return (head_dim * math.log(YARN_ORIGINAL_MAX / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair(YARN_BETA_FAST)), 0)
    high = min(math.ceil(pair(YARN_BETA_SLOW)), head_dim - 1)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return (1.0 / (yarn_factor * pos_freqs)) * ramp + (1.0 / pos_freqs) * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor ``0.1 · mscale · ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def apply_rope(x: Array, positions: Array, theta: float, yarn_factor: float = 0.0) -> Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S).  ``yarn_factor``:
    see :func:`rope_freqs`.  YaRN's cos and sin are not rescaled: DeepSeek-V2
    sets ``mscale`` equal to ``mscale_all_dim``, whose ratio is the factor."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta, yarn_factor)        # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]               # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(
    x: Array, positions_thw: Array, theta: float, sections: Tuple[int, int, int]
) -> Array:
    """M-RoPE: rotary sections driven by (temporal, height, width) positions.

    x: (B, S, H, hd); positions_thw: (B, S, 3) int32.  ``sections`` gives the
    number of *frequency pairs* assigned to each of t/h/w (sums to hd/2).
    For text tokens the stub frontend sets t == h == w == sequence position,
    which reduces M-RoPE to standard RoPE (as in the paper).
    """
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                     # (hd/2,)
    sec_id = jnp.repeat(
        jnp.arange(3), jnp.asarray(sections), total_repeat_length=hd // 2
    )                                                  # (hd/2,) ∈ {0,1,2}
    pos = jnp.take_along_axis(
        positions_thw.astype(jnp.float32),
        jnp.broadcast_to(sec_id[None, None, :], positions_thw.shape[:2] + (hd // 2,)).astype(jnp.int32),
        axis=-1,
    )                                                  # (B, S, hd/2)
    angles = pos * freqs[None, None, :]
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_swiglu(key, d_model: int, d_ff: int, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": _he(k1, (d_model, d_ff), 1.0, dtype),
        "w_up": _he(k2, (d_model, d_ff), 1.0, dtype),
        "w_down": _he(k3, (d_ff, d_model), 1.0, dtype),
    }


def swiglu(p, x: Array) -> Array:
    xc = x.astype(jnp.bfloat16)
    g = xc @ p["w_gate"].astype(jnp.bfloat16)
    u = xc @ p["w_up"].astype(jnp.bfloat16)
    return (jax.nn.silu(g) * u) @ p["w_down"].astype(jnp.bfloat16)


def init_gelu_mlp(key, d_model: int, d_ff: int, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "w_in": _he(k1, (d_model, d_ff), 1.0, dtype),
        "b_in": jnp.zeros((d_ff,), dtype),
        "w_out": _he(k2, (d_ff, d_model), 1.0, dtype),
        "b_out": jnp.zeros((d_model,), dtype),
    }


def gelu_mlp(p, x: Array) -> Array:
    xc = x.astype(jnp.bfloat16)
    h = jax.nn.gelu(xc @ p["w_in"].astype(jnp.bfloat16) + p["b_in"].astype(jnp.bfloat16))
    return h @ p["w_out"].astype(jnp.bfloat16) + p["b_out"].astype(jnp.bfloat16)


def init_embedding(key, vocab: int, d_model: int, dtype):
    return {"table": _he(key, (vocab, d_model), 1.0, dtype)}


def embed(p, tokens: Array) -> Array:
    return p["table"][tokens].astype(jnp.bfloat16)


def unembed(p, x: Array) -> Array:
    """Logits in fp32 (stable softmax/CE)."""
    return jnp.einsum(
        "...d,vd->...v", x.astype(jnp.bfloat16), p["table"].astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    )


def cross_entropy(logits: Array, labels: Array, mask: Optional[Array] = None) -> Array:
    """Mean CE over valid positions. logits fp32 (…, V), labels int (…)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# ---------------------------------------------------------------------------
# Fused (chunked) unembed + cross-entropy: full (B,S,V) logits never
# materialize — forward scans sequence chunks keeping only per-position
# logsumexp; backward recomputes each chunk's logits and emits
# (softmax − onehot) gradients.  At V≈50–150k this removes the dominant
# f32 activation of the training step (EXPERIMENTS.md §Perf).
# ---------------------------------------------------------------------------

import functools as _functools


@_functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def fused_cross_entropy(
    table: Array, x: Array, labels: Array, mask: Array, chunk: int = 8
) -> Array:
    loss, _ = _fused_ce_fwd_impl(table, x, labels, mask, chunk)
    return loss


def _fused_ce_fwd_impl(table, x, labels, mask, n_chunks):
    B, S, D = x.shape
    n = n_chunks if S % n_chunks == 0 else 1
    c = S // n
    xc = x.reshape(B, n, c, D)
    lc = labels.reshape(B, n, c)
    mc = mask.reshape(B, n, c).astype(jnp.float32)

    def body(acc, i):
        nll_sum, msum = acc
        logits = jnp.einsum(
            "bcd,vd->bcv", xc[:, i].astype(jnp.bfloat16),
            table.astype(jnp.bfloat16), preferred_element_type=jnp.float32,
        )
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, lc[:, i][..., None], axis=-1)[..., 0]
        nll = (lse - tgt) * mc[:, i]
        return (nll_sum + jnp.sum(nll), msum + jnp.sum(mc[:, i])), lse

    (nll_sum, msum), lses = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        jnp.arange(n),
    )
    loss = nll_sum / jnp.maximum(msum, 1.0)
    return loss, (lses, msum)          # lses: (n, B, c)


def _fused_ce_fwd(table, x, labels, mask, n_chunks):
    loss, (lses, msum) = _fused_ce_fwd_impl(table, x, labels, mask, n_chunks)
    return loss, (table, x, labels, mask, lses, msum)


def _fused_ce_bwd(n_chunks, res, g):
    table, x, labels, mask, lses, msum = res
    B, S, D = x.shape
    n = n_chunks if S % n_chunks == 0 else 1
    c = S // n
    xc = x.reshape(B, n, c, D)
    lc = labels.reshape(B, n, c)
    mc = mask.reshape(B, n, c).astype(jnp.float32)
    scale = g / jnp.maximum(msum, 1.0)

    def body(dtable, i):
        logits = jnp.einsum(
            "bcd,vd->bcv", xc[:, i].astype(jnp.bfloat16),
            table.astype(jnp.bfloat16), preferred_element_type=jnp.float32,
        )
        p = jnp.exp(logits - lses[i][..., None])
        onehot = jax.nn.one_hot(lc[:, i], table.shape[0], dtype=jnp.float32)
        dlogits = (p - onehot) * (mc[:, i] * scale)[..., None]
        dx_i = jnp.einsum(
            "bcv,vd->bcd", dlogits.astype(jnp.bfloat16),
            table.astype(jnp.bfloat16), preferred_element_type=jnp.float32,
        )
        dt_i = jnp.einsum(
            "bcv,bcd->vd", dlogits.astype(jnp.bfloat16),
            xc[:, i].astype(jnp.bfloat16), preferred_element_type=jnp.float32,
        )
        return dtable + dt_i, dx_i

    dt0 = jnp.zeros((table.shape[0], D), jnp.float32)
    dtable, dxs = jax.lax.scan(body, dt0, jnp.arange(n))
    dx = jnp.moveaxis(dxs, 0, 1).reshape(B, S, D).astype(x.dtype)
    return dtable.astype(table.dtype), dx, None, None


fused_cross_entropy.defvjp(_fused_ce_fwd, _fused_ce_bwd)
