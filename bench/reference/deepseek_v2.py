"""Plain DeepSeek-V2 decoder (as DeepSeek-V2-Lite configures it) over a
given latent cache prefix.

Written from the published architecture (arXiv:2405.04434 §2 and the
``config.json`` of deepseek-ai/DeepSeek-V2-Lite), not from the program:

* RMSNorm before attention and before the feed-forward, and on the final
  hidden state; residual adds.
* Multi-head latent attention without a query low-rank path
  (``q_lora_rank: null``): queries ``h W_q`` split into a no-position part
  (``qk_nope_head_dim``) and a rotary part (``qk_rope_head_dim``); the
  key/value latent ``c = RMSNorm(h W_dkv)`` and one rotary key ``rope(h
  W_kr)`` shared by every head.  The latent is up-projected to per-head
  keys ``[c W_uk, k_rope]`` and values ``c W_uv`` (no absorption into the
  query).  Softmax scale ``(dn + dr)^-0.5 · mscale²`` with YaRN's
  ``mscale = 0.1 · mscale_all_dim · ln(factor) + 1``.
* YaRN rotary frequencies: the interpolated ``1 / (factor · theta^(2i/d))``
  and the extrapolated ``1 / theta^(2i/d)`` blended by a linear ramp
  between the correction dims of ``beta_fast`` (32) and ``beta_slow`` (1)
  rotations within ``original_max_position_embeddings`` (4096) positions;
  cos and sin scaled by ``mscale(factor, mscale) / mscale(factor,
  mscale_all_dim)``, which is 1 where the two are equal, as published
  (0.707 both).
* A dense SwiGLU feed-forward in the first ``first_k_dense_replace``
  layers; then MoE: a softmax router over every routed expert, the top
  ``num_experts_per_tok`` probabilities as gates, not renormalised
  (``norm_topk_prob: false``, ``routed_scaling_factor`` 1), each chosen
  expert's SwiGLU output weighted by its gate, plus the shared experts'
  SwiGLU (width ``moe_intermediate_size · n_shared_experts``).
* An untied output head.

Everything is float32 at ``highest`` matmul precision.  The cache prefix
holds, for positions ``0 .. P-1``, the latent after its norm and the
rotary key after its rotation, as a decode cache stores them; ``tokens``
continue at position ``P``, and :func:`trunk` gives their entries too.
The prefix's attention runs in blocks of the batch, so that its
up-projected keys and values fit.

Departures from the published model:

* Rotate-half RoPE layout.  The published code rotates interleaved pairs
  (it permutes ``(x0, x1, x2, …)`` to ``(x0, x2, …, x1, x3, …)`` before the
  rotate-half form); with random weights that only permutes the columns of
  the rotary part of ``W_q`` and of ``W_kr``.
* The published ``kv_a_proj_with_mqa`` and ``kv_b_proj`` are split by
  columns into ``W_dkv``/``W_kr`` and ``W_uk``/``W_uv``: the same products.
* Expert parallelism: the MoE layer adds the outputs of the routed
  experts whose weights it is given, the router's first ``held`` (experts
  0-7: the share chip 0 of the deployment holds), after routing over all
  of them; what the other holders' experts would add is left out, as one
  chip computes it.  ``held`` equal to the router's width is the whole
  layer.
* Random weights.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.qwen import gaps

HI = jax.lax.Precision.HIGHEST


class Dims(NamedTuple):
    heads: int
    nope: int
    rope: int
    v: int
    eps: float
    scale: float          # softmax scale, YaRN's mscale² included
    top_k: int
    norm_topk: bool
    block: int            # batch rows a prefix-attention block holds


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def inv_freq(dim: int, theta: float, factor: float = 0.0, original: int = 4096,
             beta_fast: float = 32.0, beta_slow: float = 1.0) -> np.ndarray:
    """RoPE inverse frequencies (float64), YaRN-blended where ``factor``."""
    base = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not factor:
        return base

    def corr(rot):
        return dim * math.log(original / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    return base / factor * ramp + base * (1.0 - ramp)


def dims(cfg, block: int = 8) -> Dims:
    """The reference's numbers from a program configuration."""
    f = cfg.yarn_factor
    m = yarn_mscale(f, cfg.yarn_mscale_all_dim) if f and cfg.yarn_mscale_all_dim else 1.0
    return Dims(cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim,
                float(cfg.norm_eps), (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5 * m * m,
                cfg.experts_per_token, cfg.norm_topk_prob, block)


def frequencies(cfg) -> jax.Array:
    return jnp.asarray(inv_freq(cfg.qk_rope_dim, float(cfg.rope_theta), cfg.yarn_factor),
                       jnp.float32)


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(g)


def _mm(x, w):
    return jnp.matmul(x, _f32(w), precision=HI)


def _rope(x, pos, inv):
    """x: (B, T, H, d) rotated at positions ``pos`` (T,), rotate-half."""
    ang = pos[:, None].astype(jnp.float32) * inv                # (T, d/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    d = x.shape[-1]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _swiglu(m, h):
    return _mm(jax.nn.silu(_mm(h, m["w_gate"])) * _mm(h, m["w_up"]), m["w_down"])


def _attention(at, h, ckv, kr, pos, inv, d: Dims):
    """h (B, T, D) at positions ``pos``; the prefix ``ckv`` (B, P, r) and
    ``kr`` (B, P, dr).  The output, and the normed latent and rotated key
    of ``h``, the cache entries of its positions."""
    B, T, _ = h.shape
    H, dn, dr = d.heads, d.nope, d.rope
    q = _mm(h, at["w_q"]["w"]).reshape(B, T, H, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], pos, inv)
    c = _rms(_mm(h, at["w_dkv"]["w"]), at["kv_norm"]["g"], d.eps)           # (B, T, r)
    k_rope = _rope(_mm(h, at["w_kr"]["w"])[:, :, None, :], pos, inv)[:, :, 0]
    w_uk, w_uv = at["w_uk"]["w"], at["w_uv"]["w"]

    def up(lat):                       # latent (b, S, r) -> per-head keys and values
        b, S, _ = lat.shape
        return _mm(lat, w_uk).reshape(b, S, H, dn), _mm(lat, w_uv).reshape(b, S, H, d.v)

    k_new, v_new = up(c)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def block(args):
        qn, qr, kn, krn, vn, pc, pk = args
        k_pre, v_pre = up(_f32(pc))
        s_pre = (jnp.einsum("bthd,bphd->bhtp", qn, k_pre, precision=HI)
                 + jnp.einsum("bthd,bpd->bhtp", qr, _f32(pk), precision=HI))
        s_new = (jnp.einsum("bthd,bshd->bhts", qn, kn, precision=HI)
                 + jnp.einsum("bthd,bsd->bhts", qr, krn, precision=HI))
        s_new = jnp.where(causal[None, None], s_new, -jnp.inf)
        s = jax.nn.softmax(jnp.concatenate([s_pre, s_new], axis=-1) * d.scale, axis=-1)
        P = pc.shape[1]
        return (jnp.einsum("bhtp,bphd->bthd", s[..., :P], v_pre, precision=HI)
                + jnp.einsum("bhts,bshd->bthd", s[..., P:], vn, precision=HI))

    nb = B // d.block
    split = lambda a: a.reshape(nb, d.block, *a.shape[1:])        # noqa: E731
    ctx = jax.lax.map(block, tuple(split(a) for a in
                                   (q_nope, q_rope, k_new, k_rope, v_new, ckv, kr)))
    return _mm(ctx.reshape(B, T, -1), at["wo"]["w"]), c, k_rope


def moe_ffn(mp, h, d: Dims):
    """The held experts' gated outputs plus the shared experts, for h (..., D)."""
    probs = jax.nn.softmax(_mm(h, mp["router"]["w"]), axis=-1)
    gate, idx = jax.lax.top_k(probs, d.top_k)
    if d.norm_topk:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    ex = mp["experts"]
    held = ex["w_gate"].shape[0]
    # weight of each held expert: its gate where the router chose it
    w = jnp.sum(jax.nn.one_hot(idx, held) * gate[..., None], axis=-2)
    g = jnp.einsum("...d,edf->...ef", h, _f32(ex["w_gate"]), precision=HI)
    u = jnp.einsum("...d,edf->...ef", h, _f32(ex["w_up"]), precision=HI)
    y = jnp.einsum("...ef,efd->...ed", jax.nn.silu(g) * u, _f32(ex["w_down"]), precision=HI)
    y = jnp.einsum("...e,...ed->...d", w, y, precision=HI)
    return y + _swiglu(mp["shared"], h) if "shared" in mp else y


@partial(jax.jit, static_argnames=("d", "moe"))
def layer(lp, x, ckv, kr, pos, inv, *, d: Dims, moe: bool):
    """One decoder layer on x (B, T, D), and its cache entries of x."""
    a, c, k_rope = _attention(lp["attn"], _rms(x, lp["attn_norm"]["g"], d.eps), ckv, kr, pos,
                              inv, d)
    x = x + a
    h = _rms(x, lp["mlp_norm"]["g"], d.eps)
    return x + (moe_ffn(lp["moe"], h, d) if moe else _swiglu(lp["mlp"], h)), c, k_rope


def trunk(params, prefix_ckv, prefix_kr, tokens, cfg):
    """Final-normed hidden states (B, T, D) of ``tokens`` (B, T) after the
    prefix (``prefix_*``: (layers, B, P, ·)), and the tokens' cache entries:
    the normed latents (layers, B, T, r) and rotated keys (layers, B, T, dr)."""
    B, T = tokens.shape
    d = dims(cfg, block=math.gcd(B, 8))
    inv = frequencies(cfg)
    pos = prefix_ckv.shape[2] + jnp.arange(T)
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"]["table"][tokens])
        j, ckv, kr = 0, [], []
        for key, moe in (("dense_layers", False), ("moe_layers", True)):
            stack = params.get(key)
            if stack is None:
                continue
            for i in range(jax.tree_util.tree_leaves(stack)[0].shape[0]):
                lp = jax.tree_util.tree_map(lambda a: a[i], stack)
                x, c, k = layer(lp, x, prefix_ckv[j], prefix_kr[j], pos, inv, d=d, moe=moe)
                ckv.append(c)
                kr.append(k)
                j += 1
        return _rms(x, params["final_norm"]["g"], d.eps), (jnp.stack(ckv), jnp.stack(kr))


def logits(params, prefix_ckv, prefix_kr, tokens, cfg):
    """Reference logits (B, T, V), for small sizes."""
    hid, _ = trunk(params, prefix_ckv, prefix_kr, tokens, cfg)
    return jnp.einsum("btd,vd->btv", hid, _f32(params["lm_head"]["table"]), precision=HI)


def served_gap(params, hidden, served, block: int = 16):
    """Widest gap over all positions by which a served token's logit lies
    below the reference's best; ``hidden`` from :func:`trunk`, ``served``
    (B, T) int32."""
    worst = 0.0
    for t in range(0, served.shape[1], block):
        g, _ = gaps(params["lm_head"]["table"], hidden[:, t:t + block], served[:, t:t + block])
        worst = max(worst, float(jnp.max(g)))
    return worst


def first_ranked(params, hidden, block: int = 16):
    """The token each position's logits put first, (B, T) int32."""
    picks = []
    for t in range(0, hidden.shape[1], block):
        _, pick = gaps(params["lm_head"]["table"], hidden[:, t:t + block],
                       jnp.zeros(hidden[:, t:t + block].shape[:2], jnp.int32))
        picks.append(pick)
    return jnp.concatenate(picks, axis=1)


def control_gap(params, hidden, low, hidden_low):
    """Widest gap, under the reference, of the tokens that the
    lower-precision weights ``low`` put first (``hidden_low`` their
    :func:`trunk`)."""
    return served_gap(params, hidden, first_ranked(low, hidden_low))


def entry_error(got, want):
    """Widest relative error, over layers and the two caches, of cache
    entries ``got`` against the reference's ``want`` (each a pair of
    (layers, B, T, ·)): the norm of the difference over the norm of
    ``want``, a layer at a time."""
    worst = 0.0
    for g, w in zip(got, want):
        diff = jnp.sqrt(jnp.sum((_f32(jnp.asarray(g)) - w) ** 2, axis=(1, 2, 3)))
        worst = max(worst, float(jnp.max(diff / jnp.sqrt(jnp.sum(w * w, axis=(1, 2, 3))))))
    return worst


def lower_precision(params, dtype=jnp.float8_e4m3fn):
    """The weights rounded to ``dtype`` and widened back to their own dtype."""
    return jax.tree_util.tree_map(lambda a: a.astype(dtype).astype(a.dtype), params)
