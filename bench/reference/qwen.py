"""Plain Qwen1.5 (Qwen2 architecture) decoder over a given cache prefix.

Written from the published architecture, not from the program: RMSNorm,
attention with biased q/k/v projections and rotary embeddings (rotate-half,
theta from the config), SwiGLU MLP, untied head.  Everything is float32 at
``highest`` matmul precision.  The cache prefix holds keys (already
rotated, as a decode cache stores them) and values for positions
``0 .. P-1``; ``tokens`` continue at position ``P``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv              # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _mm(x, w):
    return jnp.matmul(x, w.astype(jnp.float32), precision=HI)


@partial(jax.jit, static_argnames=("heads", "theta", "eps"))
def trunk(layers, final_g, embed, prefix_k, prefix_v, tokens, *, heads, theta, eps):
    """Final-normed hidden states (B, T, D) of ``tokens`` (B, T)."""
    B, T = tokens.shape
    P = prefix_k.shape[2]
    pos = P + jnp.arange(T)
    x = embed.astype(jnp.float32)[tokens]
    n_layers = layers["attn_norm"]["g"].shape[0]
    causal = jnp.tril(jnp.ones((T, T), bool))
    for i in range(n_layers):
        lp = jax.tree_util.tree_map(lambda a: a[i], layers)
        at = lp["attn"]
        h = _rms(x, lp["attn_norm"]["g"].astype(jnp.float32), eps)
        q = (_mm(h, at["wq"]["w"]) + at["wq"]["b"].astype(jnp.float32)).reshape(B, T, heads, -1)
        k = (_mm(h, at["wk"]["w"]) + at["wk"]["b"].astype(jnp.float32)).reshape(B, T, heads, -1)
        v = (_mm(h, at["wv"]["w"]) + at["wv"]["b"].astype(jnp.float32)).reshape(B, T, heads, -1)
        hd = q.shape[-1]
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        pk = prefix_k[i].astype(jnp.float32)                   # (B, P, H, hd)
        pv = prefix_v[i].astype(jnp.float32)
        s_pre = jnp.einsum("bthd,bphd->bhtp", q, pk, precision=HI) * hd ** -0.5
        s_new = jnp.einsum("bthd,bshd->bhts", q, k, precision=HI) * hd ** -0.5
        s_new = jnp.where(causal[None, None], s_new, -jnp.inf)
        s = jax.nn.softmax(jnp.concatenate([s_pre, s_new], axis=-1), axis=-1)
        ctx = (jnp.einsum("bhtp,bphd->bthd", s[..., :P], pv, precision=HI)
               + jnp.einsum("bhts,bshd->bthd", s[..., P:], v, precision=HI))
        x = x + _mm(ctx.reshape(B, T, heads * hd), at["wo"]["w"])
        h = _rms(x, lp["mlp_norm"]["g"].astype(jnp.float32), eps)
        m = lp["mlp"]
        x = x + _mm(jax.nn.silu(_mm(h, m["w_gate"])) * _mm(h, m["w_up"]), m["w_down"])
    return _rms(x, final_g.astype(jnp.float32), eps)


@jax.jit
def gaps(head, hidden, served):
    """Per position: the reference's best logit minus its logit of the
    served token (>= 0), and the reference's best token."""
    logits = jnp.einsum("btd,vd->btv", hidden, head.astype(jnp.float32), precision=HI)
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, served[..., None], axis=-1)[..., 0]
    return best - got, jnp.argmax(logits, axis=-1).astype(jnp.int32)


def served_gap(params, prefix_k, prefix_v, fed, served, cfg, block: int = 16):
    """Widest gap over all positions by which a served token's logit lies
    below the reference's best.  ``fed``/``served``: (B, T) int32."""
    hidden = trunk(params["layers"], params["final_norm"]["g"], params["embed"]["table"],
                   prefix_k, prefix_v, fed, heads=cfg.n_heads, theta=float(cfg.rope_theta),
                   eps=float(cfg.norm_eps))
    worst = 0.0
    for t in range(0, fed.shape[1], block):
        g, _ = gaps(params["lm_head"]["table"], hidden[:, t:t + block], served[:, t:t + block])
        worst = max(worst, float(jnp.max(g)))
    return worst


def lower_precision(params, dtype=jnp.float8_e4m3fn):
    """The weights rounded to ``dtype`` and widened back."""
    return jax.tree_util.tree_map(lambda a: a.astype(dtype).astype(jnp.float32), params)


def control_gap(params, low, prefix_k, prefix_v, fed, cfg, block: int = 16):
    """Widest gap, under the reference, of the token that the
    lower-precision weights ``low`` put first at each position."""
    kw = dict(heads=cfg.n_heads, theta=float(cfg.rope_theta), eps=float(cfg.norm_eps))
    hid = trunk(params["layers"], params["final_norm"]["g"], params["embed"]["table"],
                prefix_k, prefix_v, fed, **kw)
    hid_low = trunk(low["layers"], low["final_norm"]["g"], low["embed"]["table"],
                    prefix_k, prefix_v, fed, **kw)
    worst = 0.0
    for t in range(0, fed.shape[1], block):
        _, pick = gaps(low["lm_head"]["table"], hid_low[:, t:t + block],
                       jnp.zeros(hid_low[:, t:t + block].shape[:2], jnp.int32))
        g, _ = gaps(params["lm_head"]["table"], hid[:, t:t + block], pick)
        worst = max(worst, float(jnp.max(g)))
    return worst
