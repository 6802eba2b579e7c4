"""Attention: GQA (full / sliding-window), chunked online-softmax for long
sequences, decode-step with KV cache, and MLA (DeepSeek-V2 latent attention).

Memory strategy: training/prefill always run the chunked (flash-style)
double-scan — scores never materialize beyond (q_block × kv_block) per
step — so 32 k prefill fits without attention kernels; decode computes
one-row attention against the cache (linear in cache length).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from . import layers

Array = jax.Array
NEG_INF = -1e30


def init_gqa(key, cfg):
    """cfg: needs d_model, n_heads, n_kv_heads, head_dim, qkv_bias."""
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, hd = cfg.d_model, cfg.head_dim
    p = {
        "wq": layers.init_dense(kq, d, cfg.n_heads * hd, cfg.dtype, bias=cfg.qkv_bias),
        "wk": layers.init_dense(kk, d, cfg.n_kv_heads * hd, cfg.dtype, bias=cfg.qkv_bias),
        "wv": layers.init_dense(kv, d, cfg.n_kv_heads * hd, cfg.dtype, bias=cfg.qkv_bias),
        "wo": layers.init_dense(ko, cfg.n_heads * hd, d, cfg.dtype),
    }
    return p


def _qkv(p, x, cfg, positions, pos_thw=None):
    B, S, _ = x.shape
    hd = cfg.head_dim
    q = layers.dense(p["wq"], x).reshape(B, S, cfg.n_heads, hd)
    k = layers.dense(p["wk"], x).reshape(B, S, cfg.n_kv_heads, hd)
    v = layers.dense(p["wv"], x).reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.mrope and pos_thw is not None:
        q = layers.apply_mrope(q, pos_thw, cfg.rope_theta, cfg.mrope_sections)
        k = layers.apply_mrope(k, pos_thw, cfg.rope_theta, cfg.mrope_sections)
    elif cfg.use_rope:
        q = layers.apply_rope(q, positions, cfg.rope_theta, cfg.yarn_factor)
        k = layers.apply_rope(k, positions, cfg.rope_theta, cfg.yarn_factor)
    return q, k, v


import functools


def _block_mask(qpos, kpos, S, causal, window):
    mask = kpos[None, :] < S                       # padding
    if causal:
        mask = mask & (kpos[None, :] <= qpos[:, None])
    if window > 0:
        mask = mask & (kpos[None, :] > qpos[:, None] - window)
    return mask


def _flash_fwd_impl(q, k, v, S, causal, window, q_block, kv_block):
    """Returns (out (nq,B,G,rep,qb,hdv), lse (nq,B,G,rep,qb))."""
    B, Sq, H, hd = q.shape
    G = k.shape[2]
    hd_v = v.shape[-1]
    rep = H // G
    nq, nk = Sq // q_block, k.shape[1] // kv_block
    scale = hd ** -0.5
    qr = q.reshape(B, nq, q_block, G, rep, hd)
    kr = k.reshape(B, nk, kv_block, G, hd)
    vr = v.reshape(B, nk, kv_block, G, hd_v)

    def q_step(_, qi):
        qb = qr[:, qi] * scale
        qpos = qi * q_block + jnp.arange(q_block)

        def kv_step(carry, kj):
            m, l, acc = carry
            kb, vb = kr[:, kj], vr[:, kj]
            kpos = kj * kv_block + jnp.arange(kv_block)
            s = jnp.einsum("bqgrd,bkgd->bgrqk", qb, kb,
                           preferred_element_type=jnp.float32)
            mask = _block_mask(qpos, kpos, S, causal, window)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bgrqk,bkgd->bgrqd", p.astype(vb.dtype), vb,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, G, rep, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, G, rep, q_block), jnp.float32)
        a0 = jnp.zeros((B, G, rep, q_block, hd_v), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk))
        out = (acc / jnp.maximum(l[..., None], 1e-30)).astype(q.dtype)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))   # (B,G,rep,qb)
        return None, (out, lse)

    _, (outs, lses) = jax.lax.scan(q_step, None, jnp.arange(nq))
    return outs, lses


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_core(q, k, v, S, causal, window, q_block, kv_block):
    outs, _ = _flash_fwd_impl(q, k, v, S, causal, window, q_block, kv_block)
    return outs


def _flash_core_fwd(q, k, v, S, causal, window, q_block, kv_block):
    outs, lses = _flash_fwd_impl(q, k, v, S, causal, window, q_block, kv_block)
    return outs, (q, k, v, outs, lses)


def _flash_core_bwd(S, causal, window, q_block, kv_block, res, douts):
    """FlashAttention-2-style backward: recompute block probabilities from
    the saved logsumexp instead of storing O(nq*nk*qb*kb) probability and
    mask tensors (observed ~10 GiB/layer at 4k before this)."""
    q, k, v, outs, lses = res
    B, Sq, H, hd = q.shape
    G = k.shape[2]
    hd_v = v.shape[-1]
    rep = H // G
    nq, nk = Sq // q_block, k.shape[1] // kv_block
    scale = hd ** -0.5
    qr = q.reshape(B, nq, q_block, G, rep, hd)
    kr = k.reshape(B, nk, kv_block, G, hd)
    vr = v.reshape(B, nk, kv_block, G, hd_v)
    # D_i = rowsum(dout * out): (nq, B, G, rep, qb)
    delta = jnp.sum(douts.astype(jnp.float32) * outs.astype(jnp.float32), axis=-1)

    def kv_step(dq_acc, kj):
        kb, vb = kr[:, kj], vr[:, kj]
        kpos = kj * kv_block + jnp.arange(kv_block)

        def q_step(carry, qi):
            dk_j, dv_j = carry
            qb = qr[:, qi] * scale
            qpos = qi * q_block + jnp.arange(q_block)
            s = jnp.einsum("bqgrd,bkgd->bgrqk", qb, kb,
                           preferred_element_type=jnp.float32)
            mask = _block_mask(qpos, kpos, S, causal, window)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            p = jnp.exp(s - lses[qi][..., None])             # (B,G,rep,qb,kb)
            do = douts[qi].astype(jnp.float32)               # (B,G,rep,qb,hdv)
            dv_blk = jnp.einsum("bgrqk,bgrqd->bkgd", p, do)
            dp = jnp.einsum("bgrqd,bkgd->bgrqk", do, vb.astype(jnp.float32))
            ds = p * (dp - delta[qi][..., None])
            dq_blk = jnp.einsum("bgrqk,bkgd->bqgrd", ds,
                                kb.astype(jnp.float32)) * scale
            dk_blk = jnp.einsum("bgrqk,bqgrd->bkgd", ds,
                                qr[:, qi].astype(jnp.float32)) * scale
            return (dk_j + dk_blk, dv_j + dv_blk), dq_blk

        z_dk = jnp.zeros((B, kv_block, G, hd), jnp.float32)
        z_dv = jnp.zeros((B, kv_block, G, hd_v), jnp.float32)
        (dk_j, dv_j), dq_contrib = jax.lax.scan(
            q_step, (z_dk, z_dv), jnp.arange(nq)
        )
        return dq_acc + dq_contrib, (dk_j, dv_j)

    dq0 = jnp.zeros((nq, B, q_block, G, rep, hd), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(kv_step, dq0, jnp.arange(nk))
    dq = dq.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, G * rep, hd)
    dk = dks.transpose(1, 0, 2, 3, 4).reshape(B, nk * kv_block, G, hd)
    dv = dvs.transpose(1, 0, 2, 3, 4).reshape(B, nk * kv_block, G, hd_v)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention(
    q: Array,
    k: Array,
    v: Array,
    *,
    causal: bool,
    window: int = 0,
    q_block: int = 512,
    kv_block: int = 1024,
) -> Array:
    """Chunked online-softmax attention with a FlashAttention-2-style
    custom VJP (backward recomputes probabilities blockwise from the saved
    logsumexp; plain autodiff of the double scan saves O(S^2/blocks)
    probability/mask tensors).

    q: (B, S, H, hd); k: (B, S, G, hd); v: (B, S, G, hd_v) with H % G == 0
    (hd_v may differ from hd - MLA has 192-dim qk, 128-dim v).
    window > 0 = sliding-window attention (causal, kpos > qpos - window).
    """
    B, S, H, hd = q.shape
    hd_v = v.shape[-1]
    q_block = min(q_block, S)
    kv_block = min(kv_block, S)
    pad_q = (-S) % q_block
    pad_k = (-S) % kv_block
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    Sq = S + pad_q
    outs = _flash_core(q, k, v, S, causal, window, q_block, kv_block)
    # outs: (nq, B, G, rep, qb, hd_v) -> (B, S, H, hd_v)
    out = outs.transpose(1, 2, 3, 0, 4, 5).reshape(B, H, Sq, hd_v)[:, :, :S]
    return jnp.moveaxis(out, 1, 2)


def dense_attention(
    q: Array, k: Array, v: Array, *, causal: bool, window: int = 0
) -> Array:
    """Unblocked masked attention — flop-identical to the masked flash path
    (every S×S block is computed there too), with all einsums outside any
    scan.  Used by the dry-run's accounting variant, where lax.scan bodies
    would be cost-counted once (see launch/dryrun.py)."""
    B, S, H, hd = q.shape
    G = k.shape[2]
    rep = H // G
    qr = q.reshape(B, S, G, rep, hd) * hd ** -0.5
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qr, k, preferred_element_type=jnp.float32)
    pos = jnp.arange(S)
    mask = jnp.ones((S, S), bool)
    if causal:
        mask &= pos[None, :] <= pos[:, None]
    if window > 0:
        mask &= pos[None, :] > pos[:, None] - window
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bgrqk,bkgd->bqgrd", w.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)
    return out.reshape(B, S, H, v.shape[-1])


def _attend(q, k, v, cfg, *, causal):
    if cfg.attn_impl == "dense":
        return dense_attention(q, k, v, causal=causal, window=cfg.window)
    return flash_attention(
        q, k, v, causal=causal, window=cfg.window,
        q_block=cfg.q_block, kv_block=cfg.kv_block,
    )


def gqa_train(p, x, cfg, positions, pos_thw=None) -> Array:
    q, k, v = _qkv(p, x, cfg, positions, pos_thw)
    out = _attend(q, k, v, cfg, causal=not cfg.encoder_only)
    B, S = x.shape[:2]
    return layers.dense(p["wo"], out.reshape(B, S, -1))


class KVCache(NamedTuple):
    k: Array      # (B, L, G, hd)
    v: Array      # (B, L, G, hd)


def init_kv_cache(cfg, batch: int, length: int, n_layers: int) -> KVCache:
    shape = (n_layers, batch, length, cfg.n_kv_heads, cfg.head_dim)
    return KVCache(jnp.zeros(shape, jnp.bfloat16), jnp.zeros(shape, jnp.bfloat16))


def gqa_decode(p, x, cache_k, cache_v, pos, cfg) -> Tuple[Array, Array, Array]:
    """One-token decode. x: (B, 1, D); cache_[kv]: (B, L, G, hd); pos: int32[].

    Returns (out (B, 1, D), k_new (B, 1, G, hd), v_new) — the cache itself
    is READ-ONLY here; the caller writes all layers' new-token slots with a
    single dynamic_update_slice outside the layer scan.  (Threading the
    multi-GiB cache stacks through scan ys made XLA materialize f32 copies
    of the whole cache — §Perf cell 2.)  The new token attends to itself via
    an explicit extra score column; a ring buffer wraps at L (= window for
    SWA archs), and the stale slot being replaced is masked out.
    """
    B, _, _ = x.shape
    hd = cfg.head_dim
    L = cache_k.shape[1]
    q = layers.dense(p["wq"], x).reshape(B, 1, cfg.n_heads, hd)
    k = layers.dense(p["wk"], x).reshape(B, 1, cfg.n_kv_heads, hd)
    v = layers.dense(p["wv"], x).reshape(B, 1, cfg.n_kv_heads, hd)
    if cfg.use_rope:
        posb = jnp.full((B, 1), pos, jnp.int32)
        q = layers.apply_rope(q, posb, cfg.rope_theta, cfg.yarn_factor)
        k = layers.apply_rope(k, posb, cfg.rope_theta, cfg.yarn_factor)

    from repro.distributed.sharding import axis_size, lshard

    G = cfg.n_kv_heads
    rep = cfg.n_heads // G
    slot = (pos % L).astype(jnp.int32)
    qr = q.reshape(B, G, rep, hd) * hd ** -0.5
    # Score/context constraints must MATCH the cache layout
    # (serve.decode_state_specs): kv-head-sharded when G divides the model
    # axis, else cache-length-sharded.  A mismatched constraint makes GSPMD
    # "involuntarily rematerialize" (all-gather) the whole cache per layer
    # (§Perf cell 2).
    g_sharded = G % max(axis_size("model"), 1) == 0
    s = jnp.einsum("bgrd,blgd->bgrl", qr, cache_k, preferred_element_type=jnp.float32)
    if g_sharded:
        s = lshard(s, "batch", "kv_heads", None, None)
    else:
        s = lshard(s, "batch", None, None, "seq_sp")
    s_self = jnp.einsum("bgrd,bogd->bgro", qr, k, preferred_element_type=jnp.float32)
    idx = jnp.arange(L)
    written = jnp.where(pos >= L, idx != slot, idx < pos)
    s = jnp.where(written[None, None, None, :], s, NEG_INF)
    lse_c = jax.nn.logsumexp(s, axis=-1, keepdims=True)
    lse = jnp.logaddexp(lse_c, jax.nn.logsumexp(s_self, axis=-1, keepdims=True))
    w_cache = jnp.exp(s - lse)
    w_self = jnp.exp(s_self - lse)
    ctx = jnp.einsum(
        "bgrl,blgd->bgrd", w_cache.astype(cache_v.dtype), cache_v,
        preferred_element_type=jnp.float32,
    )
    ctx = lshard(ctx, "batch", "kv_heads" if g_sharded else None, None, None)
    ctx = ctx + jnp.einsum(
        "bgro,bogd->bgrd", w_self.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    out = layers.dense(p["wo"], ctx.reshape(B, 1, cfg.n_heads * hd).astype(x.dtype))
    return out, k.astype(cache_k.dtype), v.astype(cache_v.dtype)


# ---------------------------------------------------------------------------
# MLA — Multi-head Latent Attention (DeepSeek-V2 §2.1)
# ---------------------------------------------------------------------------

def init_mla(key, cfg):
    """MLA weights.  Queries take the low-rank path (``w_dq`` →
    ``q_norm`` → ``w_uq``) when ``cfg.q_lora_rank`` is set, else one
    direct projection ``w_q`` (d_model → H·(dn+dr); DeepSeek-V2-Lite's
    ``q_lora_rank: null``).  Keys and values always go through the
    ``kv_lora_rank`` latent, which is what the decode cache holds."""
    d = cfg.d_model
    H = cfg.n_heads
    dq = H * (cfg.qk_nope_dim + cfg.qk_rope_dim)
    ks = jax.random.split(key, 8)
    if cfg.q_lora_rank:
        p = {
            "w_dq": layers.init_dense(ks[0], d, cfg.q_lora_rank, cfg.dtype),
            "q_norm": layers.init_rmsnorm(cfg.q_lora_rank, cfg.dtype),
            "w_uq": layers.init_dense(ks[1], cfg.q_lora_rank, dq, cfg.dtype),
        }
    else:
        p = {"w_q": layers.init_dense(ks[1], d, dq, cfg.dtype)}
    p.update({
        "w_dkv": layers.init_dense(ks[2], d, cfg.kv_lora_rank, cfg.dtype),
        "kv_norm": layers.init_rmsnorm(cfg.kv_lora_rank, cfg.dtype),
        "w_uk": layers.init_dense(ks[3], cfg.kv_lora_rank, H * cfg.qk_nope_dim, cfg.dtype),
        "w_uv": layers.init_dense(ks[4], cfg.kv_lora_rank, H * cfg.v_head_dim, cfg.dtype),
        "w_kr": layers.init_dense(ks[5], d, cfg.qk_rope_dim, cfg.dtype),
        "wo": layers.init_dense(ks[6], H * cfg.v_head_dim, d, cfg.dtype),
    })
    return p


def _mla_query(p, x, cfg):
    """Queries (..., H·(dn+dr)) through q-LoRA or the direct ``w_q``."""
    if "w_q" in p:
        return layers.dense(p["w_q"], x)
    cq = layers.rmsnorm(p["q_norm"], layers.dense(p["w_dq"], x), cfg.norm_eps)
    return layers.dense(p["w_uq"], cq)


def mla_softmax_mscale(cfg) -> float:
    """Factor on MLA's softmax scale ``(dn+dr)^-0.5``: YaRN's
    ``mscale(factor, mscale_all_dim)²`` where the config sets both, else 1."""
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        return layers.yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return 1.0


def _mla_qkv(p, x, cfg, positions):
    B, S, _ = x.shape
    H, dn, dr, dv = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    q = _mla_query(p, x, cfg).reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = layers.apply_rope(q_rope, positions, cfg.rope_theta, cfg.yarn_factor)

    from repro.distributed.sharding import lshard

    c_kv = layers.rmsnorm(p["kv_norm"], layers.dense(p["w_dkv"], x), cfg.norm_eps)   # (B,S,r)
    c_kv = lshard(c_kv, "batch", None, None)       # latent replicated over TP
    k_rope = layers.dense(p["w_kr"], x).reshape(B, S, 1, dr)
    k_rope = layers.apply_rope(k_rope, positions, cfg.rope_theta, cfg.yarn_factor)  # shared head
    k_nope = layers.dense(p["w_uk"], c_kv).reshape(B, S, H, dn)
    val = layers.dense(p["w_uv"], c_kv).reshape(B, S, H, dv)
    # pin head sharding through attention: the up-projections' outputs are
    # H-sharded (column-parallel); without the constraints GSPMD mixes
    # H-sharded and SP-seq-sharded layouts in backward and materializes
    # (B,H,r,S)-sized f32 reshard buffers (§Perf cell 1)
    k_nope = lshard(k_nope, "batch", None, "heads", None)
    val = lshard(val, "batch", None, "heads", None)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    q_full = lshard(q_full, "batch", None, "heads", None)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (B, S, H, dr))], axis=-1
    )
    return q_full, k_full, val, c_kv, k_rope


def mla_train(p, x, cfg, positions) -> Array:
    q, k, v, _, _ = _mla_qkv(p, x, cfg, positions)
    m = mla_softmax_mscale(cfg)
    if m != 1.0:                # the attention kernels scale by (dn+dr)^-0.5
        q = (q.astype(jnp.float32) * m).astype(q.dtype)
    out = _attend(q, k, v, cfg, causal=True)
    B, S = x.shape[:2]
    return layers.dense(p["wo"], out.reshape(B, S, -1))


def init_mla_cache(cfg, batch: int, length: int, n_layers: int):
    """MLA caches the compressed latent + shared rope key — the whole point
    of MLA is this tiny cache: (kv_lora + rope) per token vs 2·H·hd."""
    return {
        "c_kv": jnp.zeros((n_layers, batch, length, cfg.kv_lora_rank), jnp.bfloat16),
        "k_rope": jnp.zeros((n_layers, batch, length, cfg.qk_rope_dim), jnp.bfloat16),
    }


def mla_decode(p, x, c_kv_cache, k_rope_cache, pos, cfg):
    """Absorbed-matmul decode: scores/context via the latent space directly.

    x: (B, 1, D); c_kv_cache: (B, L, r); k_rope_cache: (B, L, dr).
    Cache is read-only; returns (out, c_kv_new (B,1,r), k_rope_new (B,1,dr))
    for the caller's single out-of-scan slot write (see gqa_decode).
    """
    B = x.shape[0]
    H, dn, dr, dv, r = (
        cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim, cfg.kv_lora_rank
    )
    L = c_kv_cache.shape[1]
    q = _mla_query(p, x, cfg).reshape(B, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    posb = jnp.full((B, 1), pos, jnp.int32)
    q_rope = layers.apply_rope(q_rope.reshape(B, 1, H, dr), posb, cfg.rope_theta,
                              cfg.yarn_factor).reshape(B, H, dr)

    c_kv_new = layers.rmsnorm(p["kv_norm"], layers.dense(p["w_dkv"], x), cfg.norm_eps)  # (B,1,r)
    k_rope_new = layers.apply_rope(
        layers.dense(p["w_kr"], x).reshape(B, 1, 1, dr), posb, cfg.rope_theta, cfg.yarn_factor
    ).reshape(B, 1, dr)
    slot = (pos % L).astype(jnp.int32)

    # Absorb W_uk into the query: q_lat (B, H, r).  fp32 here: absorption
    # reassociates the train-side matmul chain, so keep the extra rounding
    # out of the (tiny) per-token absorbed products.
    w_uk = p["w_uk"]["w"].reshape(r, H, dn).astype(jnp.float32)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope.astype(jnp.float32), w_uk).astype(jnp.bfloat16)
    s = jnp.einsum("bhr,blr->bhl", q_lat, c_kv_cache, preferred_element_type=jnp.float32)
    s = s + jnp.einsum("bhd,bld->bhl", q_rope.astype(jnp.bfloat16), k_rope_cache,
                       preferred_element_type=jnp.float32)
    s_self = jnp.einsum("bhr,bor->bho", q_lat, c_kv_new.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
    s_self = s_self + jnp.einsum(
        "bhd,bod->bho", q_rope.astype(jnp.bfloat16), k_rope_new.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    )
    from repro.distributed.sharding import lshard

    scale = (dn + dr) ** -0.5 * mla_softmax_mscale(cfg)
    idx = jnp.arange(L)
    written = jnp.where(pos >= L, idx != slot, idx < pos)
    s = jnp.where(written[None, None, :], s * scale, NEG_INF)
    s = lshard(s, "batch", None, "seq_sp")        # keep length-sharded
    s_self = s_self * scale
    lse = jnp.logaddexp(
        jax.nn.logsumexp(s, axis=-1, keepdims=True),
        jax.nn.logsumexp(s_self, axis=-1, keepdims=True),
    )
    w_cache = jnp.exp(s - lse)
    w_self = jnp.exp(s_self - lse)
    ctx_lat = jnp.einsum("bhl,blr->bhr", w_cache.astype(jnp.bfloat16), c_kv_cache,
                         preferred_element_type=jnp.float32)
    ctx_lat = lshard(ctx_lat, "batch", None, None)
    ctx_lat = ctx_lat + jnp.einsum(
        "bho,bor->bhr", w_self.astype(jnp.bfloat16), c_kv_new.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    )
    w_uv = p["w_uv"]["w"].reshape(r, H, dv).astype(jnp.bfloat16)
    ctx = jnp.einsum("bhr,rhv->bhv", ctx_lat.astype(jnp.bfloat16), w_uv)
    out = layers.dense(p["wo"], ctx.reshape(B, 1, H * dv))
    return out, c_kv_new.astype(c_kv_cache.dtype), k_rope_new.astype(k_rope_cache.dtype)
