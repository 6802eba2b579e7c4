"""Mixture-of-Experts: top-k router + shard-local sorted capacity dispatch.

What a layer computes: a softmax router over all ``n_experts`` picks the
top ``experts_per_token`` per token (gates renormalised to sum 1 unless
``norm_topk_prob`` is off); the layer holds the weights of the first
``n_experts_held`` of them (one chip's share under expert parallelism,
its experts ordered first in its router) and adds the gated outputs of
its own experts only, plus the shared experts, which every holder
computes.
Where the held block is all of the router's experts this is the whole
layer.  A forward drops tokens past each expert's capacity; decode drops
none.

Design notes (the two decisions that dominate MoE roofline behaviour):

* **Shard-local dispatch.** Token sorting/dispatch happens independently
  per data shard: tokens reshape to ``(dispatch_shards, T_loc, D)`` with
  dim 0 sharded over the batch mesh axes, and the sort/scatter/gather run
  under ``jax.vmap`` over that dim.  GSPMD keeps every per-row op local —
  a *global* argsort over 10⁶ tokens would otherwise lower to all-gathers
  of the whole activation buffer (observed: >100 GiB/device before this
  change).  This is the standard per-shard dispatch of production MoE
  stacks.
* **Capacity-based dropping, not dense all-experts einsum.** HLO FLOPs stay
  ≈ active FLOPs × capacity_factor, keeping the roofline's useful-compute
  ratio honest for 64–160-expert models.

Expert weights carry a leading E axis sharded over 'model' (expert
parallelism); the ``lshard`` on the dispatch buffer makes GSPMD insert the
token all-to-all at the dispatch/combine boundaries.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from . import layers

Array = jax.Array


def init_moe(key, cfg):
    """Experts as stacked SwiGLU: (E_held, d_model, moe_d_ff) /
    (E_held, moe_d_ff, d_model) for the held block; the router is
    (d_model, n_experts) fp32, the router's full width."""
    kr, ke, ks = jax.random.split(key, 3)
    E, d, f = cfg.n_experts, cfg.d_model, cfg.moe_d_ff
    expert_keys = jax.random.split(ke, cfg.n_experts_held)
    experts = jax.vmap(
        lambda k: layers.init_swiglu(k, d, f, cfg.dtype)
    )(expert_keys)
    p = {
        "router": layers.init_dense(kr, d, E, jnp.float32),
        "experts": experts,
    }
    if cfg.n_shared_experts:
        p["shared"] = layers.init_swiglu(
            ks, d, f * cfg.n_shared_experts, cfg.dtype
        )
    return p


def _dispatch_one(xt: Array, idx: Array, C: int, E: int):
    """One shard: build the (E, C, D) expert buffer by GATHER.

    xt: (T, D); idx: (T, K). Returns (buf, sort, pos) where ``sort`` is the
    token-expert permutation and ``pos`` the capacity slot (−1 = dropped).

    Gather-based construction (slot → source token) instead of scatter-add
    (token → slot): XLA float-normalizes bf16 scatters to f32, which
    materialized f32 (E,C,D) buffers (10 GiB/layer for deepseek); with the
    gather form the only scatter left is the backward into the K×-smaller
    (T,D) token gradient (§Perf C5).
    """
    T, K = idx.shape
    flat_e = idx.reshape(-1)
    sort = jnp.argsort(flat_e)                  # local, stable
    sorted_e = flat_e[sort]
    bounds = jnp.searchsorted(sorted_e, jnp.arange(E + 1))
    group_start, group_end = bounds[:-1], bounds[1:]
    pos_in_group = jnp.arange(T * K) - group_start[sorted_e]
    keep = (pos_in_group < C) & (sorted_e < E)  # idx == E: an expert not held

    # slot (e, c) ← token sort[group_start[e] + c] when c < group size
    slot_src = group_start[:, None] + jnp.arange(C)[None, :]      # (E, C)
    valid = slot_src < group_end[:, None]
    src_tok = sort[jnp.clip(slot_src, 0, T * K - 1)] // K
    buf = jnp.where(valid[..., None], xt[src_tok], 0)
    return buf, sort, jnp.where(keep, pos_in_group, -1)


def _combine_rows(out_e, sort, pos, idx, gate):
    sorted_e = idx.reshape(-1)[sort]
    keep = pos >= 0
    rows = out_e[sorted_e, jnp.where(keep, pos, 0)]
    rows = jnp.where(keep[:, None], rows, 0)
    unsort = jnp.argsort(sort)
    return rows[unsort].reshape(-1, gate.shape[-1], out_e.shape[-1])


@jax.custom_vjp
def _combine_one(out_e: Array, sort: Array, pos: Array, idx: Array, gate: Array):
    """One shard: gather expert outputs back to token order, gate-mix.

    Custom VJP: the slot→token map is injective (each (e, c) slot holds at
    most one token), so d(out_e) is a pure GATHER of the token cotangents —
    plain autodiff would scatter-add into an (E, C, D) buffer, which XLA
    float-normalizes into multi-GiB f32 temporaries (§Perf C5)."""
    contrib = _combine_rows(out_e, sort, pos, idx, gate)
    return jnp.sum(contrib * gate[..., None].astype(contrib.dtype), axis=1)


def _combine_one_fwd(out_e, sort, pos, idx, gate):
    return _combine_one(out_e, sort, pos, idx, gate), (out_e, sort, pos, idx, gate)


def _combine_one_bwd(res, dy):
    out_e, sort, pos, idx, gate = res
    T, K = gate.shape
    E, C, D = out_e.shape
    sorted_e = idx.reshape(-1)[sort]
    keep = pos >= 0
    # d_gate needs the forward rows — recompute by gather (cheap)
    contrib = _combine_rows(out_e, sort, pos, idx, gate)
    d_gate = jnp.sum(
        contrib.astype(jnp.float32) * dy[:, None, :].astype(jnp.float32), axis=-1
    ).astype(gate.dtype)
    # token cotangents in sorted order
    d_rows = (dy[:, None, :] * gate[..., None].astype(dy.dtype)).reshape(T * K, D)
    d_rows_sorted = jnp.where(keep[:, None], d_rows[sort], 0)
    # d_out_e[e, c] = d_rows_sorted[group_start[e] + c] when the slot is live
    bounds = jnp.searchsorted(sorted_e, jnp.arange(E + 1))
    slot_src = bounds[:-1][:, None] + jnp.arange(C)[None, :]
    valid = slot_src < bounds[1:][:, None]
    d_out_e = jnp.where(
        valid[..., None],
        d_rows_sorted[jnp.clip(slot_src, 0, T * K - 1)],
        0,
    ).astype(out_e.dtype)
    return d_out_e, None, None, None, d_gate


_combine_one.defvjp(_combine_one_fwd, _combine_one_bwd)


def moe_apply(p, x: Array, cfg) -> Tuple[Array, Array]:
    """x: (B, S, D) → (y (B, S, D), aux_loss scalar).

    Routes over all ``cfg.n_experts`` and adds the gated outputs of the
    held experts only (the router's first ``n_experts_held``; a token's
    other picks belong to other holders and contribute nothing here), then
    the shared experts.  Gates are the top-k softmax probabilities,
    renormalised over the k when ``cfg.norm_topk_prob``.  In decode (``S``
    of 1) each held expert's capacity is the token count, so no token is
    dropped; in every other forward it is ``int(T·K/E·capacity_factor) + 1``
    and overflow is dropped.
    """
    from repro.distributed.sharding import lshard

    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.experts_per_token
    E_held = cfg.n_experts_held
    DS = max(1, cfg.dispatch_shards)
    T = B * S
    assert T % DS == 0, (T, DS)
    T_loc = T // DS
    xt = lshard(x.reshape(DS, T_loc, D), "batch", None, None)

    logits = layers.dense(p["router"], xt, compute_dtype=jnp.float32)  # (DS,T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, K)                                # (DS,T,K)
    if cfg.norm_topk_prob:
        gate = gate / jnp.maximum(jnp.sum(gate, axis=-1, keepdims=True), 1e-9)

    # Switch-style load-balance aux loss (global means — cheap scalars)
    me = jnp.mean(probs, axis=(0, 1))
    ce = jnp.mean(jax.nn.one_hot(idx[..., 0], E, dtype=jnp.float32), axis=(0, 1))
    aux = E * jnp.sum(me * ce)

    if E_held != E:
        # picks of experts not held go to slot E_held, which the dispatch
        # never keeps
        idx = jnp.minimum(idx, E_held)

    # decode (one position a sequence) drops no token: a token picks K
    # distinct experts, so T_loc slots hold all of an expert's tokens
    C = T_loc if S == 1 else int(T_loc * K / E * cfg.capacity_factor) + 1

    buf, sort, pos = jax.vmap(lambda xx, ii: _dispatch_one(xx, ii, C, E_held))(xt, idx)
    # Scatter stays token-sharded (local; replicated within the model group).
    buf = lshard(buf, "batch", None, None, None)
    # EP layout is token-count-adaptive (§Perf):
    #  * train (T_loc large): experts over 'model'; slicing the
    #    group-replicated buffer is free, the combine re-shard carries
    #    ≈ capacity_factor × the optimal all-to-all;
    #  * decode (T_loc tiny): experts over 'data' matching the serving
    #    weight layout (serve/step.inference_param_specs) — the tiny token
    #    buffers all-to-all to the experts and back, weights never move
    #    (the train layout would all-gather GiBs of expert weights per
    #    layer to process a handful of tokens).
    serving = T_loc < 4096
    e_axis = "experts_serve" if serving else "experts"
    bufE = lshard(buf, None if serving else "batch", e_axis, None, None)

    we = p["experts"]
    h = jnp.einsum("secd,edf->secf", bufE.astype(jnp.bfloat16),
                   we["w_gate"].astype(jnp.bfloat16))
    u = jnp.einsum("secd,edf->secf", bufE.astype(jnp.bfloat16),
                   we["w_up"].astype(jnp.bfloat16))
    act = jax.nn.silu(h) * u
    out_e = jnp.einsum("secf,efd->secd", act, we["w_down"].astype(jnp.bfloat16))
    if serving:
        out_e = lshard(out_e, None, e_axis, None, None)
    else:
        out_e = lshard(out_e, "batch", None, None, None)

    y = jax.vmap(_combine_one)(out_e, sort, pos, idx, gate)   # (DS, T_loc, D)
    y = lshard(y, "batch", None, None)

    if "shared" in p:
        y = y + layers.swiglu(p["shared"], xt)
    return y.reshape(B, S, D).astype(x.dtype), aux
