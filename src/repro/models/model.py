"""Model: config → init / train-loss / forward / decode-step.

One class serves all six families (dense, moe, ssm, hybrid, vlm, audio):
layer stacks are vmap-initialized and lax.scan-applied; decode threads the
per-layer caches through the same scan.  All full-size instantiation happens
under jax.eval_shape — only reduced configs ever allocate on this host.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import lshard, param_pspecs
from . import attention, blocks, layers

Array = jax.Array
PyTree = Any


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ------------------------------------------------------------------ init

    def init(self, rng: Array) -> PyTree:
        cfg = self.cfg
        r_embed, r_stack, r_head, r_front, r_shared = jax.random.split(rng, 5)
        params: Dict[str, Any] = {}
        params["embed"] = layers.init_embedding(
            r_embed, cfg.vocab_size, cfg.d_model, cfg.dtype
        )
        if cfg.pos_embedding == "learned":
            params["pos"] = {
                "table": (
                    jax.random.normal(r_head, (cfg.max_position, cfg.d_model), jnp.float32)
                    * 0.02
                ).astype(cfg.dtype)
            }
        if cfg.frontend != "none":
            params["frontend_proj"] = layers.init_dense(
                r_front, cfg.frontend_dim, cfg.d_model, cfg.dtype
            )
        params.update(self._init_stacks(r_stack))
        params["final_norm"] = blocks._norm_init(cfg)
        if not cfg.tie_embeddings:
            params["lm_head"] = layers.init_embedding(
                r_head, cfg.vocab_size, cfg.d_model, cfg.dtype
            )
        return params

    def _init_stacks(self, rng: Array) -> Dict[str, Any]:
        cfg = self.cfg
        if cfg.family in ("dense", "vlm", "audio"):
            keys = jax.random.split(rng, cfg.n_layers)
            return {
                "layers": jax.vmap(lambda k: blocks.init_dense_block(k, cfg))(keys)
            }
        if cfg.family == "moe":
            out: Dict[str, Any] = {}
            fk = cfg.first_k_dense
            r1, r2 = jax.random.split(rng)
            if fk:
                keys = jax.random.split(r1, fk)
                out["dense_layers"] = jax.vmap(
                    lambda k: blocks.init_dense_block(k, cfg, d_ff=cfg.dense_d_ff)
                )(keys)
            keys = jax.random.split(r2, cfg.n_layers - fk)
            out["moe_layers"] = jax.vmap(lambda k: blocks.init_moe_block(k, cfg))(keys)
            return out
        if cfg.family == "ssm":
            keys = jax.random.split(rng, cfg.n_layers)
            return {
                "layers": jax.vmap(lambda k: blocks.init_mamba_block(k, cfg))(keys)
            }
        if cfg.family == "hybrid":
            every = cfg.shared_attn_every
            n_groups = cfg.n_layers // every
            tail = cfg.n_layers - n_groups * every
            r1, r2, r3 = jax.random.split(rng, 3)
            gkeys = jax.random.split(r1, (n_groups, every))
            out = {
                "mamba_groups": jax.vmap(
                    jax.vmap(lambda k: blocks.init_mamba_block(k, cfg))
                )(gkeys),
                "shared_attn": blocks.init_dense_block(r3, cfg),
            }
            if tail:
                tkeys = jax.random.split(r2, tail)
                out["mamba_tail"] = jax.vmap(
                    lambda k: blocks.init_mamba_block(k, cfg)
                )(tkeys)
            return out
        raise ValueError(f"unknown family {cfg.family}")

    def abstract_params(self) -> PyTree:
        key = jax.random.key(0)
        return jax.eval_shape(lambda: self.init(key))

    def param_specs(self, mesh=None) -> PyTree:
        return param_pspecs(self.abstract_params(), zero3=self.cfg.zero3, mesh=mesh)

    # --------------------------------------------------------------- forward

    def _remat(self, fn):
        if self.cfg.remat == "full":
            return jax.checkpoint(fn)
        if self.cfg.remat == "dots":
            return jax.checkpoint(
                fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            )
        return fn

    def _sp_shard(self, x: Array) -> Array:
        """Sequence-parallel residual constraint (Megatron-SP via GSPMD):
        the per-layer saved carry shards (batch, seq) over (data, model) —
        without this, L × (B_loc·S·D) saved residuals overflow HBM on the
        deep archs.  GSPMD inserts the all-gather at attention/MLP use."""
        if self.cfg.sp:
            return lshard(x, "batch", "seq_sp", None)
        return x

    def _scan_stack(self, stack: PyTree, x: Array, apply_fn) -> Tuple[Array, Array]:
        base_fn = apply_fn

        def apply_sp(lp, h):
            h, a = base_fn(lp, h)
            return self._sp_shard(h), a

        fn = self._remat(apply_sp)
        if not self.cfg.scan_layers:
            # unrolled: the dry-run's accounting variant (cost_analysis
            # counts lax.scan bodies once — see launch/dryrun.py)
            aux = jnp.zeros((), jnp.float32)
            n = jax.tree_util.tree_leaves(stack)[0].shape[0]
            for i in range(n):
                lp = jax.tree_util.tree_map(lambda a: a[i], stack)
                x, a = fn(lp, x)
                aux = aux + a
            return x, aux

        def body(carry, lp):
            h, aux = carry
            h, a = fn(lp, h)
            return (h, aux + a), None

        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), stack)
        return x, aux

    def forward(self, params: PyTree, batch: Dict[str, Array]) -> Tuple[Array, Array]:
        """Full-sequence forward. Returns (logits, aux_loss)."""
        x, aux = self._trunk(params, batch)
        cfg = self.cfg
        head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
        logits = layers.unembed(head, x)
        logits = lshard(logits, "batch", None, "vocab")
        return logits, aux

    def _trunk(self, params: PyTree, batch: Dict[str, Array]) -> Tuple[Array, Array]:
        """Everything up to (and including) the final norm."""
        cfg = self.cfg
        pos_thw = None
        if cfg.family == "vlm":
            img = layers.dense(params["frontend_proj"], batch["patches"])
            txt = layers.embed(params["embed"], batch["tokens"])
            x = jnp.concatenate([img.astype(jnp.bfloat16), txt], axis=1)
            pos_thw = batch["pos_thw"]
        elif cfg.family == "audio":
            x = layers.dense(params["frontend_proj"], batch["frames"])
        else:
            x = layers.embed(params["embed"], batch["tokens"])
        B, S = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        if cfg.pos_embedding == "learned":
            x = x + params["pos"]["table"][:S][None].astype(x.dtype)
        x = lshard(x, "batch", None, None)

        aux = jnp.zeros((), jnp.float32)
        if cfg.family in ("dense", "vlm", "audio"):
            x, aux = self._scan_stack(
                params["layers"],
                x,
                lambda lp, h: blocks.dense_block_train(lp, h, cfg, positions, pos_thw),
            )
        elif cfg.family == "moe":
            if "dense_layers" in params:
                x, a1 = self._scan_stack(
                    params["dense_layers"],
                    x,
                    lambda lp, h: blocks.dense_block_train(lp, h, cfg, positions),
                )
                aux = aux + a1
            x, a2 = self._scan_stack(
                params["moe_layers"],
                x,
                lambda lp, h: blocks.moe_block_train(lp, h, cfg, positions),
            )
            aux = aux + a2
        elif cfg.family == "ssm":
            x, aux = self._scan_stack(
                params["layers"],
                x,
                lambda lp, h: blocks.mamba_block_train(lp, h, cfg),
            )
        elif cfg.family == "hybrid":
            x, aux = self._hybrid_forward(params, x, positions)

        x = blocks.norm_apply(cfg, params["final_norm"], x)
        return x, aux

    def _hybrid_forward(self, params, x, positions):
        cfg = self.cfg
        shared = params["shared_attn"]
        mamba_fn = self._remat(
            lambda lp, h: self._sp_shard(blocks.mamba_block_train(lp, h, cfg)[0])
        )
        shared_fn = self._remat(
            lambda h: self._sp_shard(
                blocks.dense_block_train(shared, h, cfg, positions)[0]
            )
        )

        if not cfg.scan_layers:
            ng = jax.tree_util.tree_leaves(params["mamba_groups"])[0].shape[0]
            for g in range(ng):
                glp = jax.tree_util.tree_map(lambda a: a[g], params["mamba_groups"])
                ne = jax.tree_util.tree_leaves(glp)[0].shape[0]
                for i in range(ne):
                    lp = jax.tree_util.tree_map(lambda a: a[i], glp)
                    x = mamba_fn(lp, x)
                x = shared_fn(x)
            if "mamba_tail" in params:
                nt = jax.tree_util.tree_leaves(params["mamba_tail"])[0].shape[0]
                for i in range(nt):
                    lp = jax.tree_util.tree_map(lambda a: a[i], params["mamba_tail"])
                    x = mamba_fn(lp, x)
            return x, jnp.zeros((), jnp.float32)

        def group(h, glp):
            def inner(hh, lp):
                return mamba_fn(lp, hh), None

            h, _ = jax.lax.scan(inner, h, glp)
            return shared_fn(h), None

        x, _ = jax.lax.scan(group, x, params["mamba_groups"])
        if "mamba_tail" in params:
            def inner(hh, lp):
                return mamba_fn(lp, hh), None

            x, _ = jax.lax.scan(inner, x, params["mamba_tail"])
        return x, jnp.zeros((), jnp.float32)

    # ------------------------------------------------------------------ loss

    def loss(self, params: PyTree, batch: Dict[str, Array]) -> Tuple[Array, Dict]:
        cfg = self.cfg
        if cfg.family == "vlm":
            # VLM slices text positions out of mixed logits — small model,
            # keep the explicit-logits path.
            logits, aux = self.forward(params, batch)
            s_img = batch["patches"].shape[1]
            s_txt = batch["tokens"].shape[1]
            txt_logits = logits[:, s_img - 1 : s_img - 1 + s_txt]
            ce = layers.cross_entropy(txt_logits, batch["labels"], batch.get("mask"))
        else:
            # fused chunked unembed+CE: (B,S,V) logits never materialize
            x, aux = self._trunk(params, batch)
            head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
            labels = batch["labels"]
            mask = batch.get("mask")
            if mask is None:
                mask = jnp.ones(labels.shape, jnp.float32)
            ce = layers.fused_cross_entropy(
                head["table"], x, labels, mask, cfg.ce_chunks
            )
        total = ce + cfg.aux_loss_coef * aux
        return total, {"ce": ce, "aux": aux}

    # ---------------------------------------------------------------- decode

    def cache_len(self, seq_len: int) -> int:
        if self.cfg.window:
            return min(seq_len, self.cfg.window)
        return seq_len

    def init_decode_state(
        self, batch: int, seq_len: int, start_pos: Optional[int] = None
    ) -> Dict[str, Any]:
        """Decode state with a cache sized for ``seq_len``.

        ``start_pos`` defaults to ``seq_len`` (the dry-run cell semantics:
        a full context already processed, decoding the next token); pass 0
        to generate from scratch.
        """
        cfg = self.cfg
        if not cfg.has_decode:
            raise ValueError(f"{cfg.name} is encoder-only: no decode state")
        L = self.cache_len(seq_len)
        sp = seq_len if start_pos is None else start_pos
        state: Dict[str, Any] = {"pos": jnp.asarray(sp, jnp.int32)}
        nl = cfg.n_layers
        if cfg.family in ("dense", "vlm"):
            if cfg.mla:
                c = attention.init_mla_cache(cfg, batch, L, nl)
                state.update({"mla_ckv": c["c_kv"], "mla_kr": c["k_rope"]})
            else:
                kv = attention.init_kv_cache(cfg, batch, L, nl)
                state.update({"kv_k": kv.k, "kv_v": kv.v})
        elif cfg.family == "moe":
            if cfg.mla:
                c = attention.init_mla_cache(cfg, batch, L, nl)
                state.update({"mla_ckv": c["c_kv"], "mla_kr": c["k_rope"]})
            else:
                kv = attention.init_kv_cache(cfg, batch, L, nl)
                state.update({"kv_k": kv.k, "kv_v": kv.v})
        elif cfg.family == "ssm":
            from . import ssm as ssm_mod

            sc = ssm_mod.init_ssm_cache(cfg, batch, nl)
            state.update({"ssm_state": sc.state, "ssm_conv": sc.conv})
        elif cfg.family == "hybrid":
            from . import ssm as ssm_mod

            every = cfg.shared_attn_every
            n_groups = nl // every
            tail = nl - n_groups * every
            sc = ssm_mod.init_ssm_cache(cfg, batch, n_groups * every)
            state.update(
                {
                    "ssm_state": sc.state.reshape(
                        n_groups, every, *sc.state.shape[1:]
                    ),
                    "ssm_conv": sc.conv.reshape(n_groups, every, *sc.conv.shape[1:]),
                }
            )
            if tail:
                tc = ssm_mod.init_ssm_cache(cfg, batch, tail)
                state.update({"ssm_state_tail": tc.state, "ssm_conv_tail": tc.conv})
            kv = attention.init_kv_cache(cfg, batch, L, n_groups)
            state.update({"kv_k": kv.k, "kv_v": kv.v})
        return state

    def decode_step(
        self, params: PyTree, state: Dict[str, Any], tokens: Array
    ) -> Tuple[Array, Dict[str, Any]]:
        """One token for every sequence. tokens: (B, 1) int32."""
        cfg = self.cfg
        pos = state["pos"]
        x = layers.embed(params["embed"], tokens)
        if cfg.pos_embedding == "learned":
            pe = jax.lax.dynamic_slice_in_dim(
                params["pos"]["table"], jnp.minimum(pos, cfg.max_position - 1), 1
            )
            x = x + pe[None].astype(x.dtype)
        x = lshard(x, "batch", None, None)
        new_state = dict(state)

        if cfg.family in ("dense", "vlm", "moe"):
            fk = cfg.first_k_dense if cfg.family == "moe" else 0
            c0, c1 = (
                (state["mla_ckv"], state["mla_kr"])
                if cfg.mla
                else (state["kv_k"], state["kv_v"])
            )
            Lc = c0.shape[2]
            slot = (pos % Lc).astype(jnp.int32)

            def run(stack, x, caches, block_decode):
                """Caches are read-only scan xs; ys = each layer's new-token
                entries (B, 1, …) — the slot write happens once, below, so
                the multi-GiB stacks never thread through scan carries/ys."""
                if not cfg.scan_layers:
                    # The barrier keeps the compiler from fusing across a
                    # layer boundary, so each layer rounds exactly as a
                    # layer-at-a-time executor (the compressed serving
                    # ring) computes it.  On TPU a scan body, or layers
                    # fused together, round differently.
                    n = jax.tree_util.tree_leaves(stack)[0].shape[0]
                    outs0, outs1 = [], []
                    for i in range(n):
                        lp = jax.tree_util.tree_map(lambda a: a[i], stack)
                        x, (u0, u1) = block_decode(
                            lp, x, (caches[0][i], caches[1][i]), pos, cfg
                        )
                        x, u0, u1 = jax.lax.optimization_barrier((x, u0, u1))
                        outs0.append(u0)
                        outs1.append(u1)
                    return x, (jnp.stack(outs0), jnp.stack(outs1))

                def body(h, xs):
                    lp, ck, cv = xs
                    h, news = block_decode(lp, h, (ck, cv), pos, cfg)
                    return h, news

                return jax.lax.scan(body, x, (stack, *caches))

            if cfg.family == "moe":
                if fk:
                    x, (d0, d1) = run(
                        params["dense_layers"], x, (c0[:fk], c1[:fk]),
                        blocks.dense_block_decode,
                    )
                x, (m0, m1) = run(
                    params["moe_layers"], x, (c0[fk:], c1[fk:]),
                    blocks.moe_block_decode,
                )
                n0 = jnp.concatenate([d0, m0]) if fk else m0
                n1 = jnp.concatenate([d1, m1]) if fk else m1
            else:
                x, (n0, n1) = run(
                    params["layers"], x, (c0, c1), blocks.dense_block_decode
                )
            # single slot write for all layers
            if cfg.mla:
                new_state["mla_ckv"] = _slot_write(c0, n0, slot)
                new_state["mla_kr"] = _slot_write(c1, n1, slot)
            else:
                new_state["kv_k"] = _slot_write(c0, n0, slot)
                new_state["kv_v"] = _slot_write(c1, n1, slot)

        elif cfg.family == "ssm":
            if not cfg.scan_layers:
                outs_s, outs_c = [], []
                for i in range(cfg.n_layers):
                    lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
                    x, (st, cv) = blocks.mamba_block_decode(
                        lp, x, (state["ssm_state"][i], state["ssm_conv"][i]), pos, cfg
                    )
                    x, st, cv = jax.lax.optimization_barrier((x, st, cv))
                    outs_s.append(st)
                    outs_c.append(cv)
                new_state.update(
                    {"ssm_state": jnp.stack(outs_s), "ssm_conv": jnp.stack(outs_c)}
                )
            else:
                def body(h, xs):
                    lp, st, cv = xs
                    h, (st, cv) = blocks.mamba_block_decode(lp, h, (st, cv), pos, cfg)
                    return h, (st, cv)

                x, (ns, nc) = jax.lax.scan(
                    body, x, (params["layers"], state["ssm_state"], state["ssm_conv"])
                )
                new_state.update({"ssm_state": ns, "ssm_conv": nc})

        elif cfg.family == "hybrid" and not cfg.scan_layers:
            shared = params["shared_attn"]
            Lc = state["kv_k"].shape[2]
            slot = (pos % Lc).astype(jnp.int32)
            ng = jax.tree_util.tree_leaves(params["mamba_groups"])[0].shape[0]
            gs, gc, gk, gv = [], [], [], []
            for g in range(ng):
                glp = jax.tree_util.tree_map(lambda a: a[g], params["mamba_groups"])
                ne = jax.tree_util.tree_leaves(glp)[0].shape[0]
                ss, cc = [], []
                for i in range(ne):
                    lp = jax.tree_util.tree_map(lambda a: a[i], glp)
                    x, (st, cv) = blocks.mamba_block_decode(
                        lp, x,
                        (state["ssm_state"][g, i], state["ssm_conv"][g, i]),
                        pos, cfg,
                    )
                    ss.append(st)
                    cc.append(cv)
                x, (kn, vn) = blocks.dense_block_decode(
                    shared, x, (state["kv_k"][g], state["kv_v"][g]), pos, cfg
                )
                gs.append(jnp.stack(ss))
                gc.append(jnp.stack(cc))
                gk.append(kn)
                gv.append(vn)
            new_state.update(
                {
                    "ssm_state": jnp.stack(gs),
                    "ssm_conv": jnp.stack(gc),
                    "kv_k": _slot_write(state["kv_k"], jnp.stack(gk), slot),
                    "kv_v": _slot_write(state["kv_v"], jnp.stack(gv), slot),
                }
            )
            if "mamba_tail" in params:
                ts, tc = [], []
                nt = jax.tree_util.tree_leaves(params["mamba_tail"])[0].shape[0]
                for i in range(nt):
                    lp = jax.tree_util.tree_map(lambda a: a[i], params["mamba_tail"])
                    x, (st, cv) = blocks.mamba_block_decode(
                        lp, x,
                        (state["ssm_state_tail"][i], state["ssm_conv_tail"][i]),
                        pos, cfg,
                    )
                    ts.append(st)
                    tc.append(cv)
                new_state.update(
                    {"ssm_state_tail": jnp.stack(ts), "ssm_conv_tail": jnp.stack(tc)}
                )

        elif cfg.family == "hybrid":
            shared = params["shared_attn"]
            Lc = state["kv_k"].shape[2]
            slot = (pos % Lc).astype(jnp.int32)

            def group(h, xs):
                glp, st_g, cv_g, ck, cvv = xs

                def inner(hh, ys):
                    lp, st, cv = ys
                    hh, (st, cv) = blocks.mamba_block_decode(lp, hh, (st, cv), pos, cfg)
                    return hh, (st, cv)

                h, (st_g, cv_g) = jax.lax.scan(inner, h, (glp, st_g, cv_g))
                h, (kn, vn) = blocks.dense_block_decode(
                    shared, h, (ck, cvv), pos, cfg
                )
                return h, (st_g, cv_g, kn, vn)

            x, (ns, nc, nk, nv) = jax.lax.scan(
                group,
                x,
                (
                    params["mamba_groups"],
                    state["ssm_state"],
                    state["ssm_conv"],
                    state["kv_k"],
                    state["kv_v"],
                ),
            )
            new_state.update(
                {
                    "ssm_state": ns,
                    "ssm_conv": nc,
                    "kv_k": _slot_write(state["kv_k"], nk, slot),
                    "kv_v": _slot_write(state["kv_v"], nv, slot),
                }
            )
            if "mamba_tail" in params:
                def inner(hh, ys):
                    lp, st, cv = ys
                    hh, (st, cv) = blocks.mamba_block_decode(lp, hh, (st, cv), pos, cfg)
                    return hh, (st, cv)

                x, (ts, tc) = jax.lax.scan(
                    inner,
                    x,
                    (
                        params["mamba_tail"],
                        state["ssm_state_tail"],
                        state["ssm_conv_tail"],
                    ),
                )
                new_state.update({"ssm_state_tail": ts, "ssm_conv_tail": tc})

        x = blocks.norm_apply(cfg, params["final_norm"], x)
        head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
        logits = layers.unembed(head, x)
        new_state["pos"] = pos + 1
        return logits, new_state


def _slot_write(cache: Array, new: Array, slot: Array, axis: int = 2) -> Array:
    """Write the new-token entries at ``slot`` along the cache-length axis
    as a masked select.  dynamic_update_slice with a dynamic index on a
    SHARDED dim makes GSPMD replicate the whole cache ("involuntary full
    rematerialization"); an elementwise one-hot select stays shard-local."""
    idx = jax.lax.broadcasted_iota(jnp.int32, cache.shape, axis)
    return jnp.where(idx == slot, new.astype(cache.dtype), cache)


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)


def count_params_analytic(cfg: ModelConfig, active_only: bool = False) -> int:
    """Exact parameter count via eval_shape (no allocation)."""
    model = Model(cfg)
    tree = model.abstract_params()
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        n = math.prod(leaf.shape)
        pstr = "/".join(str(getattr(k, "key", k)) for k in path)
        if active_only and "experts/" in pstr and cfg.n_experts:
            n = n * cfg.experts_per_token // cfg.n_experts
        total += n
    return total
