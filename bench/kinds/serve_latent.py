"""Compressed-resident decode of a latent-attention MoE model (DeepSeek-V2):
weights held as ZNN1 payloads in HBM
(``CompressedParamStore.from_params(payload_feed=True)``) and decoded a
layer at a time for every token by ``make_compressed_serve_step``, as in
``kinds/serve.py``, whose window step, release and end-to-end metrics this
kind builds on.

Mix parameters are ``kinds/serve.py``'s, and ``entry_limit``.  The prefix
is the latent cache: positions ``0 .. start_pos-1`` of ``mla_ckv`` (the
normed key/value latent) and ``mla_kr`` (the rotated rope key) are drawn
from the seed straight into the decode state.

The configuration file names its keys as the published ``config.json``
does; ``model_config`` maps the MoE, MLA and YaRN keys onto the program's
fields.  ``n_routed_experts`` is the experts this chip holds, the router's
first ones; ``expert_parallel`` states the router's width.

After the window the plain reference (``reference/deepseek_v2.py``) runs
over the prefix and every lap's tokens.  Compared with their limits: the
widest gap by which a served token's logit lies below the reference's best
(``gap_limit``), and the widest relative error of the cache entries the
ring wrote in the last lap against the reference's (``entry_limit``).  The
control (``control``) puts the fp8 (e4m3) reference in the program's
place: its first-ranked tokens for the served ones, its entries for the
ring's.
"""

from __future__ import annotations

import dataclasses
import sys
import time

import numpy as np

from bench import state
from bench.kinds import serve
from bench.kinds.serve import _huffdecode_bytes, end_to_end  # noqa: F401

# config-file key -> ModelConfig field, beside state.FIELDS
LATENT = {
    "moe_intermediate_size": "moe_d_ff",
    "intermediate_size": "dense_d_ff",
    "n_routed_experts": "experts_held",
    "num_experts_per_tok": "experts_per_token",
    "n_shared_experts": "n_shared_experts",
    "first_k_dense_replace": "first_k_dense",
    "kv_lora_rank": "kv_lora_rank",
    "q_lora_rank": "q_lora_rank",
    "qk_nope_head_dim": "qk_nope_dim",
    "qk_rope_head_dim": "qk_rope_dim",
    "v_head_dim": "v_head_dim",
    "norm_topk_prob": "norm_topk_prob",
}
YARN = {"factor": "yarn_factor", "mscale_all_dim": "yarn_mscale_all_dim"}
# What the program computes; a file that asks for anything else is refused.
SUPPORTED = {"scoring_func": "softmax", "topk_method": "greedy", "routed_scaling_factor": 1,
             "hidden_act": "silu", "moe_layer_freq": 1, "n_group": 1, "topk_group": 1}
# YaRN as the program computes it (``models/layers.py``'s constants; cos and
# sin unscaled, so ``mscale`` equals ``mscale_all_dim``).
ROPE_SCALING = {"type": "yarn", "original_max_position_embeddings": 4096, "beta_fast": 32,
                "beta_slow": 1}
# Widths a smaller model (a test's overrides) takes from the registry's reduced().
WIDTHS = ("moe_d_ff", "dense_d_ff", "kv_lora_rank", "q_lora_rank", "qk_nope_dim",
          "qk_rope_dim", "v_head_dim")


def model_config(config, overrides=None):
    """The program's configuration of the file.  Where ``overrides`` set a
    model size, the widths they leave out come from ``reduced()`` of the
    registered configuration."""
    from repro.configs import get_config

    for key, want in SUPPORTED.items():
        if config.get(key, want) != want:
            raise ValueError(f"{key} = {config[key]!r}: the program computes {want!r}")
    rs = config["rope_scaling"]
    if (any(rs.get(k) != v for k, v in ROPE_SCALING.items())
            or rs.get("mscale") != rs.get("mscale_all_dim")):
        raise ValueError(f"rope_scaling {rs!r}: the program computes {ROPE_SCALING!r} "
                         "with mscale equal to mscale_all_dim")
    values = {f: config[k] for k, f in LATENT.items() if k in config}
    values["q_lora_rank"] = values.get("q_lora_rank") or 0
    values["d_ff"] = values["moe_d_ff"]                  # per routed expert in the moe family
    values.update({f: rs[k] for k, f in YARN.items()})
    ep = config["expert_parallel"]
    values["n_experts"] = ep["router_experts"]
    cfg = dataclasses.replace(state.model_config(config), **values)
    if overrides:
        small = get_config(config["registry"]).reduced()
        cfg = dataclasses.replace(cfg, **{f: getattr(small, f) for f in WIDTHS
                                          if f not in overrides})
        cfg = dataclasses.replace(cfg, **overrides)
    return cfg


def _latent(run, jax, cfg, full: bool):
    """The decode state's latent caches, positions past the prefix zero
    (``full``), or the prefix alone; both from the same draws."""
    import jax.numpy as jnp

    t = run.traffic
    B, L, P = t["batch"], t["cache_len"], t["start_pos"]
    key = jax.random.fold_in(state.seed_key(run.seed), 1)

    @jax.jit
    def make(key):
        out = []
        for k, width in zip(jax.random.split(key), (cfg.kv_lora_rank, cfg.qk_rope_dim)):
            def layer(i, k=k, width=width):
                x = jax.random.normal(jax.random.fold_in(k, i), (B, P, width), jnp.bfloat16)
                return jnp.pad(x, ((0, 0), (0, L - P), (0, 0))) if full else x

            # one layer's draws at a time, written into the stacked result
            out.append(jax.lax.map(layer, jnp.arange(cfg.n_layers)))
        return tuple(out)

    return make(key)


def setup(run, jax):
    import jax.numpy as jnp

    from bench import program
    from repro.models import build_model
    from repro.serve.compressed import CompressedParamStore
    from repro.serve.step import make_compressed_serve_step

    t = run.traffic
    t0 = time.perf_counter()
    cfg = model_config(run.config, run.overrides.get("model"))
    model = build_model(cfg)
    abstract = state.abstract_params(cfg)
    params = state.make_filler(abstract)(state.seed_key(run.seed))
    jax.block_until_ready(params)
    t1 = time.perf_counter()
    zcfg, opts = program.codec(run.config)
    store = CompressedParamStore.from_params(params, zcfg, options=opts, payload_feed=True)
    del params
    t2 = time.perf_counter()
    ring = make_compressed_serve_step(model, store, ring=t["ring"])
    ckv, kr = _latent(run, jax, cfg, full=True)
    st = {"pos": jnp.asarray(t["start_pos"], jnp.int32), "mla_ckv": ckv, "mla_kr": kr}
    del ckv, kr
    pick = jax.jit(lambda logits: jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None])
    tok = jax.random.randint(jax.random.fold_in(state.seed_key(run.seed), 2),
                             (t["batch"], 1), 0, cfg.vocab_size, dtype=jnp.int32)
    logits, _ = ring(st, tok)                             # warm every shape
    np.asarray(pick(logits))
    del logits
    run.extra.update(cfg=cfg, model=model, store=store, ring=ring, pick=pick, st=st, tok=tok,
                     weights=_weights_per_token(cfg, abstract), flops=0.0,
                     huffdecode=_huffdecode_bytes(store), laps=[[np.asarray(tok)[:, 0]]],
                     tokens=0, raw=store.raw_bytes, stored=store.comp_bytes)
    run.extra["setup_split"] = {"state_s": f"{t1 - t0:.3f}", "store_s": f"{t2 - t1:.3f}",
                                "warm_step_s": f"{time.perf_counter() - t2:.3f}"}


def _weights_per_token(cfg, abstract):
    """Matrix weights one token multiplies by: every matrix of the layer
    stacks (a held expert's in proportion to the tokens routed to it:
    top-k of the router's width), and the head."""
    n = 0.0
    for p, x in state.flat_leaves(abstract).items():
        if p.startswith(("dense_layers/", "moe_layers/")) and x.ndim >= 3:
            n += x.size * (cfg.experts_per_token / cfg.n_experts if "/experts/" in p else 1)
    return n + cfg.vocab_size * cfg.d_model


def step(run, jax, i):
    """``kinds/serve.py``'s step, whose FLOPs count GQA attention
    (``4 · layers · heads · head_dim`` a position of context); absorbed MLA
    takes ``2 · layers · heads · (2 · kv_lora_rank + qk_rope_dim)``:
    scores over the latent and the rope key, context over the latent."""
    x, cfg = run.extra, run.extra["cfg"]
    context = run.traffic["start_pos"] + len(x["laps"][-1])
    serve.step(run, jax, i)
    x["flops"] += run.traffic["batch"] * cfg.n_layers * cfg.n_heads * context * (
        2.0 * (2 * cfg.kv_lora_rank + cfg.qk_rope_dim) - 4.0 * cfg.head_dim)


def release(run, jax):
    """Keep the cache entries the ring wrote in the last lap that fed
    tokens, then drop the device state."""
    x, P = run.extra, run.traffic["start_pos"]
    n = [len(lap) for lap in x["laps"] if len(lap) > 1][-1] - 1
    x["written"] = tuple(np.asarray(x["st"][k][:, :, P:P + n]) for k in ("mla_ckv", "mla_kr"))
    serve.release(run, jax)


def _reference_inputs(run, jax):
    cfg = run.extra["cfg"]
    params = state.make_filler(state.abstract_params(cfg))(state.seed_key(run.seed))
    ckv, kr = _latent(run, jax, cfg, full=False)
    laps = [np.stack(lap, axis=1) for lap in run.extra["laps"] if len(lap) > 1]
    return cfg, params, ckv, kr, laps


def check(run, jax, ops):
    import jax.numpy as jnp

    from bench.reference import deepseek_v2 as ref

    cfg, params, ckv, kr, laps = _reference_inputs(run, jax)
    t, x = run.traffic, run.extra
    low = ref.lower_precision(params) if x.get("control") else None
    gap = control_gap = 0.0
    written = x["written"]
    for lap in laps:                                     # (B, steps + 1)
        fed = jnp.asarray(lap[:, :-1])
        hid, entries = ref.trunk(params, ckv, kr, fed, cfg)
        gap = max(gap, ref.served_gap(params, hid, jnp.asarray(lap[:, 1:])))
        if low is not None:                              # the fp8 reference in the ring's place
            hid_low, written = ref.trunk(low, ckv, kr, fed, cfg)
            control_gap = max(control_gap, ref.control_gap(params, hid, low, hid_low))
    error = ref.entry_error(written, entries)            # the last lap's
    x["served_gap"] = gap
    if low is not None:
        x["control_gap"] = gap = control_gap
    n = sum(lap.shape[1] - 1 for lap in laps)
    return ({"served_logit_gap": {"value": gap, "limit": t["gap_limit"]},
             "cache_entry_error": {"value": error, "limit": t["entry_limit"]}},
            ops, int(n != ops))


def after_check(run, jax):
    """Traced runs: time plain ``decode_step`` (layer at a time) on the same
    weights uncompressed, over the same latent prefix, and print the
    seconds a step on standard error beside the ring's."""
    import jax.numpy as jnp

    from bench.harness import span
    from repro.models import build_model

    t = run.traffic
    cfg, params, _, _, _ = _reference_inputs(run, jax)
    model = build_model(dataclasses.replace(cfg, scan_layers=False))
    dec = jax.jit(model.decode_step)
    ckv, kr = _latent(run, jax, cfg, full=True)
    st = {"pos": jnp.asarray(t["start_pos"], jnp.int32), "mla_ckv": ckv, "mla_kr": kr}
    del ckv, kr
    tok = run.extra["pick"](dec(params, st, np.asarray(run.extra["laps"][0][0])[:, None])[0])
    jax.block_until_ready(tok)
    times = []
    for _ in range(t["control_steps"]):
        t0 = time.perf_counter()
        with span(jax, "control_step"):
            logits, st = dec(params, st, tok)
            tok = run.extra["pick"](logits)
            jax.block_until_ready(tok)
        times.append(time.perf_counter() - t0)
    run.extra["control_step_s"] = times
    print(f"control: plain decode_step s={','.join(f'{x:.4f}' for x in times)} "
          f"ring step s={','.join(f'{x:.4f}' for x in run.per_op)}", file=sys.stderr, flush=True)
