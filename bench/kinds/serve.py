"""Compressed-resident decode: weights held as ZNN1 payloads in HBM
(``CompressedParamStore.from_params(payload_feed=True)``) and decoded a
layer at a time for every token by ``make_compressed_serve_step``.

Mix parameters (``traffic/<mix>.json``):
  ``batch``       sequences decoded together, greedily;
  ``cache_len``   positions of the KV cache;
  ``start_pos``   positions ``0 .. start_pos-1`` of the cache are filled
                  from the seed in set-up; decoding starts there, and when
                  the cache is full the position returns to ``start_pos``
                  (a new lap over the same prefix);
  ``ring``        decoded layers in flight;
  ``control_steps``  plain ``decode_step`` steps timed after the window of
                  a traced run, on the same weights uncompressed.

After the window the plain reference (``reference/qwen.py``) runs over the
prefix and every lap's tokens, and the widest gap by which a served token's
logit lies below the reference's best is compared with its limit.
"""

from __future__ import annotations

import time

import numpy as np

from bench import program, state



def _prefix(run, jax, cfg):
    """Cache entries of the prefix positions, from the seed."""
    import jax.numpy as jnp

    t = run.traffic
    key = jax.random.fold_in(state.seed_key(run.seed), 1)
    shape = (cfg.n_layers, t["batch"], t["start_pos"], cfg.n_kv_heads, cfg.head_dim)

    @jax.jit
    def make(key):
        kk, kv = jax.random.split(key)
        return (jax.random.normal(kk, shape, jnp.float32).astype(jnp.bfloat16),
                jax.random.normal(kv, shape, jnp.float32).astype(jnp.bfloat16))

    return make(key)


def setup(run, jax):
    import jax.numpy as jnp

    from repro.serve.compressed import CompressedParamStore
    from repro.serve.step import make_compressed_serve_step

    t = run.traffic
    t0 = time.perf_counter()
    cfg, model = program.model(run)
    fill = state.make_filler(state.abstract_params(cfg))
    params = fill(state.seed_key(run.seed))
    pk, pv = _prefix(run, jax, cfg)
    jax.block_until_ready((params, pk, pv))
    t1 = time.perf_counter()
    zcfg, opts = program.codec(run.config)
    store = CompressedParamStore.from_params(params, zcfg, options=opts, payload_feed=True)
    del params
    t2 = time.perf_counter()
    ring = make_compressed_serve_step(model, store, ring=t["ring"])

    @jax.jit
    def fresh(pk, pv):
        st = model.init_decode_state(t["batch"], t["cache_len"], start_pos=t["start_pos"])
        P = t["start_pos"]
        st["kv_k"] = st["kv_k"].at[:, :, :P].set(pk)
        st["kv_v"] = st["kv_v"].at[:, :, :P].set(pv)
        return st

    st = fresh(pk, pv)
    del pk, pv
    pick = jax.jit(lambda logits: jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None])
    tok = jax.random.randint(jax.random.fold_in(state.seed_key(run.seed), 2),
                             (t["batch"], 1), 0, cfg.vocab_size, dtype=jnp.int32)
    logits, _ = ring(st, tok)                             # warm every shape
    np.asarray(pick(logits))
    del logits
    weights = sum(x.size for p, x in state.flat_leaves(state.abstract_params(cfg)).items()
                  if p.startswith("layers/") and p.endswith("/w"))
    weights += cfg.vocab_size * cfg.d_model                    # the head
    run.extra.update(cfg=cfg, model=model, store=store, ring=ring, pick=pick, st=st, tok=tok,
                     weights=weights, flops=0.0, huffdecode=_huffdecode_bytes(store),
                     laps=[[np.asarray(tok)[:, 0]]], tokens=0,
                     raw=store.raw_bytes, stored=store.comp_bytes)
    run.extra["setup_split"] = {"state_s": f"{t1 - t0:.3f}", "store_s": f"{t2 - t1:.3f}",
                                "warm_step_s": f"{time.perf_counter() - t2:.3f}"}


def _huffdecode_bytes(store):
    """HUFF work of one ring step, from the store's payloads; ``None`` where
    the store does not expose them."""
    from bench import work
    from bench.reference import znn

    stacks = getattr(store, "_stacks", None)
    try:
        streams = [znn.parse(ct.blob) for ms in stacks.values() for m in ms
                   for ct in m["leaves"]]
    except (AttributeError, KeyError, TypeError, ValueError):
        return None
    return work.huffdecode_bytes(streams)


def step(run, jax, i):
    import jax.numpy as jnp

    from bench.harness import span

    t = run.traffic
    x = run.extra
    with span(jax, "ring_step"):
        logits, x["st"] = x["ring"](x["st"], x["tok"])
        x["tok"] = x["pick"](logits)
        served = np.asarray(x["tok"])[:, 0]
    pos = t["start_pos"] + len(x["laps"][-1]) - 1
    cfg = x["cfg"]
    x["flops"] += t["batch"] * (2.0 * x["weights"]
                                + 4.0 * cfg.n_layers * cfg.n_heads * cfg.head_dim * (pos + 1))
    x["laps"][-1].append(served)
    x["tokens"] += t["batch"]
    if t["start_pos"] + len(x["laps"][-1]) - 1 >= t["cache_len"]:
        x["st"]["pos"] = jnp.asarray(t["start_pos"], jnp.int32)
        x["laps"].append([served])


def release(run, jax):
    for k in ("store", "ring", "st", "tok"):
        run.extra.pop(k, None)


def _reference_inputs(run, jax):
    cfg = run.extra["cfg"]
    fill = state.make_filler(state.abstract_params(cfg))
    params = fill(state.seed_key(run.seed))
    pk, pv = _prefix(run, jax, cfg)
    laps = [np.stack(lap, axis=1) for lap in run.extra["laps"] if len(lap) > 1]
    return cfg, params, pk, pv, laps


def check(run, jax, ops):
    import jax.numpy as jnp

    from bench.reference import qwen

    cfg, params, pk, pv, laps = _reference_inputs(run, jax)
    gap = 0.0
    for lap in laps:                                     # (B, steps + 1)
        gap = max(gap, qwen.served_gap(params, pk, pv, jnp.asarray(lap[:, :-1]),
                                       jnp.asarray(lap[:, 1:]), cfg))
    if run.extra.get("control"):
        low = qwen.lower_precision(params)
        run.extra["control_gap"] = max(
            qwen.control_gap(params, low, pk, pv, jnp.asarray(lap[:, :-1]), cfg) for lap in laps)
    run.extra["served_gap"] = gap
    n = sum(lap.shape[1] - 1 for lap in laps)
    return {"served_logit_gap": {"value": gap, "limit": run.traffic["gap_limit"]}}, ops, int(n != ops)


def end_to_end(run, window_s, ops):
    return {"decode_tokens_per_s": run.extra["tokens"] / window_s,
            "stored_per_raw": run.extra["stored"] / run.extra["raw"]}


def after_check(run, jax):
    """Traced runs: time plain ``decode_step`` (layer at a time) on the same
    weights uncompressed, the control for ``ring_slowdown``."""
    import dataclasses

    from bench.harness import span
    from repro.models import build_model

    t = run.traffic
    cfg, params, pk, pv, _ = _reference_inputs(run, jax)
    model = build_model(dataclasses.replace(cfg, scan_layers=False))
    dec = jax.jit(model.decode_step)
    st = model.init_decode_state(t["batch"], t["cache_len"], start_pos=t["start_pos"])
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
