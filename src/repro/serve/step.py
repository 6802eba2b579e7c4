"""Serving: prefill + single-token decode with sharded caches.

Cache sharding policy (decode cells):
  * batch axis → 'data' when divisible (decode_32k: 128/16 ✓; long_500k has
    batch 1 → replicated over data, noted in EXPERIMENTS.md);
  * kv-head axis → 'model' when divisible (MQA granite kv=1 → replicated;
    its head_dim shards instead);
  * MLA latent dim → 'model' (contraction-sharded attention, partial-sum
    all-reduce inserted by GSPMD);
  * SSM state heads → 'model' when divisible.
"""

from __future__ import annotations

import contextlib
from typing import Any, Callable, Dict, Tuple

import jax
from jax.sharding import PartitionSpec as P

from repro.core import tracing
from repro.models.model import Model, _slot_write

PyTree = Any

_NO_SPAN = contextlib.nullcontext()


def _div(n: int, mesh, axis: str) -> bool:
    return axis in mesh.shape and n % mesh.shape[axis] == 0


def inference_param_specs(model: Model, mesh) -> PyTree:
    """Serving-time parameter layout (§Perf: decode is not ZeRO-3 country).

    Dense/attention weights: TP over 'model', replicated over 'data' —
    per-layer ZeRO-3 all-gathers amortize over training batches but cost
    GiBs per decoded token.  Experts: E over 'data' × ff over 'model' so
    expert weights never move; the tiny decode token buffers all-to-all
    instead."""
    import jax.tree_util as jtu

    base = model.param_specs(mesh)          # includes zero3 if cfg.zero3
    cfg = model.cfg

    def one(path_tuple, leaf, spec):
        path = "/".join(str(getattr(k, "key", k)) for k in path_tuple)
        nd = leaf.ndim
        if "experts/" in path and cfg.n_experts:
            e_ax = "data" if _div(cfg.n_experts_held, mesh, "data") else None
            f_ax = "model" if _div(cfg.moe_d_ff, mesh, "model") else None
            pad = [None] * (nd - 3)
            if path.endswith("w_down"):
                return P(*(pad + [e_ax, f_ax, None]))
            return P(*(pad + [e_ax, None, f_ax]))
        # strip the zero3 ('data') axis everywhere else
        return P(*[None if ax == "data" else ax for ax in (list(spec) + [None] * nd)[:nd]])

    abstract = model.abstract_params()
    return jtu.tree_map_with_path(
        lambda p, l, s: one(p, l, s), abstract, base
    )


def decode_state_specs(model: Model, state_tree: PyTree, mesh) -> PyTree:
    cfg = model.cfg

    def one(path_tuple, leaf):
        path = "/".join(str(getattr(k, "key", k)) for k in path_tuple)
        nd = leaf.ndim
        if path.endswith("pos"):
            return P()
        shape = leaf.shape
        if "kv_" in path:
            # (L, B, Lc, G, hd).  Preference order for the 'model' axis:
            # kv heads when they divide, else the CACHE LENGTH dim —
            # length-sharded decode keeps the score einsum local and
            # combines softmax via tiny stat all-reduces.  Sharding head_dim
            # forces XLA into involuntary full-cache all-gathers
            # (§Perf cell 2: 2.5 GiB × n_layers per step before this).
            b = "data" if _div(shape[1], mesh, "data") else None
            if _div(shape[3], mesh, "model"):
                return P(None, b, None, "model", None)
            if _div(shape[2], mesh, "model"):
                return P(None, b, "model", None, None)
            hd = "model" if _div(shape[4], mesh, "model") else None
            return P(None, b, None, None, hd)
        if "mla_" in path:
            # (L, B, Lc, r) — shard the cache length; sharding the latent r
            # makes every layer's score einsum a (B,H,Lc)-sized partial-sum
            # all-reduce (§Perf cell 1/2 finding).
            b = "data" if _div(shape[1], mesh, "data") else None
            if _div(shape[2], mesh, "model"):
                return P(None, b, "model", None)
            r = "model" if _div(shape[3], mesh, "model") else None
            return P(None, b, None, r)
        if "ssm_state" in path:
            # (L[, G], B, H, P, N)
            b = "data" if _div(shape[-4], mesh, "data") else None
            h = "model" if _div(shape[-3], mesh, "model") else None
            return P(*([None] * (nd - 4) + [b, h, None, None]))
        if "ssm_conv" in path:
            b = "data" if _div(shape[-3], mesh, "data") else None
            c = "model" if _div(shape[-1], mesh, "model") else None
            return P(*([None] * (nd - 3) + [b, None, c]))
        return P(*([None] * nd))

    return jax.tree_util.tree_map_with_path(one, state_tree)


def make_serve_step(model: Model) -> Callable:
    """serve_step(params, state, tokens) → (logits, state)."""

    def serve_step(params, state, tokens):
        return model.decode_step(params, state, tokens)

    return serve_step


# ---------------------------------------------------------------------------
# shared decode-step scaffolding (compressed ring + KV-tiered steps)
#
# The per-layer loop steps below reproduce decode_step outside the scan: the
# same block functions, the same eager front (embed + learned positions) and
# tail (final norm + unembed), the same single post-loop cache write.  These
# helpers are that shared skeleton — one source of truth for the layer plan
# and the bit-identity claim.  The bits match decode_step's layer-at-a-time
# form (``scan_layers=False``, a barrier between layers).  On the CPU they
# also match the scanned step; on a TPU the compiler rounds a scan body
# differently from a standalone layer, so the scanned step differs in the
# last bits.
# ---------------------------------------------------------------------------


def _layer_plan(cfg) -> list:
    """[(stack_key, layer_index, block_kind)] in decode order."""
    if cfg.family == "moe":
        fk = cfg.first_k_dense
        return [("dense_layers", i, "dense") for i in range(fk)] + [
            ("moe_layers", i, "moe") for i in range(cfg.n_layers - fk)
        ]
    return [
        ("layers", i, "ssm" if cfg.family == "ssm" else "dense")
        for i in range(cfg.n_layers)
    ]


def _block_kinds(cfg) -> Dict[str, Callable]:
    """One compile per block *kind*, shared by every layer (all layers of a
    stack have identical shapes) — the same block functions decode_step
    runs per layer, so the math is bit-identical to its layer-at-a-time
    form (``scan_layers=False``)."""
    from repro.models import blocks

    return {
        "dense": jax.jit(
            lambda lp, h, c0, c1, pos: blocks.dense_block_decode(
                lp, h, (c0, c1), pos, cfg
            )
        ),
        "moe": jax.jit(
            lambda lp, h, c0, c1, pos: blocks.moe_block_decode(
                lp, h, (c0, c1), pos, cfg
            )
        ),
        "ssm": jax.jit(
            lambda lp, h, st, cv, pos: blocks.mamba_block_decode(
                lp, h, (st, cv), pos, cfg
            )
        ),
    }


def _decode_front(cfg, sp, tokens, pos):
    """Embed + learned positions, mirroring decode_step line for line (kept
    eager: a token-sized gather — bitwise the same ops)."""
    import jax.numpy as jnp

    from repro.models import layers
    from repro.distributed.sharding import lshard

    x = layers.embed(sp["embed"], tokens)
    if cfg.pos_embedding == "learned":
        pe = jax.lax.dynamic_slice_in_dim(
            sp["pos"]["table"], jnp.minimum(pos, cfg.max_position - 1), 1
        )
        x = x + pe[None].astype(x.dtype)
    return lshard(x, "batch", None, None)


@jax.jit
def _write_slots(c0, c1, outs0, outs1, slot):
    """The post-loop slot write of both caches, as decode_step does it, in
    one compiled call: run eagerly, the one-hot select would materialise a
    cache-sized position iota and mask (6.7 GB at a 3 GB latent cache)."""
    import jax.numpy as jnp

    return (_slot_write(c0, jnp.stack(outs0), slot),
            _slot_write(c1, jnp.stack(outs1), slot))


def _decode_tail(cfg, sp, x):
    from repro.models import blocks, layers

    x = blocks.norm_apply(cfg, sp["final_norm"], x)
    head = sp["embed"] if cfg.tie_embeddings else sp["lm_head"]
    return layers.unembed(head, x)


def make_kv_tiered_serve_step(model: Model, params, kv_store) -> Callable:
    """Decode step over a :class:`repro.serve.kvcache.KVCacheStore`.

    ``serve_step(tokens) -> logits`` — the cache lives in ``kv_store``
    (hot suffix + compressed cold blocks) instead of the state dict, and
    advances as a side effect of the call.  Logits are **bit-identical**
    to ``model.decode_step`` (layer at a time, see the note above
    ``_layer_plan``) over the untiered cache: each layer's block
    function receives the store's reassembled full-length caches
    (byte-identical arrays — see ``serve/kvcache.py``), and the new-token
    entries flow through the same masked one-hot write.  Peak cache
    residency drops to hot buffers + compressed payloads + one layer's
    reassembly in flight.

    ssm / hybrid models have no cache-length axis and are rejected.
    """
    import jax.numpy as jnp

    cfg = model.cfg
    if cfg.family in ("ssm", "hybrid"):
        raise NotImplementedError(
            f"{cfg.name}: family {cfg.family!r} has no attention-cache "
            "length axis to tier"
        )
    if not cfg.has_decode:
        raise ValueError(f"{cfg.name}: family {cfg.family!r} has no decode path")
    if kv_store.n_layers != cfg.n_layers:
        raise ValueError(
            f"kv_store holds {kv_store.n_layers} layers, "
            f"model {cfg.name} has {cfg.n_layers}"
        )
    plan = _layer_plan(cfg)
    kinds = _block_kinds(cfg)

    def serve_step(tokens):
        pos = jnp.asarray(kv_store.pos, jnp.int32)
        x = _decode_front(cfg, params, tokens, pos)
        outs0, outs1 = [], []
        for j, (key, i, kind) in enumerate(plan):
            lp = jax.tree_util.tree_map(lambda a, i=i: a[i], params[key])
            c0j, c1j = kv_store.layer_caches(j)
            x, (u0, u1) = kinds[kind](lp, x, c0j, c1j, pos)
            outs0.append(u0)
            outs1.append(u1)
        kv_store.append(jnp.stack(outs0), jnp.stack(outs1))
        return _decode_tail(cfg, params, x)

    serve_step.kv_store = kv_store
    return serve_step


def make_compressed_serve_step(
    model: Model,
    store,
    *,
    ring: int = 2,
    prefetch: bool = True,
    tiles: int = 1,
    kv_store=None,
) -> Callable:
    """Compressed-resident decode step over a ``CompressedParamStore``.

    ``serve_step(state, tokens) -> (logits, new_state)`` — same contract as
    :func:`make_serve_step`'s step, but the weights live in ``store`` as
    ZNN1 payloads and decode **just ahead of compute**: a double-buffered
    prefetch/decode ring (default ``ring=2``) runs layer *i*'s matmuls
    while a single background worker decodes layer *i+1* into the next
    slot, so at most ``ring`` layers of decoded weights are claimed at any
    moment (``store.peak_resident`` asserts this).  Each slot is released
    as soon as its layer's compute is dispatched; XLA frees the decoded
    buffers when the matmuls retire.

    Logits and new state are **bit-identical** to the uncompressed
    ``model.decode_step`` run layer at a time (see the note above
    ``_layer_plan``): the per-layer block functions are the same code
    decode_step runs (jit-compiled once per block *kind*, reused by every
    layer), the cache slot-write happens
    once after the loop exactly as in decode_step, and the payload decode
    itself is byte-identical across ``backend`` × ``entropy_backend`` ×
    ``threads`` (the knob contract; ``prefetch=False`` gives the
    host-sequential fallback with residency 1).

    hybrid (mamba-group) models are rejected: their shared attention
    params repeat across groups, which does not fit a per-layer ring.

    ``kv_store`` (a :class:`repro.serve.kvcache.KVCacheStore`) composes
    the KV-cache tier with the weight ring: the state dict then carries
    only ``pos`` — caches live in the store as a hot suffix + compressed
    cold blocks, each layer attends over its reassembled full-length
    caches (bit-identical arrays), and the post-loop slot write becomes
    ``kv_store.append``.  Everything compressible at serve time — weights
    at rest AND cold cache — is then ZNN1 payloads.

    ``tiles`` sets the decode *granularity*: with ``tiles > 1`` each layer
    splits into ``tiles`` contiguous tensor-groups
    (``store.decode_layer_tile``) that decode as independent ring jobs —
    a layer's first tensor-group is decoded and resident while its last
    group is still in the decoder, and the next layer's first tiles start
    decoding before the current layer's tail tiles are consumed.  Peak
    decoded residency is accounted per tile slot: at most ``ring × tiles``
    tile slots (each roughly ``1/tiles`` of a layer) instead of ``ring``
    whole layers.  Tiling changes scheduling and residency only — the
    reassembled layer is leaf-for-leaf identical, so logits stay
    bit-identical to ``model.decode_step``.
    """
    import jax.numpy as jnp
    from concurrent.futures import ThreadPoolExecutor

    cfg = model.cfg
    if cfg.family == "hybrid":
        raise NotImplementedError(
            "hybrid (mamba-group) models are not supported by the "
            "compressed serving ring: shared_attn params repeat per group"
        )
    if not cfg.has_decode:
        raise ValueError(f"{cfg.name}: family {cfg.family!r} has no decode path")
    if ring < 1:
        raise ValueError(f"ring must be >= 1, got {ring}")
    if tiles < 1:
        raise ValueError(f"tiles must be >= 1, got {tiles}")
    if kv_store is not None and cfg.family == "ssm":
        raise NotImplementedError(
            f"{cfg.name}: ssm state has no cache-length axis to tier"
        )

    plan = _layer_plan(cfg)
    for key in {k for k, _, _ in plan}:
        want = sum(1 for k, _, _ in plan if k == key)
        if store.n_layers(key) != want:
            raise ValueError(
                f"store stack {key!r} holds {store.n_layers(key)} layers, "
                f"model {cfg.name} needs {want}"
            )

    kinds = _block_kinds(cfg)

    executor = (
        ThreadPoolExecutor(max_workers=1, thread_name_prefix="znn-ring")
        if (prefetch and ring > 1)
        else None
    )
    # Ring depth in decode-job units: jobs are whole layers (tiles == 1) or
    # tile slots (tiles > 1) — either way the ring keeps ring-1 layers'
    # worth of decode ahead of compute.
    n_jobs = len(plan) * tiles
    depth = (ring - 1) * tiles if executor is not None else 0

    def _decode(n: int):
        j, t = divmod(n, tiles)
        key, i, kind = plan[j]
        with tracing.span("znn.ring.decode"):
            with tracing.span("znn.ring.experts") if kind == "moe" else _NO_SPAN:
                if tiles == 1:
                    out = store.decode_layer(key, i)
                else:
                    out = store.decode_layer_tile(key, i, t, tiles)
        tracing.count(f"ring_raw_bytes.{kind}",
                      sum(a.nbytes for a in jax.tree_util.tree_leaves(out)))
        return out

    def _job(n: int, op):
        with tracing.joined(op):
            return _decode(n)

    def _release(key: str, i: int) -> None:
        if tiles == 1:
            store.release(key, i)
        else:
            for t in range(tiles):
                store.release_tile(key, i, t, tiles)

    def serve_step(state, tokens):
        with tracing.operation("znn.ring.step"):
            return _step(state, tokens)

    def _step(state, tokens):
        pos = state["pos"]
        x = _decode_front(cfg, store.static, tokens, pos)
        new_state = dict(state)
        op = tracing.current_op()

        inflight: list = []
        nxt = 0

        def pump() -> None:
            # Keep up to ring-1 layers' worth of decode jobs ahead of
            # compute; the worker fills the next slot while the current
            # layer's matmuls run.
            nonlocal nxt
            while (
                executor is not None
                and nxt < n_jobs
                and len(inflight) < depth
            ):
                inflight.append(executor.submit(_job, nxt, op))
                nxt += 1

        def next_job(n: int):
            nonlocal nxt
            with tracing.span("znn.ring.wait"):
                if inflight:
                    out = inflight.pop(0).result()
                else:
                    out = _decode(n)
                    nxt = n + 1
            pump()
            return out

        def layer_params(j: int):
            if tiles == 1:
                return next_job(j)
            # Collect the layer's tiles in order; pump() between tiles so
            # later layers' tiles enter the decoder as slots free up — the
            # tile-granular overlap.
            arrays: Dict[int, Any] = {}
            for t in range(tiles):
                arrays.update(next_job(j * tiles + t))
            key, i, _ = plan[j]
            return store.layer_unflatten(
                key, i, [arrays[k] for k in sorted(arrays)]
            )

        pump()
        if cfg.family == "ssm":
            outs_s, outs_c = [], []
            for j, (key, i, kind) in enumerate(plan):
                lp = layer_params(j)
                with tracing.span("znn.ring.layer"):
                    x, (st, cv) = kinds[kind](
                        lp, x, state["ssm_state"][j], state["ssm_conv"][j], pos
                    )
                    _release(key, i)
                outs_s.append(st)
                outs_c.append(cv)
            with tracing.span("znn.ring.tail"):
                new_state["ssm_state"] = jnp.stack(outs_s)
                new_state["ssm_conv"] = jnp.stack(outs_c)
        elif kv_store is not None:
            outs0, outs1 = [], []
            for j, (key, i, kind) in enumerate(plan):
                lp = layer_params(j)
                with tracing.span("znn.ring.layer"):
                    c0j, c1j = kv_store.layer_caches(j)
                    x, (u0, u1) = kinds[kind](lp, x, c0j, c1j, pos)
                    _release(key, i)
                outs0.append(u0)
                outs1.append(u1)
            # single post-loop cache write, exactly as decode_step — into
            # the tiered store's hot buffer instead of the state dict
            with tracing.span("znn.ring.tail"):
                kv_store.append(jnp.stack(outs0), jnp.stack(outs1))
        else:
            c0, c1 = (
                (state["mla_ckv"], state["mla_kr"])
                if cfg.mla
                else (state["kv_k"], state["kv_v"])
            )
            Lc = c0.shape[2]
            slot = (pos % Lc).astype(jnp.int32)
            outs0, outs1 = [], []
            for j, (key, i, kind) in enumerate(plan):
                lp = layer_params(j)
                with tracing.span("znn.ring.layer"):
                    x, (u0, u1) = kinds[kind](lp, x, c0[j], c1[j], pos)
                    _release(key, i)
                outs0.append(u0)
                outs1.append(u1)
            # single slot write for all layers, exactly as decode_step
            with tracing.span("znn.ring.tail"):
                keys = ("mla_ckv", "mla_kr") if cfg.mla else ("kv_k", "kv_v")
                new_state.update(zip(keys, _write_slots(c0, c1, outs0, outs1, slot)))

        with tracing.span("znn.ring.tail"):
            logits = _decode_tail(cfg, store.static, x)
            new_state["pos"] = pos + 1
        return logits, new_state

    serve_step.store = store
    serve_step.ring = ring
    serve_step.tiles = tiles
    serve_step.kv_store = kv_store
    return serve_step


def make_prefill(model: Model) -> Callable:
    """prefill(params, batch) → logits for the full prompt (chunked attn)."""

    def prefill(params, batch):
        logits, _ = model.forward(params, batch)
        return logits

    return prefill


def greedy_generate(
    model: Model, params, prompt, steps: int
) -> Tuple[Any, Any]:
    """Small-scale generation loop for examples/tests (feeds tokens one by
    one through the decode step; caches sized for prompt+steps).

    ``steps == 0`` is valid (prompt is fed through the cache, no tokens are
    sampled; returns an empty ``(B, 0)`` int32 array).  An empty prompt or
    negative ``steps`` raises ``ValueError`` — there is no logits history
    to sample the first token from.
    """
    import jax.numpy as jnp

    if getattr(prompt, "ndim", None) != 2:
        raise ValueError(
            f"prompt must be a (B, S) token array, got shape "
            f"{getattr(prompt, 'shape', None)}"
        )
    B, S = prompt.shape
    if S == 0:
        raise ValueError(
            "prompt must contain at least one token (S == 0): the first "
            "sampled token is argmax over the prompt's last logits"
        )
    if steps < 0:
        raise ValueError(f"steps must be >= 0, got {steps}")
    state = model.init_decode_state(B, S + steps, start_pos=0)
    step = jax.jit(model.decode_step)
    logits = None
    for t in range(S):
        logits, state = step(params, state, prompt[:, t : t + 1])
    if steps == 0:
        return jnp.zeros((B, 0), dtype=jnp.int32), state
    out = []
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    for _ in range(steps):
        out.append(tok)
        logits, state = step(params, state, tok)
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    return jnp.concatenate(out, axis=1), state
