"""DeepSeek-V2-Lite on the normal path: MLA with a direct query projection,
YaRN RoPE, top-k gates without renormalisation over a held block of
experts, and decode that drops no token — checked against the published
formulas and the plain reference (``bench/reference/deepseek_v2.py``) at
the reduced size on seeded random weights."""

import dataclasses
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import state  # noqa: E402
from bench.reference import deepseek_v2 as ref  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import ARCHS  # noqa: E402
from repro.core import tracing  # noqa: E402
from repro.models import attention, build_model, layers, moe  # noqa: E402
from repro.serve import CompressedParamStore, make_compressed_serve_step  # noqa: E402

# Program (bf16 weights and activations) against the float32 reference, on
# logits of scale ~0.23 (std) at the reduced size: every matmul rounds its
# inputs to bf16 (relative 2^-9), which over two layers and the head reads
# 0.008-0.010 on four seeds.  0.03 leaves three times that; the reference
# itself with fp8 (e4m3) weights reads 0.099-0.153 on the same seeds.
LOGIT_TOL = 0.03


def _cfg(**kw):
    """The reduced model with the deployment's routing: a 64-wide router,
    top-6, experts 0-7 held."""
    cfg = get_config("deepseek_v2_lite").reduced()
    return dataclasses.replace(cfg, **{"n_experts": 64, "experts_held": 8,
                                       "experts_per_token": 6, **kw})


def _params(cfg, seed):
    return state.make_filler(state.abstract_params(cfg))(state.seed_key(seed))


def test_registry_holds_the_published_config():
    cfg = get_config("deepseek_v2_lite")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.vocab_size) == (27, 2048, 16, 102400)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim,
            cfg.v_head_dim) == (0, 512, 128, 64, 128)
    assert (cfg.n_experts, cfg.n_experts_held, cfg.experts_per_token, cfg.n_shared_experts,
            cfg.moe_d_ff, cfg.first_k_dense, cfg.dense_d_ff) == (64, 64, 6, 2, 1408, 1, 10944)
    assert not cfg.norm_topk_prob and not cfg.tie_embeddings
    assert (cfg.yarn_factor, cfg.yarn_mscale_all_dim) == (40, 0.707)
    assert "deepseek_v2_lite" not in ARCHS          # the dry-run set stays as it is
    assert get_config("deepseek_v2_lite").reduced().q_lora_rank == 0
    assert get_config("deepseek_v2_236b").reduced().q_lora_rank == 32


def _published_inv_freq(dim, base, factor, original, beta_fast, beta_slow):
    """DeepseekV2YarnRotaryEmbedding's frequencies, line for line."""
    def find_correction_dim(num_rotations):
        return (dim * math.log(original / (num_rotations * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), dim - 1)
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    return freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask, (low, high)


def test_yarn_frequencies_and_softmax_scale_match_the_published_formulas():
    cfg = get_config("deepseek_v2_lite")
    want, corr = _published_inv_freq(64, 1e4, 40, 4096, 32, 1)
    assert corr == (10, 23)
    assert (layers.YARN_ORIGINAL_MAX, layers.YARN_BETA_FAST, layers.YARN_BETA_SLOW) == (
        4096, 32, 1)
    got = np.asarray(layers.rope_freqs(64, 1e4, 40.0))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(ref.inv_freq(64, 1e4, 40), want, rtol=1e-6)
    mscale = 0.1 * 0.707 * math.log(40) + 1.0
    assert attention.mla_softmax_mscale(cfg) == pytest.approx(mscale ** 2, rel=1e-12)
    assert ref.dims(cfg).scale == pytest.approx(192 ** -0.5 * mscale ** 2, rel=1e-12)
    # the rotation at the YaRN frequencies; mscale == mscale_all_dim, so
    # cos and sin are not rescaled
    x = jax.random.normal(jax.random.key(0), (2, 5, 3, 64), jnp.float32)
    pos = jnp.arange(5)[None] + 4000
    ang = np.asarray(pos, np.float64)[..., None] * want
    cos, sin = np.cos(ang)[..., None, :], np.sin(ang)[..., None, :]
    x1, x2 = np.split(np.asarray(x, np.float64), 2, axis=-1)
    np.testing.assert_allclose(np.asarray(layers.apply_rope(x, pos, 1e4, cfg.yarn_factor)),
                               np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1),
                               atol=2e-3)
    assert attention.mla_softmax_mscale(get_config("deepseek_v2_236b")) == 1.0


def _rope_before(x, positions, theta):
    """apply_rope as it was before YaRN."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angles = positions[..., None].astype(jnp.float32) * freqs
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def test_default_rope_is_unchanged():
    """Without YaRN the rotation lowers to the same ops and the same bits."""
    cfg = get_config("qwen15_4b")
    x = jax.random.normal(jax.random.key(1), (2, 7, 4, 128), jnp.bfloat16)
    pos = jnp.arange(7)[None] + 1000
    before = jax.make_jaxpr(lambda a, p: _rope_before(a, p, cfg.rope_theta))(x, pos)
    rope = lambda a, p: layers.apply_rope(a, p, cfg.rope_theta, cfg.yarn_factor)  # noqa: E731
    assert str(jax.make_jaxpr(rope)(x, pos)) == str(before)
    np.testing.assert_array_equal(np.asarray(rope(x, pos)),
                                  np.asarray(_rope_before(x, pos, cfg.rope_theta)))


def test_mla_without_q_lora_projects_queries_directly():
    cfg = _cfg()
    p = attention.init_mla(jax.random.key(0), cfg)
    H, dq = cfg.n_heads, cfg.qk_nope_dim + cfg.qk_rope_dim
    assert p["w_q"]["w"].shape == (cfg.d_model, H * dq)
    assert not {"w_dq", "q_norm", "w_uq"} & set(p)
    lora = attention.init_mla(jax.random.key(0), get_config("deepseek_v2_236b").reduced())
    assert {"w_dq", "q_norm", "w_uq"} <= set(lora) and "w_q" not in lora


def _moe_params(cfg, seed=0):
    return moe.init_moe(jax.random.key(seed), cfg)       # bf16 experts, fp32 router


def test_held_shares_add_up_to_the_uncut_layer():
    """Eight chips' shares of one MoE layer, with the shared experts
    counted once, give the reference's uncut layer and the program's.
    Chip c holds experts 8c .. 8c+7, which its router lists first: the
    whole router with its columns rotated by 8c."""
    whole = _cfg(experts_held=0)
    share = _cfg()
    p = _moe_params(whole)
    x = jax.random.normal(jax.random.key(2), (16, 1, whole.d_model), jnp.float32)
    x = x.astype(jnp.bfloat16)
    total = 0.0
    for c in range(8):
        pc = {"router": {"w": jnp.roll(p["router"]["w"], -8 * c, axis=1)},
              "experts": jax.tree_util.tree_map(lambda a: a[8 * c:8 * c + 8], p["experts"])}
        if c == 0:
            pc["shared"] = p["shared"]
        y, _ = moe.moe_apply(pc, x, share)
        total = total + np.asarray(y, np.float32)
    uncut, _ = moe.moe_apply(p, x, whole)
    want = np.asarray(ref.moe_ffn(p, x.astype(jnp.float32), ref.dims(whole)))
    scale = np.abs(want).max()
    # the shares round in bf16 one by one, the uncut layer once
    np.testing.assert_allclose(total, np.asarray(uncut, np.float32), atol=2e-2 * scale)
    np.testing.assert_allclose(total, want, atol=2e-2 * scale)
    # a chip's share alone is not the layer
    y0, _ = moe.moe_apply({**p, "experts": jax.tree_util.tree_map(lambda a: a[:8], p["experts"])},
                          x, share)
    assert np.abs(np.asarray(y0, np.float32) - want).max() > 0.1 * scale


def test_decode_drops_no_token():
    """A zero router ties every expert, so every token picks experts 0-5,
    all held: 64 tokens on each, where the training capacity
    ``int(T·K/E·1.25) + 1`` holds 8."""
    cfg = _cfg()
    p = _moe_params(cfg)
    p["router"]["w"] = jnp.zeros_like(p["router"]["w"])
    T = 64
    assert int(T * cfg.experts_per_token / cfg.n_experts * cfg.capacity_factor) + 1 < T
    x = jax.random.normal(jax.random.key(3), (T, 1, cfg.d_model), jnp.float32).astype(jnp.bfloat16)
    y, _ = moe.moe_apply(p, x, cfg)
    h = x.astype(jnp.float32)[:, 0]
    ex = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p["experts"])
    want = sum((jax.nn.silu(h @ ex["w_gate"][e]) * (h @ ex["w_up"][e])) @ ex["w_down"][e]
               for e in range(cfg.experts_per_token)) / cfg.n_experts
    sh = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p["shared"])
    want = want + (jax.nn.silu(h @ sh["w_gate"]) * (h @ sh["w_up"])) @ sh["w_down"]
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(y, np.float32)[:, 0], np.asarray(want),
                               atol=2e-2 * scale)
    np.testing.assert_allclose(np.asarray(ref.moe_ffn(p, h, ref.dims(cfg))), np.asarray(want),
                               atol=1e-5 * scale)


@pytest.mark.parametrize("B,S", [(64, 1), (4, 16)])
def test_capacity_is_the_token_count_in_decode_alone(B, S, monkeypatch):
    """Decode (one position a sequence) holds every token; any other
    forward, a small training batch included, keeps the training capacity
    ``int(T·K/E·capacity_factor) + 1``."""
    cfg = _cfg()
    seen = []
    real = moe._dispatch_one

    def spy(xt, idx, C, E):
        seen.append((C, E))
        return real(xt, idx, C, E)

    monkeypatch.setattr(moe, "_dispatch_one", spy)
    x = jax.random.normal(jax.random.key(5), (B, S, cfg.d_model), jnp.float32)
    moe.moe_apply(_moe_params(cfg), x.astype(jnp.bfloat16), cfg)
    T = B * S
    train = int(T * cfg.experts_per_token / cfg.n_experts * cfg.capacity_factor) + 1
    assert seen == [(T if S == 1 else train, cfg.experts_held)]
    assert train < T


def test_gates_are_renormalised_only_when_asked():
    cfg = _cfg(experts_held=0)
    p = _moe_params(cfg)
    x = jax.random.normal(jax.random.key(4), (8, 1, cfg.d_model), jnp.float32).astype(jnp.bfloat16)
    plain, _ = moe.moe_apply(p, x, cfg)
    norm, _ = moe.moe_apply(p, x, dataclasses.replace(cfg, norm_topk_prob=True))
    h = x.astype(jnp.float32)
    for flag, got in ((False, plain), (True, norm)):
        want = ref.moe_ffn(p, h, ref.dims(dataclasses.replace(cfg, norm_topk_prob=flag)))
        scale = float(jnp.abs(want).max())
        np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                                   atol=2e-2 * scale)


def test_decode_matches_forward():
    """Teacher-forced decode reproduces the training forward (YaRN, the
    direct query and the mscale² on both), with a capacity factor at which
    the forward drops no token either."""
    cfg = get_config("deepseek_v2_lite").reduced()
    cfg = dataclasses.replace(cfg, capacity_factor=cfg.n_experts / cfg.experts_per_token)
    model = build_model(cfg)
    params = model.init(jax.random.key(1))
    B, S = 1, 24
    tokens = jnp.asarray(np.random.default_rng(2).integers(0, cfg.vocab_size, (B, S)))
    fwd, _ = jax.jit(model.forward)(params, {"tokens": tokens, "labels": tokens})
    st = model.init_decode_state(B, S, start_pos=0)
    step = jax.jit(model.decode_step)
    dec = []
    for t in range(S):
        lg, st = step(params, st, tokens[:, t:t + 1])
        dec.append(np.asarray(lg[:, 0], np.float32))
    dec, fwd = np.stack(dec, axis=1), np.asarray(fwd, np.float32)
    # absorbed-matmul decode reassociates the train-side bf16 chain
    np.testing.assert_array_equal(dec.argmax(-1), fwd.argmax(-1))
    assert np.abs(dec - fwd).mean() < 5e-2


@pytest.mark.parametrize("seed", [11, 12])
def test_ring_and_decode_step_match_the_reference(seed):
    """Greedy decode from position 0 through the compressed ring, and plain
    ``decode_step``, against the reference's full forward pass over the
    same tokens, in logits; the fp8 reference does not pass."""
    cfg = _cfg()
    model = build_model(cfg)
    params = _params(cfg, seed)
    store = CompressedParamStore.from_params(params)
    ring = make_compressed_serve_step(model, store)
    dec = jax.jit(model.decode_step)
    B, S = 4, 10
    s_ring = s_dec = model.init_decode_state(B, S, start_pos=0)
    tok = jax.random.randint(jax.random.key(seed), (B, 1), 0, cfg.vocab_size, dtype=jnp.int32)
    fed, by_ring, by_dec = [], [], []
    for _ in range(S):
        fed.append(tok)
        a, s_ring = ring(s_ring, tok)
        b, s_dec = dec(params, s_dec, tok)
        by_ring.append(np.asarray(a[:, 0]))
        by_dec.append(np.asarray(b[:, 0]))
        tok = jnp.argmax(a[:, -1:], axis=-1).astype(jnp.int32)
    fed = jnp.concatenate(fed, axis=1)
    empty = (jnp.zeros((cfg.n_layers, B, 0, cfg.kv_lora_rank), jnp.bfloat16),
             jnp.zeros((cfg.n_layers, B, 0, cfg.qk_rope_dim), jnp.bfloat16))
    want = np.asarray(ref.logits(params, *empty, fed, cfg))
    assert np.abs(np.stack(by_ring, 1) - want).max() <= LOGIT_TOL
    assert np.abs(np.stack(by_dec, 1) - want).max() <= LOGIT_TOL
    low = np.asarray(ref.logits(ref.lower_precision(params), *empty, fed, cfg))
    assert np.abs(low - want).max() > LOGIT_TOL


def test_ring_counts_raw_bytes_and_spans_expert_decode(tmp_path):
    """``ring_raw_bytes.<kind>`` grows by each kind's raw layer bytes a
    step; ``znn.ring.experts`` wraps every MoE layer's decode job, inside
    ``znn.ring.decode``."""
    cfg = _cfg()
    model = build_model(cfg)
    params = _params(cfg, 5)
    store = CompressedParamStore.from_params(params)
    ring = make_compressed_serve_step(model, store)
    st = model.init_decode_state(2, 4, start_pos=0)
    tok = jnp.zeros((2, 1), jnp.int32)
    raw = {k: sum(m["raw_bytes"] for m in store._stacks[k]) for k in store.stack_keys}
    tracing.reset()
    with jax.profiler.trace(str(tmp_path / "trace")):
        for _ in range(2):
            logits, st = ring(st, tok)
        jax.block_until_ready(logits)
    c = tracing.counters()
    assert c["ring_raw_bytes.dense"] == 2 * raw["dense_layers"]
    assert c["ring_raw_bytes.moe"] == 2 * raw["moe_layers"]
    n_moe = cfg.n_layers - cfg.first_k_dense
    experts = [r for r in tracing.records() if r[0] == "znn.ring.experts"]
    assert len(experts) == 2 * n_moe
    assert all(r[4] == "worker" and r[5] == "znn.ring.decode" for r in experts)
    tracing.reset()
