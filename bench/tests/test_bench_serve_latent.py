"""The latent-attention MoE serve cell (``kinds/serve_latent.py``): its
mapping of the published keys, its sound and faulty runs, and the readers
of the ring's expert spans, at a size a CPU holds."""

import dataclasses
import json
import math
import sys
import time
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_runs as runs  # noqa: E402
from test_bench_faults_save_serve import _serve_faults  # noqa: E402

CELL = "serve.deepseek_v2_lite-L5"


def _run(seconds=0.5, control=False):
    """A whole run at the Qwen cell's test widths.  Under the HuBERT widths
    ``bench_runs`` gives every other cell, logits have a std of 0.16 and a
    stale cache moves a served logit by less than the cell's limit (0.089
    on the CPU; there the comparison of cache entries catches it); here by
    0.77."""
    import io
    import tempfile

    from bench import harness

    log = io.StringIO()
    with tempfile.TemporaryDirectory() as work:
        r = harness.run_cell(CELL, 2**33 + 5, seconds, False, t_start=time.perf_counter(),
                             require_tpu=False, overrides={**runs.overrides(CELL),
                                                           "model": runs.QWEN},
                             control=control, workroot=Path(work), spec=runs.spec(), log=log)
    r["log"] = log.getvalue()
    return r


def _config():
    from bench import harness

    return harness.load_cell(runs.spec(), CELL)["config"]


def test_published_keys_map_onto_the_program():
    from bench.kinds import serve_latent

    cfg = serve_latent.model_config(_config())
    assert (cfg.n_layers, cfg.first_k_dense, cfg.d_model, cfg.n_heads, cfg.vocab_size) == (
        5, 1, 2048, 16, 102400)
    assert (cfg.dense_d_ff, cfg.moe_d_ff, cfg.n_shared_experts) == (10944, 1408, 2)
    assert (cfg.n_experts, cfg.experts_held, cfg.experts_per_token) == (64, 8, 6)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim,
            cfg.v_head_dim) == (0, 512, 128, 64, 128)
    assert not cfg.norm_topk_prob and cfg.norm_eps == 1e-6 and cfg.rope_theta == 10000
    assert (cfg.yarn_factor, cfg.yarn_mscale_all_dim) == (40, 0.707)


def test_a_smaller_model_takes_the_widths_it_does_not_name_from_reduced():
    """Under a test's model overrides, the expert, dense and latent widths
    and the head dims come from the registry's ``reduced()``; what the
    overrides name wins; depth, routing and YaRN stay the file's."""
    from bench.kinds import serve_latent
    from repro.configs import get_config

    small = get_config("deepseek_v2_lite").reduced()
    ov = runs.overrides(CELL)["model"]
    cfg = serve_latent.model_config(_config(), ov)
    for f in serve_latent.WIDTHS:
        assert getattr(cfg, f) == getattr(small, f), f
    for f, v in ov.items():
        assert getattr(cfg, f) == v, f
    assert (cfg.n_layers, cfg.n_experts, cfg.experts_held, cfg.experts_per_token) == (5, 64, 8, 6)
    assert cfg.yarn_factor == 40 and cfg.q_lora_rank == 0
    named = serve_latent.model_config(_config(), {**ov, "kv_lora_rank": 24})
    assert named.kv_lora_rank == 24 and named.moe_d_ff == small.moe_d_ff


@pytest.mark.parametrize("key,value", [("scoring_func", "sigmoid"), ("routed_scaling_factor", 2.5),
                                       ("topk_method", "group_limited_greedy")])
def test_routing_the_program_does_not_compute_is_refused(key, value):
    from bench.kinds import serve_latent

    with pytest.raises(ValueError, match=key):
        serve_latent.model_config({**_config(), key: value})


@pytest.mark.parametrize("key,value", [("original_max_position_embeddings", 8192),
                                       ("beta_fast", 16), ("beta_slow", 2), ("mscale", 1.0),
                                       ("type", "linear")])
def test_yarn_the_program_does_not_compute_is_refused(key, value):
    from bench.kinds import serve_latent

    config = _config()
    with pytest.raises(ValueError, match="rope_scaling"):
        serve_latent.model_config({**config, "rope_scaling": {**config["rope_scaling"],
                                                              key: value}})


def test_sound_run_is_correct():
    r = _run()
    assert r["correct"], r["log"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert "compiles=0" in r["log"]
    assert set(r["metrics"]) == {"decode_tokens_per_s", "stored_per_raw", "setup_s"}
    for c in r["compared"].values():
        assert c["value"] < c["limit"]
    assert set(r["compared"]) == {"served_logit_gap", "cache_entry_error"}
    json.dumps(r)


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
def test_fault_is_not_correct(fault, monkeypatch):
    _serve_faults(fault, monkeypatch)
    r = _run(seconds=2.0)        # several steps: a stale ring state shows from the second
    assert not r["correct"], r["log"]


def test_a_ring_that_writes_no_cache_entry_is_not_correct(monkeypatch):
    """At the HuBERT widths a stale ring state moves no served logit past
    its limit; the cache entries the ring should have written are compared
    on their own."""
    _serve_faults("unchanged", monkeypatch)
    r = runs.run(CELL, seconds=2.0)
    entry = r["compared"]["cache_entry_error"]
    assert not r["correct"] and entry["value"] > entry["limit"], r["log"]


def test_control_through_the_cell_is_not_correct():
    """The cell's own check with the fp8 reference in the ring's place."""
    r = _run(control=True)
    assert not r["correct"], r["log"]
    assert r["control"]["control_gap"] == r["compared"]["served_logit_gap"]["value"]
    assert r["control"]["served_gap"] < r["compared"]["served_logit_gap"]["limit"]


def test_readers_read_the_rings_expert_spans(tmp_path):
    import jax

    from bench import harness
    from repro.core import tracing

    parts = harness.load_cell(runs.spec(), CELL)
    ov = runs.overrides(CELL)
    config = dict(parts["config"])
    config["codec"] = {**config["codec"], **ov["codec"]}
    run = harness.Run(CELL, config, {**parts["traffic"], **ov["traffic"]}, 2**33 + 7,
                      tmp_path / "work", ov)
    run.workdir.mkdir()
    run.extra["control"] = False
    kind = parts["kind"]
    kind.setup(run, jax)
    names = ["ring_wait_share.serve", "expert_decode_share.serve_latent",
             "ring_decode_GBps.serve"]
    readers = {name: harness.load_metric(name) for name in names}

    tracing.reset()
    empty = {"window_s": 1.0, "ops": 1, "run": run, "trace": None, "peaks": None}
    assert all(read(empty) is None for read in readers.values())
    ops = 2
    with jax.profiler.trace(str(tmp_path / "trace")):
        w0 = time.perf_counter()
        for i in range(ops):
            kind.step(run, jax, i)
        window_s = time.perf_counter() - w0
    m = {"window_s": window_s, "ops": ops, "run": run, "trace": None, "peaks": None}
    values = {name: read(m) for name, read in readers.items()}
    traced = tracing.snapshot()["traced"]
    store = run.extra["store"]
    kind.release(run, jax)
    for name, v in values.items():
        assert v is not None and math.isfinite(v) and 0 < v <= 100.0, (name, v)
    raw = {k: sum(mf["raw_bytes"] for mf in store._stacks[f"{k}_layers"]) for k in ("dense", "moe")}
    assert traced["ring_raw_bytes.moe"] == ops * raw["moe"]
    decode_s = tracing.snapshot()["spans"]["znn.ring.decode"]["worker"]["total_s"]
    assert values["ring_decode_GBps.serve"] == pytest.approx(ops * sum(raw.values()) / decode_s
                                                              / 1e9)
    tracing.reset()
    assert all(read(m) is None for read in readers.values())


def test_reference_control_is_not_correct_at_published_widths():
    """fp8 (e4m3) weights under the reference at the published widths, the
    file's five layers, a slice of the vocabulary and a short prefix so
    that a CPU holds it: the gap of the tokens the fp8 reference puts
    first exceeds the cell's limit."""
    import jax
    import jax.numpy as jnp

    from bench import harness, state
    from bench.kinds import serve_latent
    from bench.reference import deepseek_v2 as ref

    parts = harness.load_cell(runs.spec(), CELL)
    cfg = dataclasses.replace(serve_latent.model_config(parts["config"]), vocab_size=8192)
    params = state.make_filler(state.abstract_params(cfg))(state.seed_key(7))
    key = state.seed_key(8)
    B, P, T = 2, 64, 6
    ckv = jax.random.normal(jax.random.fold_in(key, 0), (cfg.n_layers, B, P, cfg.kv_lora_rank),
                            jnp.bfloat16)
    kr = jax.random.normal(jax.random.fold_in(key, 1), (cfg.n_layers, B, P, cfg.qk_rope_dim),
                           jnp.bfloat16)
    fed = jax.random.randint(jax.random.fold_in(key, 2), (B, T), 0, cfg.vocab_size)
    low = ref.lower_precision(params)
    hid, entries = ref.trunk(params, ckv, kr, fed, cfg)
    hid_low, entries_low = ref.trunk(low, ckv, kr, fed, cfg)
    assert ref.control_gap(params, hid, low, hid_low) > parts["traffic"]["gap_limit"]
    assert ref.entry_error(entries_low, entries) > parts["traffic"]["entry_limit"]
