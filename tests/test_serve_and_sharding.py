"""Coverage for serving (generation loop, cache specs), sharding rules,
container format details, and stats/classification."""

import dataclasses

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.core import container, stats, zipnn
from repro.core.codec import ChunkEntry, Method
from repro.distributed import sharding
from repro.models import build_model
from repro.serve.step import decode_state_specs, greedy_generate, inference_param_specs


class TestGeneration:
    def test_greedy_generate_deterministic(self):
        cfg = get_config("repro_gpt_100m").reduced()
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        prompt = jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 8)), jnp.int32
        )
        out1, _ = greedy_generate(model, params, prompt, 6)
        out2, _ = greedy_generate(model, params, prompt, 6)
        np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
        assert out1.shape == (2, 6)
        assert int(jnp.max(out1)) < cfg.vocab_size

    def test_swa_ring_generation_past_window(self):
        """Generate beyond the SWA window: the ring cache must wrap without
        shape errors and keep producing valid tokens."""
        cfg = dataclasses.replace(
            get_config("h2o_danube3_4b").reduced(), window=16
        )
        model = build_model(cfg)
        params = model.init(jax.random.key(1))
        B, S, gen = 1, 8, 16                   # prompt+gen > window
        state = model.init_decode_state(B, S + gen, start_pos=0)
        assert state["kv_k"].shape[2] == 16    # ring == window
        step = jax.jit(model.decode_step)
        tok = jnp.zeros((B, 1), jnp.int32)
        for _ in range(S + gen):
            logits, state = step(params, state, tok)
            assert bool(jnp.isfinite(logits).all())
            tok = jnp.argmax(logits, -1).astype(jnp.int32)


class TestShardingRules:
    def _specs(self, arch):
        cfg = get_config(arch)
        model = build_model(cfg)
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        # use a fake big mesh for divisibility logic
        class FakeMesh:
            axis_names = ("data", "model")
            shape = {"data": 16, "model": 16}
        return model.abstract_params(), sharding.param_pspecs(
            model.abstract_params(), zero3=cfg.zero3, mesh=FakeMesh()
        )

    def test_mlp_weights_are_sharded(self):
        params, specs = self._specs("yi_6b")
        wg = specs["layers"]["mlp"]["w_gate"]
        assert wg == P(None, "data", "model")      # (L, d-zero3, ff-model)
        wd = specs["layers"]["mlp"]["w_down"]
        assert wd == P(None, "model", "data")

    def test_attention_and_embed_rules(self):
        params, specs = self._specs("yi_6b")
        assert specs["layers"]["attn"]["wq"]["w"] == P(None, "data", "model")
        assert specs["embed"]["table"] == P("model", "data")

    def test_experts_rule_precedence(self):
        params, specs = self._specs("deepseek_v2_236b")
        we = specs["moe_layers"]["moe"]["experts"]["w_gate"]
        assert we == P(None, "model", "data", None)  # (L, E-model, d-zero3, f)

    def test_indivisible_dims_fall_back(self):
        params, specs = self._specs("mamba2_130m")   # vocab 50280 % 16 != 0
        assert specs["embed"]["table"][0] is None

    def test_inference_specs_strip_zero3(self):
        cfg = get_config("deepseek_v2_236b")
        model = build_model(cfg)

        class FakeMesh:
            axis_names = ("data", "model")
            shape = {"data": 16, "model": 16}

        specs = inference_param_specs(model, FakeMesh())
        # dense weights: no 'data' axis anywhere
        q = specs["moe_layers"]["attn"]["w_uq"]["w"]
        assert "data" not in [a for a in q if a]
        # experts: E over data, ff over model
        we = specs["moe_layers"]["moe"]["experts"]["w_gate"]
        assert we == P(None, "data", None, "model")

    def test_decode_state_specs_prefer_length_sharding(self):
        cfg = get_config("qwen15_4b")              # kv=20 ∤ 16
        model = build_model(cfg)
        state = jax.eval_shape(lambda: model.init_decode_state(128, 1024))

        class FakeMesh:
            axis_names = ("data", "model")
            shape = {"data": 16, "model": 16}

        specs = decode_state_specs(model, state, FakeMesh())
        assert specs["kv_k"] == P(None, "data", "model", None, None)

    def test_lshard_noop_without_mesh(self):
        x = jnp.ones((4, 4))
        y = sharding.lshard(x, "batch", None)
        assert y is x


class TestContainerFormat:
    def test_metadata_map_enables_random_access(self):
        rng = np.random.default_rng(0)
        w = (rng.standard_normal(300_000) * 0.02).astype(ml_dtypes.bfloat16)
        blob = zipnn.compress_bytes(
            np.ascontiguousarray(w).view(np.uint8), "bfloat16"
        )
        meta, mv = container.unpack_stream(bytes(blob))
        assert meta.layout_name == "bf16"
        assert meta.n_planes == 2
        # every payload offset is consistent with the declared lengths
        for pl in range(meta.n_planes):
            for c, e in enumerate(meta.entries[pl]):
                view = container.payload_view(meta, mv, pl, c)
                assert len(view) == e.comp_len

    def test_bad_magic_rejected(self):
        with pytest.raises(ValueError):
            container.unpack_stream(b"NOPE" + b"\x00" * 64)

    def test_entry_methods_recorded(self):
        rng = np.random.default_rng(1)
        w = (rng.standard_normal(300_000) * 0.02).astype(ml_dtypes.bfloat16)
        blob = zipnn.compress_bytes(np.ascontiguousarray(w).view(np.uint8), "bfloat16")
        meta, _ = container.unpack_stream(bytes(blob))
        exp_methods = {e.method for e in meta.entries[0]}
        frac_methods = {e.method for e in meta.entries[1]}
        assert exp_methods <= {Method.HUFF, Method.HUFFLIB}   # compressible
        assert frac_methods == {Method.STORE}                 # random fraction


class TestStats:
    def test_classify_regular_vs_clean(self):
        rng = np.random.default_rng(0)
        regular = [(rng.standard_normal(100_000) * 0.02).astype(np.float32)]
        assert stats.classify_model(regular) == "regular"
        u = regular[0].view(np.uint32) & np.uint32(0xFFFFF000)
        clean = [u.view(np.float32).copy()]
        assert stats.classify_model(clean) == "clean"

    def test_byte_entropy_bounds(self):
        assert stats.byte_entropy(np.zeros(1000, np.uint8)) == 0.0
        rnd = np.random.default_rng(0).integers(0, 256, 100_000).astype(np.uint8)
        assert 7.9 < stats.byte_entropy(rnd) <= 8.0


class TestMesh:
    def test_make_host_mesh(self):
        from repro.launch.mesh import make_host_mesh, n_chips

        mesh = make_host_mesh()
        assert n_chips(mesh) == 1
        assert tuple(mesh.axis_names) == ("data", "model")

    def test_make_model_mesh(self):
        from repro.launch.mesh import make_model_mesh, n_chips

        mesh = make_model_mesh(1)
        assert n_chips(mesh) == 1
        assert dict(mesh.shape) == {"data": 1, "model": 1}
        assert set(mesh.axis_types) == {jax.sharding.AxisType.Auto}


class TestServeLauncher:
    def test_serves_from_checkpoint(self, tmp_path, monkeypatch, capsys):
        """launch/serve.py restores a ZipNN checkpoint and generates from
        it; on a CPU host the restore stays on the numpy path."""
        import sys

        from repro.checkpoint import CheckpointConfig, CheckpointManager
        from repro.launch import serve

        cfg = get_config("repro_gpt_100m").reduced()
        params = build_model(cfg).init(jax.random.key(0))
        CheckpointManager(CheckpointConfig(str(tmp_path))).save(
            5, {"params": params}, blocking=True
        )
        argv = ["serve", "--arch", "repro_gpt_100m", "--reduced",
                "--ckpt-dir", str(tmp_path), "--batch", "2",
                "--prompt-len", "4", "--gen", "3"]
        monkeypatch.setattr(sys, "argv", argv)
        serve.main()
        out = capsys.readouterr().out
        assert "restored step 5" in out
        prompt = jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 4)), jnp.int32
        )
        want, _ = greedy_generate(build_model(cfg), params, prompt, 3)
        assert f"first sequence: {np.asarray(want[0]).tolist()}" in out

