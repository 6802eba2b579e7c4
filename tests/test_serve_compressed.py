"""Compressed-resident serving: the prefetch/decode ring must be invisible.

The contract under test: ``make_compressed_serve_step`` over a
``CompressedParamStore`` produces **bit-identical** logits and decode state
to the uncompressed ``model.decode_step`` — across model families, ring
depths, and the ``backend`` × ``entropy_backend`` knobs — while never
holding more than ``ring`` decoded layers (``store.peak_resident``).

Plus regression tests for the decode-surface bugfixes that shipped with
the ring: ``delta_decompress`` base validation, assert-free integrity
guards, ``greedy_generate`` degenerate shapes, and the
``decompress_pytree(device_resident=True)`` path the store builds on.
"""

import ast
import dataclasses
import inspect

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import zipnn
from repro.models import build_model
from repro.serve import CompressedParamStore, make_compressed_serve_step
from repro.serve.step import greedy_generate


def _tiny(name: str):
    cfg = get_config(name).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _lockstep(cfg, model, params, cstep, steps=3, seed=0):
    """Drive jit(decode_step) and the ring step on the same tokens; return
    True iff logits AND every state leaf match bit for bit at every step."""
    step = jax.jit(model.decode_step)
    B = 2
    sa = model.init_decode_state(B, steps, start_pos=0)
    sb = model.init_decode_state(B, steps, start_pos=0)
    rng = np.random.default_rng(seed)
    for _ in range(steps):
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 1)), jnp.int32)
        la, sa = step(params, sa, toks)
        lb, sb = cstep(sb, toks)
        if np.asarray(la).tobytes() != np.asarray(lb).tobytes():
            return False
        for k in sa:
            if np.asarray(sa[k]).tobytes() != np.asarray(sb[k]).tobytes():
                return False
    return True


class TestCompressedServe:
    @pytest.mark.parametrize(
        "arch",
        [
            "repro_gpt_100m",      # dense
            "olmoe_1b_7b",         # moe (first_k_dense == 0)
            "deepseek_v2_236b",    # moe with dense prefix + MLA caches
            "mamba2_130m",         # ssm
        ],
    )
    def test_ring_bit_identical_per_family(self, arch):
        cfg, model, params = _tiny(arch)
        store = CompressedParamStore.from_params(params)
        cstep = make_compressed_serve_step(model, store)
        assert _lockstep(cfg, model, params, cstep)
        assert 1 <= store.peak_resident <= 2      # the double-buffer claim
        assert store.resident_count == 0          # every slot released
        assert store.comp_bytes < store.raw_bytes # actually compressed

    @pytest.mark.parametrize("arch", ["repro_gpt_100m", "mamba2_130m"])
    def test_ring_bit_identical_to_layer_at_a_time_step(self, arch):
        """The reference the ring matches on every platform, the TPU
        included: decode_step one layer at a time (scan_layers=False)."""
        cfg, model, params = _tiny(arch)
        unrolled = build_model(dataclasses.replace(cfg, scan_layers=False))
        store = CompressedParamStore.from_params(params)
        cstep = make_compressed_serve_step(model, store)
        assert _lockstep(cfg, unrolled, params, cstep)

    @pytest.mark.parametrize("ring,prefetch", [(1, False), (2, True), (3, True)])
    def test_ring_depths(self, ring, prefetch):
        cfg, model, params = _tiny("repro_gpt_100m")
        store = CompressedParamStore.from_params(params)
        cstep = make_compressed_serve_step(
            model, store, ring=ring, prefetch=prefetch
        )
        assert _lockstep(cfg, model, params, cstep, steps=2)
        assert store.peak_resident <= ring

    def test_knob_sweep_bit_identical(self):
        """Ring decode across backend × entropy_backend (host fallback and
        the device Huffman decoder) — logits identical on every combo."""
        cfg, model, params = _tiny("repro_gpt_100m")
        combos = [
            dict(backend=None, entropy_backend=None),        # host default
            dict(backend="host", entropy_backend="host", threads=2),
            dict(backend="device", entropy_backend="device"),
        ]
        huff = zipnn.ZipNNConfig(backend="huffman")
        for knobs in combos:
            store = CompressedParamStore.from_params(params, huff, **knobs)
            cstep = make_compressed_serve_step(model, store)
            assert _lockstep(cfg, model, params, cstep, steps=1), knobs
            assert store.peak_resident <= 2

    def test_store_payloads_knob_independent(self):
        """Two stores from the same params hold byte-identical payloads
        regardless of knobs — the determinism contract applied at rest."""
        _, _, params = _tiny("repro_gpt_100m")
        a = CompressedParamStore.from_params(params)
        b = CompressedParamStore.from_params(params, threads=2)
        for key in a.stack_keys:
            for i in range(a.n_layers(key)):
                la = [c.blob for c in a._stacks[key][i]["leaves"]]
                lb = [c.blob for c in b._stacks[key][i]["leaves"]]
                assert la == lb

    def test_hybrid_rejected(self):
        cfg = get_config("zamba2_7b").reduced()
        model = build_model(cfg)
        with pytest.raises(NotImplementedError):
            make_compressed_serve_step(model, CompressedParamStore())

    def test_layer_count_mismatch_rejected(self):
        cfg, model, params = _tiny("repro_gpt_100m")
        store = CompressedParamStore()              # empty: 0 layers
        store.static = dict(params)
        with pytest.raises(ValueError, match="layers"):
            make_compressed_serve_step(model, store)

    def test_footprint_accounting(self):
        _, _, params = _tiny("repro_gpt_100m")
        store = CompressedParamStore.from_params(params)
        assert 0 < store.ratio_pct < 100
        assert store.max_layer_raw_bytes > 0
        # footprint = payloads + static + ring slots, monotone in ring
        assert store.footprint_bytes(2) > store.footprint_bytes(1)
        assert (
            store.footprint_bytes(2)
            == store.comp_bytes + store.static_bytes
            + 2 * store.max_layer_raw_bytes
        )


class TestDecompressPytreeDeviceResident:
    def _manifest(self):
        rng = np.random.default_rng(0)
        tree = {
            "a": rng.standard_normal((64, 32)).astype(ml_dtypes.bfloat16),
            "b": rng.standard_normal((128,)).astype(np.float32),
        }
        return tree, zipnn.compress_pytree(tree, zipnn.ZipNNConfig(backend="huffman"))

    def test_device_resident_tree(self):
        tree, manifest = self._manifest()
        out = zipnn.decompress_pytree(
            manifest, zipnn.ZipNNConfig(backend="huffman"),
            backend="device", entropy_backend="device", device_resident=True,
        )
        for k, ref in tree.items():
            leaf = out[k]
            assert not isinstance(leaf, np.ndarray)   # stayed a jax.Array
            assert np.asarray(leaf).tobytes() == ref.tobytes()

    def test_host_resolved_leaves_fall_back_to_numpy(self):
        tree, manifest = self._manifest()
        out = zipnn.decompress_pytree(manifest, device_resident=True)
        for k, ref in tree.items():
            assert isinstance(out[k], np.ndarray)
            assert out[k].tobytes() == ref.tobytes()

    def test_manager_batched_full_restore(self, tmp_path):
        from repro.checkpoint.manager import CheckpointConfig, CheckpointManager

        rng = np.random.default_rng(1)
        tree = {
            "w": rng.standard_normal((32, 16)).astype(ml_dtypes.bfloat16),
            "b": rng.standard_normal((16,)).astype(np.float32),
        }
        mgr = CheckpointManager(
            CheckpointConfig(directory=str(tmp_path), async_save=False)
        )
        mgr.save(0, tree)
        s, back = mgr.restore()
        assert s == 0
        for k in tree:
            assert np.asarray(back[k]).tobytes() == tree[k].tobytes()


class TestDeltaDecompressValidation:
    def test_mismatched_base_raises_cleanly(self):
        rng = np.random.default_rng(0)
        base = rng.standard_normal((8, 8)).astype(np.float32)
        new = base.copy()
        new[0, 0] += 1.0
        ct = zipnn.delta_compress(new, base)
        with pytest.raises(ValueError, match="delta requires matching"):
            zipnn.delta_decompress(ct, base[:4])               # wrong shape
        with pytest.raises(ValueError, match="delta requires matching"):
            zipnn.delta_decompress(ct, base.astype(np.float16))  # wrong dtype
        with pytest.raises(ValueError, match="delta requires matching"):
            zipnn.delta_decompress(ct, base[:4], backend="device")
        # the matching base still round-trips
        out = zipnn.delta_decompress(ct, base)
        assert out.tobytes() == new.tobytes()


class TestIntegrityGuardsAreRealExceptions:
    MODULES = (
        "repro.checkpoint.hub",
        "repro.distributed.grad_sync",
        "repro.core.container",
        "repro.core.codec",
        "repro.core.zipnn",
        "repro.checkpoint.manager",
    )

    def test_no_bare_asserts_on_integrity_surface(self):
        """Integrity checks must survive ``python -O``: no ``assert``
        statements anywhere in the audited decode/transfer modules."""
        import importlib

        for name in self.MODULES:
            mod = importlib.import_module(name)
            tree = ast.parse(inspect.getsource(mod))
            offenders = [
                n.lineno for n in ast.walk(tree) if isinstance(n, ast.Assert)
            ]
            assert not offenders, f"{name} has assert at lines {offenders}"

    def test_hub_lossless_guard_raises(self, monkeypatch):
        from repro.checkpoint import hub

        monkeypatch.setattr(
            hub.zipnn, "decompress_bytes", lambda *a, **k: b"corrupt"
        )
        with pytest.raises(IOError, match="lossless"):
            hub.simulate_transfer(
                np.zeros(64, np.float32).tobytes(), "float32",
                "cached_download_cloud",
            )

    def test_codec_table_blob_guard(self):
        from repro.core import codec

        pc = codec.PlaneCodec(codec.CodecParams(chunk_bytes=256))
        with pytest.raises(RuntimeError, match="build_table"):
            pc.table_blob()


class TestGreedyGenerateDegenerate:
    @pytest.fixture(scope="class")
    def dense(self):
        return _tiny("repro_gpt_100m")

    def test_empty_prompt_raises(self, dense):
        _, model, params = dense
        with pytest.raises(ValueError, match="at least one token"):
            greedy_generate(model, params, jnp.zeros((2, 0), jnp.int32), 4)

    def test_negative_steps_raises(self, dense):
        _, model, params = dense
        with pytest.raises(ValueError, match="steps"):
            greedy_generate(model, params, jnp.zeros((1, 2), jnp.int32), -1)

    def test_bad_rank_raises(self, dense):
        _, model, params = dense
        with pytest.raises(ValueError, match="\\(B, S\\)"):
            greedy_generate(model, params, jnp.zeros((4,), jnp.int32), 1)

    def test_zero_steps_returns_empty(self, dense):
        cfg, model, params = dense
        prompt = jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 3)),
            jnp.int32,
        )
        out, state = greedy_generate(model, params, prompt, 0)
        assert out.shape == (2, 0) and out.dtype == jnp.int32
        assert int(state["pos"]) == 3          # prompt fed through the cache

    def test_single_token_prompt(self, dense):
        cfg, model, params = dense
        out, _ = greedy_generate(
            model, params, jnp.ones((1, 1), jnp.int32), 2
        )
        assert out.shape == (1, 2)
        assert int(jnp.max(out)) < cfg.vocab_size
