"""Substrate tests: optimizer, data pipeline, train step (loss decreases),
checkpoint manager (compression, deltas, periodic bases, crash recovery,
async), gradient sync, hub transfer."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointConfig, CheckpointManager
from repro.checkpoint.hub import simulate_transfer
from repro.configs import get_config
from repro.data import DataConfig, batch_specs, make_batch
from repro.distributed.grad_sync import GradSync, straggler_reissue_plan
from repro.models import build_model
from repro.optim import AdamWConfig
from repro.train import init_train_state, make_train_step


@pytest.fixture(scope="module")
def tiny_setup():
    cfg = get_config("repro_gpt_100m").reduced()
    model = build_model(cfg)
    state = init_train_state(model, jax.random.key(0))
    return cfg, model, state


class TestData:
    def test_deterministic_across_restarts(self):
        cfg = get_config("yi_6b").reduced()
        dc = DataConfig(seq_len=32, global_batch=4, seed=7)
        b1 = make_batch(cfg, dc, 5)
        b2 = make_batch(cfg, dc, 5)
        np.testing.assert_array_equal(np.asarray(b1["tokens"]), np.asarray(b2["tokens"]))
        b3 = make_batch(cfg, dc, 6)
        assert not np.array_equal(np.asarray(b1["tokens"]), np.asarray(b3["tokens"]))

    def test_specs_match_batches(self):
        for arch in ["yi_6b", "qwen2_vl_2b", "hubert_xlarge", "mamba2_130m"]:
            cfg = get_config(arch).reduced()
            dc = DataConfig(seq_len=64, global_batch=2)
            specs = batch_specs(cfg, dc)
            batch = make_batch(cfg, dc, 0)
            assert set(specs) == set(batch)
            for k in specs:
                assert specs[k].shape == batch[k].shape, (arch, k)

    def test_tokens_in_vocab(self):
        cfg = get_config("yi_6b").reduced()
        dc = DataConfig(seq_len=128, global_batch=4)
        b = make_batch(cfg, dc, 3)
        assert int(jnp.max(b["tokens"])) < cfg.vocab_size
        assert int(jnp.min(b["tokens"])) >= 0


class TestTrainStep:
    def test_loss_decreases(self, tiny_setup):
        cfg, model, state = tiny_setup
        dc = DataConfig(seq_len=64, global_batch=8)
        ocfg = AdamWConfig(lr=1e-2, warmup_steps=2, total_steps=40)
        step = jax.jit(make_train_step(model, ocfg))
        batch = make_batch(cfg, dc, 0)   # overfit one batch
        losses = []
        for i in range(30):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0] - 1.0, losses[::6]
        assert np.isfinite(losses).all()

    def test_microbatch_equivalence(self, tiny_setup):
        cfg, model, _ = tiny_setup
        state = init_train_state(model, jax.random.key(1))
        dc = DataConfig(seq_len=32, global_batch=8)
        ocfg = AdamWConfig(lr=1e-3)
        batch = make_batch(cfg, dc, 0)
        s1, m1 = jax.jit(make_train_step(model, ocfg, microbatches=1))(state, batch)
        s2, m2 = jax.jit(make_train_step(model, ocfg, microbatches=4))(state, batch)
        # same data, same params → grads should match to accumulation error
        for a, b in zip(
            jax.tree_util.tree_leaves(s1["params"]),
            jax.tree_util.tree_leaves(s2["params"]),
        ):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32), atol=5e-2
            )

    def test_lr_schedule(self):
        from repro.optim import lr_schedule

        ocfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100, min_lr_frac=0.1)
        assert float(lr_schedule(ocfg, jnp.asarray(0))) == 0.0
        assert abs(float(lr_schedule(ocfg, jnp.asarray(10))) - 1.0) < 1e-6
        assert float(lr_schedule(ocfg, jnp.asarray(100))) == pytest.approx(0.1, abs=1e-3)


class TestCheckpointManager:
    def _state(self, seed=0, scale=1.0):
        rng = np.random.default_rng(seed)
        import ml_dtypes

        return {
            "params": {
                "w": (rng.standard_normal((256, 256)) * 0.02 * scale).astype(
                    ml_dtypes.bfloat16
                ),
                "b": np.zeros(256, np.float32),
            },
            "opt": {"m": {"w": (rng.standard_normal((256, 256)) * 1e-4).astype(np.float32)}},
            "step": np.asarray(seed, np.int32),
        }

    def test_save_restore_roundtrip(self, tmp_path):
        mgr = CheckpointManager(CheckpointConfig(str(tmp_path), async_save=False))
        state = self._state(3)
        mgr.save(3, state, blocking=True)
        step, back = mgr.restore()
        assert step == 3
        np.testing.assert_array_equal(
            back["params"]["w"].view(np.uint8), state["params"]["w"].view(np.uint8)
        )
        np.testing.assert_array_equal(back["opt"]["m"]["w"], state["opt"]["m"]["w"])

    def test_periodic_base_and_deltas(self, tmp_path):
        mgr = CheckpointManager(
            CheckpointConfig(str(tmp_path), base_every=3, async_save=False, keep_bases=99)
        )
        base = self._state(0)
        for i in range(6):
            st = self._state(0)
            # small drift: ~1% of weights change per "epoch"
            w = np.asarray(st["params"]["w"], np.float32)
            idx = np.random.default_rng(i).integers(0, w.size, w.size // 100)
            w.reshape(-1)[idx] *= 1.001
            import ml_dtypes

            st["params"]["w"] = w.astype(ml_dtypes.bfloat16)
            st["step"] = np.asarray(i, np.int32)
            mgr.save(i, st, blocking=True)
        stats = mgr.stats()
        kinds = [s["kind"] for s in stats]
        assert kinds == ["base", "delta", "delta", "base", "delta", "delta"]
        # deltas must compress far better than bases
        base_r = [s["ratio_pct"] for s in stats if s["kind"] == "base"]
        delta_r = [s["ratio_pct"] for s in stats if s["kind"] == "delta"]
        assert min(base_r) > 50.0
        assert max(delta_r) < 30.0
        # every delta restores exactly
        for i in range(6):
            _, back = mgr.restore(i)
            assert int(back["step"]) == i

    def test_crash_recovery_skips_torn_checkpoint(self, tmp_path):
        mgr = CheckpointManager(CheckpointConfig(str(tmp_path), async_save=False))
        mgr.save(1, self._state(1), blocking=True)
        mgr.save(2, self._state(2), blocking=True)
        # corrupt the newest one (torn write)
        with open(tmp_path / "step_2" / "data.bin", "r+b") as f:
            f.seek(100)
            f.write(b"\xde\xad\xbe\xef" * 8)
        step, back = mgr.restore()
        assert step == 1 and int(back["step"]) == 1

    def test_async_save(self, tmp_path):
        mgr = CheckpointManager(CheckpointConfig(str(tmp_path), async_save=True))
        mgr.save(7, self._state(7))
        mgr.wait()
        assert mgr.latest_step() == 7

    @pytest.mark.parametrize(
        "async_save, blocking", [(False, True), (True, True), (True, False)]
    )
    def test_failed_save_raises(self, tmp_path, monkeypatch, async_save, blocking):
        """A blocking save raises its own error; an async one at wait()."""
        mgr = CheckpointManager(
            CheckpointConfig(str(tmp_path), async_save=async_save)
        )

        def disk_full(*args, **kwargs):
            raise OSError("disk full")

        monkeypatch.setattr(mgr, "_write", disk_full)
        if blocking:
            with pytest.raises(OSError, match="disk full"):
                mgr.save(1, self._state(1), blocking=True)
        else:
            mgr.save(1, self._state(1))
            with pytest.raises(RuntimeError, match="disk full"):
                mgr.wait()
        mgr.wait()                      # nothing stale is left to raise
        assert mgr.latest_step() is None

    def test_retention_gc(self, tmp_path):
        mgr = CheckpointManager(
            CheckpointConfig(str(tmp_path), base_every=2, keep_bases=1, async_save=False)
        )
        for i in range(6):
            mgr.save(i, self._state(i), blocking=True)
        remaining = sorted(s["step"] for s in mgr.stats())
        assert remaining == [4, 5]          # last base + its delta

    def test_elastic_shard_restore(self, tmp_path):
        from jax.sharding import PartitionSpec as P

        mgr = CheckpointManager(CheckpointConfig(str(tmp_path), async_save=False))
        mgr.save(1, self._state(1), blocking=True)
        mesh = jax.make_mesh((1,), ("data",))
        specs = {
            "params": {"w": P(None, None), "b": P(None)},
            "opt": {"m": {"w": P(None, None)}},
            "step": P(),
        }
        step, tree = mgr.shard_restore(None, mesh, specs)
        assert step == 1
        assert isinstance(tree["params"]["w"], jax.Array)

    def test_resume_counts_from_disk(self, tmp_path):
        cfg = CheckpointConfig(str(tmp_path), base_every=2, async_save=False, keep_bases=99)
        mgr = CheckpointManager(cfg)
        mgr.save(0, self._state(0), blocking=True)
        mgr.save(1, self._state(1), blocking=True)
        # new manager (process restart) must continue the base cadence
        mgr2 = CheckpointManager(cfg)
        mgr2.save(2, self._state(2), blocking=True)
        kinds = [s["kind"] for s in mgr2.stats()]
        assert kinds == ["base", "delta", "base"]


class TestMomentChains:
    """Optimizer-moment compression: AdamW m/v delta-vs-previous-save.

    EMA moments drift a little every step, so vs-prev deltas are much
    sparser than vs-base — moment leaves in delta saves carry kind
    ``delta_prev`` with ``prev_step`` links, bases store moments in full
    (bounding the restore chain at ``base_every``), and every step
    restores bit-exactly through the chain."""

    def _state(self, i, rng):
        import ml_dtypes

        w = (rng.standard_normal((128, 128)) * 0.02).astype(ml_dtypes.bfloat16)
        g = (rng.standard_normal((128, 128)) * 1e-3).astype(np.float32)
        return {
            "params": {"w": w},
            "opt": {
                "m": {"w": g},
                "v": {"w": np.square(g)},
                "count": np.asarray(i, np.int32),
            },
            "step": np.asarray(i, np.int32),
        }

    def _drifted(self, steps, seed=0):
        """A save sequence whose moments drift like EMAs (small per-step
        change), while params drift independently."""
        import ml_dtypes

        rng = np.random.default_rng(seed)
        st = self._state(0, rng)
        out = [st]
        for i in range(1, steps):
            st = {
                "params": {"w": st["params"]["w"]},
                "opt": {
                    "m": {"w": st["opt"]["m"]["w"].copy()},
                    "v": {"w": st["opt"]["v"]["w"].copy()},
                    "count": np.asarray(i, np.int32),
                },
                "step": np.asarray(i, np.int32),
            }
            # ~1% of moment entries move per step (EMA-style slow drift)
            for key in ("m", "v"):
                arr = st["opt"][key]["w"].reshape(-1)
                idx = rng.integers(0, arr.size, arr.size // 100)
                arr[idx] *= 1.01
            w = np.asarray(st["params"]["w"], np.float32)
            idx = rng.integers(0, w.size, w.size // 100)
            w.reshape(-1)[idx] *= 1.001
            st["params"]["w"] = w.astype(ml_dtypes.bfloat16)
            out.append(st)
        return out

    def _manifest(self, tmp_path, step):
        import json

        with open(tmp_path / f"step_{step}" / "manifest.json") as f:
            return json.load(f)

    def test_delta_prev_chain_kinds_and_links(self, tmp_path):
        mgr = CheckpointManager(
            CheckpointConfig(
                str(tmp_path), base_every=3, async_save=False, keep_bases=99
            )
        )
        states = self._drifted(6)
        for i, st in enumerate(states):
            mgr.save(i, st, blocking=True)
        for i in range(6):
            man = self._manifest(tmp_path, i)
            kinds = {e["key"]: e["kind"] for e in man["entries"]}
            if i % 3 == 0:                       # base: moments in full
                assert kinds["opt/m/w"] == "full"
                assert kinds["opt/v/w"] == "full"
                assert man["prev_step"] is None
            else:                                # delta: moments vs prev save
                assert kinds["opt/m/w"] == "delta_prev"
                assert kinds["opt/v/w"] == "delta_prev"
                assert man["prev_step"] == i - 1
                assert kinds["params/w"] == "delta"   # params still vs base
            # non-moment opt leaves never chain
            assert kinds["opt/count"] in ("full", "delta")

    def test_chain_restores_bit_exact(self, tmp_path):
        mgr = CheckpointManager(
            CheckpointConfig(
                str(tmp_path), base_every=3, async_save=False, keep_bases=99
            )
        )
        states = self._drifted(7, seed=1)
        for i, st in enumerate(states):
            mgr.save(i, st, blocking=True)
        for i, st in enumerate(states):
            _, back = mgr.restore(i)
            for key in ("m", "v"):
                np.testing.assert_array_equal(
                    back["opt"][key]["w"].view(np.uint8),
                    st["opt"][key]["w"].view(np.uint8),
                )
            np.testing.assert_array_equal(
                back["params"]["w"].view(np.uint8),
                st["params"]["w"].view(np.uint8),
            )

    def test_moment_deltas_beat_full(self, tmp_path):
        """Slow-drifting moments must compress far better vs-prev than the
        full moment payload in the base save."""
        mgr = CheckpointManager(
            CheckpointConfig(
                str(tmp_path), base_every=4, async_save=False, keep_bases=99
            )
        )
        states = self._drifted(4, seed=2)
        for i, st in enumerate(states):
            mgr.save(i, st, blocking=True)
        base_man = self._manifest(tmp_path, 0)
        delta_man = self._manifest(tmp_path, 2)
        size = lambda man, key: next(
            e["size"] for e in man["entries"] if e["key"] == key
        )
        assert size(delta_man, "opt/m/w") < 0.5 * size(base_man, "opt/m/w")
        assert size(delta_man, "opt/v/w") < 0.5 * size(base_man, "opt/v/w")

    def test_restart_breaks_chain_safely(self, tmp_path):
        """The prev-moment snapshot lives in RAM only: a new manager must
        not emit delta_prev on its first save, and restores stay exact."""
        cfg = CheckpointConfig(
            str(tmp_path), base_every=4, async_save=False, keep_bases=99
        )
        states = self._drifted(4, seed=3)
        mgr = CheckpointManager(cfg)
        mgr.save(0, states[0], blocking=True)
        mgr.save(1, states[1], blocking=True)
        mgr2 = CheckpointManager(cfg)            # process restart
        mgr2.save(2, states[2], blocking=True)
        man = self._manifest(tmp_path, 2)
        kinds = {e["key"]: e["kind"] for e in man["entries"]}
        assert kinds["opt/m/w"] != "delta_prev"
        assert man["prev_step"] is None
        _, back = mgr2.restore(2)
        np.testing.assert_array_equal(
            back["opt"]["m"]["w"], states[2]["opt"]["m"]["w"]
        )

    def test_moment_keys_empty_disables_chaining(self, tmp_path):
        mgr = CheckpointManager(
            CheckpointConfig(
                str(tmp_path), base_every=3, async_save=False,
                keep_bases=99, moment_keys=(),
            )
        )
        for i, st in enumerate(self._drifted(3, seed=4)):
            mgr.save(i, st, blocking=True)
        man = self._manifest(tmp_path, 1)
        kinds = {e["kind"] for e in man["entries"]}
        assert "delta_prev" not in kinds
        assert man["prev_step"] is None

    def test_chain_survives_retention_gc(self, tmp_path):
        """GC deletes whole base segments (base + its deltas), so surviving
        delta_prev chains always have their predecessors on disk."""
        mgr = CheckpointManager(
            CheckpointConfig(
                str(tmp_path), base_every=3, keep_bases=1, async_save=False
            )
        )
        states = self._drifted(6, seed=5)
        for i, st in enumerate(states):
            mgr.save(i, st, blocking=True)
        remaining = sorted(s["step"] for s in mgr.stats())
        assert remaining == [3, 4, 5]
        for i in (3, 4, 5):
            _, back = mgr.restore(i)
            np.testing.assert_array_equal(
                back["opt"]["m"]["w"].view(np.uint8),
                states[i]["opt"]["m"]["w"].view(np.uint8),
            )


class TestGradSync:
    def test_lossless_and_compressed(self, tiny_setup):
        cfg, model, state = tiny_setup
        gs = GradSync()
        manifest, stats = gs.pack(state["params"])
        assert stats.ratio_pct < 90.0       # bf16-dominated tree compresses
        back = gs.unpack(manifest)
        for a, b in zip(
            jax.tree_util.tree_leaves(jax.device_get(state["params"])),
            jax.tree_util.tree_leaves(back),
        ):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()

    def test_exchange_model(self, tiny_setup):
        cfg, model, state = tiny_setup
        gs = GradSync()
        rep = gs.exchange(state["params"], n_peers=4, link_gbps=1.0)
        assert rep["ratio_pct"] < 90.0
        assert rep["zipnn_s"] > 0 and rep["raw_s"] > 0

    def test_straggler_plan(self):
        times = [1.0, 1.1, 0.9, 1.0, 5.0, 1.05, 9.0, 1.0]
        assert straggler_reissue_plan(times) == [4, 6]


class TestHubTransfer:
    def test_download_speedup_on_compressible_model(self):
        import ml_dtypes

        w = (np.random.default_rng(0).standard_normal(2_000_000) * 0.02).astype(
            ml_dtypes.bfloat16
        )
        rep = simulate_transfer(
            np.ascontiguousarray(w).view(np.uint8).tobytes(), "bfloat16",
            "first_download_home",
        )
        assert rep.comp_bytes < 0.72 * rep.raw_bytes
        assert rep.speedup > 1.0            # slow link ⇒ compression wins
