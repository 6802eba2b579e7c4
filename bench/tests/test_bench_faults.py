"""A run with the timed path broken underneath comes out not correct, for
each fault its cell can have; and the controls (the reference one
precision down) come out not correct."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_runs as runs  # noqa: E402


def _flat_map(tree, fn):
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return jax.tree_util.tree_unflatten(treedef, fn(leaves))


def _restore_faults(fault, monkeypatch):
    import jax
    import jax.numpy as jnp
    from repro.checkpoint import manager

    real = manager.CheckpointManager.restore

    def broken(self, *a, **k):
        step, tree = real(self, *a, **k)
        if fault == "unchanged":           # hands back fresh buffers, nothing restored
            tree = jax.tree_util.tree_map(jnp.zeros_like, tree)
        elif fault == "half":              # half of the leaves left out
            flat = {"/".join(str(getattr(x, "key", x)) for x in p): v
                    for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]}
            tree = manager._unflatten(dict(sorted(flat.items())[: len(flat) // 2]))
        elif fault == "altered":           # one bit of one element flipped
            def flip(leaves):
                u = jnp.dtype(f"uint{8 * leaves[0].dtype.itemsize}")
                bits = jax.lax.bitcast_convert_type(leaves[0], u).reshape(-1)
                bits = bits.at[7].set(bits[7] ^ 1)
                leaves[0] = jax.lax.bitcast_convert_type(bits, leaves[0].dtype).reshape(leaves[0].shape)
                return leaves
            tree = _flat_map(tree, flip)
        return step, tree

    monkeypatch.setattr(manager.CheckpointManager, "restore", broken)


FAULTS = {"restore.qwen15_4b-L2": _restore_faults,
          "resume.hubert_xlarge-train": _restore_faults}


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
@pytest.mark.parametrize("cell", sorted(FAULTS))
def test_fault_is_not_correct(cell, fault, monkeypatch):
    FAULTS[cell](fault, monkeypatch)
    r = runs.run(cell, seconds=2.0)        # several steps: a stale ring state shows from the second
    assert not r["correct"], r["log"]


def test_resume_altered_answer_is_not_correct(monkeypatch):
    _restore_faults("altered", monkeypatch)
    assert not runs.run("resume.hubert_xlarge-train", seconds=1.0)["correct"]


@pytest.mark.parametrize("cell", ["restore.qwen15_4b-L2", "save.hubert_xlarge-train"])
def test_state_control_is_not_correct(cell):
    """A restore cell's control counts the elements that rounding its state
    one precision down changes; the save cell's saves each state so rounded
    in its place and goes through the cell's own check."""
    import jax

    from bench import control, harness

    parts = harness.load_cell(runs.spec(), cell)
    if parts["traffic"]["kind"] == "save":
        r = runs.run(cell, control=True)
        assert not r["correct"], r["log"]
        got = {k: c["value"] for k, c in r["compared"].items()}
    else:
        got = control.state_control(parts, 3, jax, overrides=runs.overrides(cell))
    assert got["mismatched_elements"] > 0        # limit 0


