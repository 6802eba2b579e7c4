"""Faults and controls of the save and serve cells (see test_bench_faults.py)."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_runs as runs  # noqa: E402


def _save_faults(fault, monkeypatch):
    from repro.checkpoint import manager

    real = manager.CheckpointManager._write
    first = {}

    def broken(self, step, flat, *a, **k):
        if fault == "unchanged":           # writes the first state it saw, again
            first.setdefault("flat", flat)
            flat = first["flat"]
        elif fault == "half":
            flat = dict(sorted(flat.items())[: len(flat) // 2])
        elif fault == "altered":           # the low bit of every 100th element flipped
            flat = dict(flat)
            for key, arr in flat.items():
                w = arr.copy().reshape(-1).view(np.uint32 if arr.itemsize == 4 else np.uint16)
                w[::100] ^= 1
                flat[key] = w.view(arr.dtype).reshape(arr.shape)
        return real(self, step, flat, *a, **k)

    monkeypatch.setattr(manager.CheckpointManager, "_write", broken)


def _serve_faults(fault, monkeypatch):
    import jax.numpy as jnp
    from repro.serve import step as serve_step

    real = serve_step.make_compressed_serve_step

    def make(*a, **k):
        ring = real(*a, **k)

        def broken(state, tokens):
            logits, new = ring(state, tokens)
            if fault == "unchanged":       # state handed back as it came
                new = state
            elif fault == "half":          # half of the rows never computed
                logits = logits.at[: logits.shape[0] // 2].set(0.0)
            elif fault == "altered":       # one row's token altered where produced
                logits = logits.at[0, :, :].set(-logits[0, :, :])
            return logits, new

        return broken

    monkeypatch.setattr(serve_step, "make_compressed_serve_step", make)


FAULTS = {"save.hubert_xlarge-train": _save_faults, "serve.qwen15_4b-L2": _serve_faults}


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
@pytest.mark.parametrize("cell", sorted(FAULTS))
def test_fault_is_not_correct(cell, fault, monkeypatch):
    FAULTS[cell](fault, monkeypatch)
    r = runs.run(cell, seconds=2.0)        # several steps: a stale ring state shows from the second
    assert not r["correct"], r["log"]


def test_serve_control_is_not_correct():
    """At the published widths (two layers), with a slice of the vocabulary
    and a short prefix so that a CPU holds it."""
    from bench import control, harness

    parts = harness.load_cell(harness.load_spec(), "serve.qwen15_4b-L2")
    gap = control.serve_reference_control(parts["config"], 7, batch=2, prefix=64, steps=6,
                                          overrides={"vocab_size": 8192})
    assert gap > parts["traffic"]["gap_limit"]
