"""Closed-loop restore: one caller restores the newest checkpoint into
device memory (``CheckpointManager.restore(device_resident=True)``), again
and again.

Mix parameters (``traffic/<mix>.json``):
  ``state``      ``"params"`` (weights from the seed) or ``"train"``
                 (fp32 params and AdamW moments, see ``state.make_train_state``);
  ``subtrees``   top-level keys of the params kept (``null``: all);
  ``saves``      checkpoints written in set-up, an AdamW update between
                 two (the first a base, later ones deltas);
  ``updates_before_first_save``  AdamW updates before the first save.

Every leaf of every restore is compared with the state that was saved, on
the device, by one bit-compare dispatched after the restore returns; the
counts are read once the window has closed.
"""

from __future__ import annotations

import time

from bench import program, state, work
from bench.reference import znn

STEP0 = 1000          # AdamW step of the first save: past the warm-up


def make_state(run, jax):
    cfg = state.model_config(run.config, run.overrides.get("model"))
    key = state.seed_key(run.seed)
    t = run.traffic
    if t["state"] == "params":
        fill = state.make_filler(state.abstract_params(cfg, t.get("subtrees")))
        return {"params": fill(key)}, None, key
    init, update = state.make_train_state(cfg)
    s = init(key)
    for i in range(t.get("updates_before_first_save", 0)):
        s = update(s, jax.random.fold_in(key, i), STEP0 - t["updates_before_first_save"] + i)
    return s, update, key


def decoded_steps(ck: znn.Checkpoint, step: int):
    """Steps a restore of ``step`` decodes: it, its base, its moment chain."""
    seen, todo = [], [step]
    while todo:
        s = todo.pop()
        if s in seen:
            continue
        seen.append(s)
        m = ck.manifest(s)
        todo += [x for x in (m.get("base_step"), m.get("prev_step")) if x is not None]
    return sorted(seen)


class Compare:
    """Bit-compare of a restored tree with the saved one, on the device."""

    def __init__(self, jax, want):
        self.want = state.flat_leaves(want)
        self.keys = sorted(self.want)

    def __call__(self, jax, got_tree):
        got = state.flat_leaves(got_tree)
        ok = [k for k in self.keys if k in got and got[k].shape == self.want[k].shape
              and got[k].dtype == self.want[k].dtype]
        lost = sum(self.want[k].size for k in self.keys if k not in ok)
        lost += sum(1 for k in got if k not in self.want)
        return lost, state.mismatches([got[k] for k in ok], [self.want[k] for k in ok])


def setup(run, jax):
    from repro.core import device_entropy

    t0 = time.perf_counter()
    s, update, key = make_state(run, jax)
    jax.block_until_ready(s)
    t1 = time.perf_counter()
    mgr = program.manager(run.config, run.workdir / "ckpt")
    step = STEP0
    for j in range(run.traffic["saves"]):
        if j:
            s = update(s, jax.random.fold_in(key, 10_000 + j), step)
            step += 1
        mgr.save(step, s, blocking=True)
    t2 = time.perf_counter()
    compare = Compare(jax, s)
    ck = znn.Checkpoint(run.workdir / "ckpt")
    newest = ck.steps()[-1]
    steps = decoded_steps(ck, newest)
    pairs = [(ck.stream(st, e["key"]), e["kind"] != "full")     # stream, is an XOR delta
             for st in steps for e in ck.manifest(st)["entries"]]
    run.extra.update(mgr=mgr, compare=compare, counts=[],
                     raw=state.tree_bytes(s),
                     read_stored=sum(ck.stored_bytes(st) for st in steps),
                     huffdecode=work.huffdecode_bytes(st for st, _ in pairs),
                     plane_consumer=work.plane_bytes(pairs),
                     stored_per_raw=sum(ck.stored_bytes(st) for st in ck.steps())
                     / sum(ck.manifest(st)["raw_bytes"] for st in ck.steps()))
    _, tree = mgr.restore(device_resident=True)             # warm every shape
    jax.block_until_ready(tree)
    int(compare(jax, tree)[1])                              # and the compare
    del tree
    device_entropy.reset_transfer_stats()
    run.extra["setup_split"] = {"state_s": f"{t1 - t0:.3f}", "saves_s": f"{t2 - t1:.3f}",
                                "warm_restore_s": f"{time.perf_counter() - t2:.3f}"}


def step(run, jax, i):
    from bench.harness import span

    with span(jax, "restore"):
        _, tree = run.extra["mgr"].restore(device_resident=True)
        jax.block_until_ready(tree)
    run.extra["counts"].append(run.extra["compare"](jax, tree))


def release(run, jax):
    from repro.core import device_entropy

    run.extra["uploads"] = device_entropy.transfer_stats()["payload_bytes"]
    run.extra.pop("mgr")


def check(run, jax, ops):
    per_op = [lost + int(n) for lost, n in run.extra.pop("counts")]
    run.extra.pop("compare")
    return ({"mismatched_elements": {"value": sum(per_op), "limit": 0}},
            len(per_op), sum(1 for x in per_op if x))


def end_to_end(run, window_s, ops):
    return {"restore_GBps": run.extra["raw"] * ops / window_s / 1e9,
            "stored_per_raw": run.extra["stored_per_raw"]}


def after_check(run, jax):
    pass
