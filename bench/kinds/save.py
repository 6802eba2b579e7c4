"""Closed-loop training save: ``updates_between_saves`` AdamW updates of the
program's optimizer with gradients from the seed, then
``CheckpointManager.save(step, state, blocking=True)``; the manager's own
base/delta cycle runs on, and a window holds whole cycles of it.

Mix parameters (``traffic/<mix>.json``):
  ``updates_between_saves``  AdamW updates before each save, set-up's too;
  ``warm_saves``             saves made in set-up (a base, then a delta),
                             which warm both paths and set
                             ``stored_per_raw``;
  ``sample_chunks``          chunks of each save read back by the plain
                             reader, drawn from the seed.

After the window the states of all saves are made again by replaying the
same updates (the compiled update is deterministic), and the sampled chunks
of every save still on disk are read back by ``reference/znn.py`` and
compared bit for bit.  The control saves each state rounded one precision
down, in its own type, in place of the state itself.
"""

from __future__ import annotations

import sys
import time

import numpy as np

from bench import program, state, work
from bench.reference import znn

STEP0 = 1000


def _upd(run, jax, s, n):
    return run.extra["update"](s, jax.random.fold_in(run.extra["key"], n), STEP0 + n)


def setup(run, jax):
    t0 = time.perf_counter()
    cfg = state.model_config(run.config, run.overrides.get("model"))
    key = state.seed_key(run.seed)
    init, update = state.make_train_state(cfg)
    run.extra.update(key=key, update=update, n=0,
                     lower=jax.jit(lambda t: jax.tree_util.tree_map(_lower, t)))
    s = init(key)
    jax.block_until_ready(s)
    t1 = time.perf_counter()
    mgr = program.manager(run.config, run.workdir / "ckpt")
    run.extra.update(mgr=mgr, saves=[], raw=state.tree_bytes(s), written=0)
    for _ in range(run.traffic["warm_saves"]):
        s = _save_one(run, jax, s)
    ck = znn.Checkpoint(run.workdir / "ckpt")
    run.extra["stored_per_raw"] = (sum(ck.stored_bytes(st) for st in ck.steps())
                                   / sum(ck.manifest(st)["raw_bytes"] for st in ck.steps()))
    run.extra.update(state=s, window_from=len(run.extra["saves"]), written=0)
    run.extra["setup_split"] = {"state_s": f"{t1 - t0:.3f}",
                                "warm_saves_s": f"{time.perf_counter() - t1:.3f}"}


# (exponent, mantissa) bits one precision down: bfloat16 for float32, e4m3
# for bfloat16.  ``reduce_precision`` and not a cast there and back, which
# XLA may fold away as excess precision.
LOWER = {"float32": (8, 7), "bfloat16": (4, 3)}


def _lower(x):
    """A leaf rounded one precision down, kept in its own type: the control."""
    import jax

    return jax.lax.reduce_precision(x, *LOWER[x.dtype.name])


def _save_one(run, jax, s):
    from bench.harness import span

    with span(jax, "adamw_updates"):
        for _ in range(run.traffic["updates_between_saves"]):
            s = _upd(run, jax, s, run.extra["n"])
            run.extra["n"] += 1
        jax.block_until_ready(s)
    step = STEP0 + run.extra["n"] - 1
    with span(jax, "save"):
        run.extra["mgr"].save(step, run.extra["lower"](s) if run.extra["control"] else s,
                              blocking=True)
    run.extra["saves"].append((step, run.extra["n"]))
    run.extra["written"] += (run.workdir / "ckpt" / f"step_{step}" / "data.bin").stat().st_size
    return s


def step(run, jax, i):
    run.extra["state"] = _save_one(run, jax, run.extra["state"])


def closes(run, ops):
    """The window closes only on whole base/delta cycles: any ``base_every``
    saves in a row hold one base, so every window saves the same mix."""
    return ops % run.config["checkpoint"]["base_every"] == 0


def release(run, jax):
    run.extra.pop("state")
    run.extra.pop("mgr")


def check(run, jax, ops):
    """Replay the updates, then read back sampled chunks of every save."""
    ck = znn.Checkpoint(run.workdir / "ckpt")
    on_disk = set(ck.steps())
    saves = run.extra["saves"][run.extra["window_from"]:]
    rng = np.random.default_rng([run.seed, 7])
    wants, expect = [], {}
    key = run.extra["key"]
    cfg = state.model_config(run.config, run.overrides.get("model"))
    init, _ = state.make_train_state(cfg)
    s, n = init(key), 0
    missing = 0
    latest = run.extra["saves"][-1][0]
    take = {}
    for step_, n_after in saves:
        while n < n_after:
            s = _upd(run, jax, s, n)
            n += 1
        if step_ not in on_disk:
            missing += step_ == latest      # retention may drop older saves
            continue
        flat = state.flat_leaves(s)
        keys = sorted(flat)
        sizes = np.array([flat[k].size for k in keys], np.float64)
        for j in rng.choice(len(keys), run.traffic["sample_chunks"], p=sizes / sizes.sum()):
            k = keys[j]
            e = chunk_elems(run.config, flat[k].dtype.itemsize)
            c = int(rng.integers(-(-flat[k].size // e)))
            wants.append((step_, k, c))
            expect[(step_, k, c)] = _chunk_words(jax, take, flat[k], c, e)
    run.extra.update(window_work(ck, saves, run.extra["window_from"],
                                 run.config["checkpoint"]["base_every"]))
    bad, bad_saves = 0, set()
    try:
        got = ck.read_chunks(wants)
    except (KeyError, ValueError, OSError):                 # find the save at fault
        got = {}
        for step_ in sorted({w[0] for w in wants}):
            mine = [w for w in wants if w[0] == step_]
            try:
                got.update(ck.read_chunks(mine))
            except (KeyError, ValueError, OSError) as e:
                print(f"check: save {step_} unreadable: {e!r}", file=sys.stderr)
    for step_ in sorted({w[0] for w in wants}):
        for w in [w for w in wants if w[0] == step_]:
            if w not in got or got[w].shape != expect[w].shape:
                n_bad = expect[w].size
            else:
                n_bad = int(np.count_nonzero(got[w] != expect[w]))
            bad += n_bad
            if n_bad:
                bad_saves.add(step_)
    return ({"mismatched_elements": {"value": bad, "limit": 0},
             "newest_save_missing": {"value": missing, "limit": 0}},
            len(saves), len(bad_saves) + missing)


WORK = ("bitpack", "plane_producer", "chunk_histogram")


def window_work(ck, saves, first, base_every):
    """Bytes each save kernel must move in the window's ``saves`` (the
    manager's saves from number ``first`` on), by kernel (``WORK``), from
    their chunk tables.  A save that retention has removed counts as the
    mean of the saves of its kind still on disk, its kind (base or delta)
    following from its place in the cycle of ``base_every``; ``{}`` where
    none of that kind is left."""
    on_disk = set(ck.steps())
    got, by_kind = {}, {}
    for step_, _ in saves:
        if step_ in on_disk:
            m = ck.manifest(step_)
            pairs = [(ck.stream(step_, e["key"]), e["kind"] != "full") for e in m["entries"]]
            streams = [st for st, _ in pairs]
            got[step_] = (work.bitpack_bytes(streams), work.plane_bytes(pairs),
                          work.histogram_bytes(streams))
            by_kind.setdefault(m["kind"], []).append(got[step_])
    total = np.zeros(len(WORK))
    for i, (step_, _) in enumerate(saves, first):
        kind = "base" if i % base_every == 0 else "delta"
        if step_ not in got and kind not in by_kind:
            return {}
        total += got[step_] if step_ in got else np.mean(by_kind[kind], axis=0)
    return dict(zip(WORK, map(float, total)))


def chunk_elems(config, itemsize):
    """Elements of one chunk: the configuration's parameter bytes per chunk."""
    return config["codec"]["chunk_param_bytes"] // itemsize


def _chunk_words(jax, take, leaf, c, e):
    """Words of chunk ``c`` (``e`` elements a chunk) of a device leaf."""
    import numpy as np

    n = leaf.size
    if n <= e:
        words = np.asarray(jax.device_get(leaf)).reshape(-1)
    else:
        start = min(c * e, n - e)
        fn = take.setdefault((leaf.shape, leaf.dtype, e), jax.jit(
            lambda x, a: jax.lax.dynamic_slice_in_dim(x.reshape(-1), a, e)))
        words = np.asarray(jax.device_get(fn(leaf, start)))[c * e - start:]
    return words.view(np.uint32 if words.dtype.itemsize == 4 else np.uint16)


def end_to_end(run, window_s, ops):
    return {"save_GBps": run.extra["raw"] * ops / window_s / 1e9,
            "stored_per_raw": run.extra["stored_per_raw"]}


def after_check(run, jax):
    pass
