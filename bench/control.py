#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the program's and the
control's, per seed, at a cell's own size.

    python bench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

The control is the plain reference put in the program's place, computed
one precision below the configuration's: fp8 (e4m3) for bf16 state, bf16
for fp32 state.

* Restore cells compare states bit for bit; their number is the count of
  mismatched elements, limit 0.  The control's reading is the count of
  elements that the state, rounded to the lower precision and widened
  back, changes (the state as the cell's set-up makes it).
* The save cell runs whole, for ``--seconds``, with each state saved
  rounded to the lower precision and widened back in place of the state
  itself; its own check then reads the sampled chunks back against the
  replayed state, and its numbers are the control's reading.
* The serve cell compares served tokens with the reference; its number is
  the widest gap by which a served token's logit lies below the
  reference's best.  Each seed runs the cell for ``--seconds``, then reads
  both the program's gap and the control's (the gap of the token that the
  fp8 reference puts first, at the same positions).

The benchmark's own runs never run this.  One line of JSON per seed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def state_control(parts, seed, jax, overrides=None):
    """Elements changed by rounding the cell's saved state one precision down."""
    import jax.numpy as jnp

    from bench import harness, state
    from bench.kinds import restore

    run = harness.Run("control", parts["config"], parts["traffic"], seed, ROOT,
                      overrides or {})
    s, _, _ = restore.make_state(run, jax)
    low = {"bfloat16": jnp.float8_e4m3fn, "float32": jnp.bfloat16}
    changed = 0
    for x in jax.tree_util.tree_leaves(s):
        y = x.astype(low[x.dtype.name]).astype(x.dtype)
        changed += int(state.mismatches([y], [x]))
    return {"mismatched_elements": changed, "elements": sum(
        x.size for x in jax.tree_util.tree_leaves(s))}


def serve_reference_control(config, seed, *, batch, prefix, steps, overrides=None):
    """The control with no program: the gap, under the float32 reference,
    of the tokens that the fp8 reference puts first, over ``steps`` tokens
    drawn from the seed after a ``prefix`` of cache entries from the seed."""
    import jax
    import jax.numpy as jnp

    from bench import state
    from bench.reference import qwen

    cfg = state.model_config(config, overrides)
    params = state.make_filler(state.abstract_params(cfg))(state.seed_key(seed))
    key = state.seed_key(seed + 1)
    shape = (cfg.n_layers, batch, prefix, cfg.n_kv_heads, cfg.head_dim)
    pk = jax.random.normal(jax.random.fold_in(key, 0), shape).astype(jnp.bfloat16)
    pv = jax.random.normal(jax.random.fold_in(key, 1), shape).astype(jnp.bfloat16)
    fed = jax.random.randint(jax.random.fold_in(key, 2), (batch, steps), 0, cfg.vocab_size)
    return qwen.control_gap(params, qwen.lower_precision(params), pk, pv, fed, cfg)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from bench import harness

    parts = harness.load_cell(harness.load_spec(), args.workload)
    harness.device_info(jax, parts["cell"]["chips"])
    for seed in args.seeds:
        if parts["traffic"]["kind"] in ("serve", "save"):
            r = harness.run_cell(args.workload, seed, args.seconds, False,
                                 t_start=time.perf_counter(), control=True)
            out = {"seed": seed, "correct": r["correct"], **r["control"],
                   **{k: c["value"] for k, c in r["compared"].items()},
                   "attempted": r["attempted"]}
        else:
            out = {"seed": seed, **state_control(parts, seed, jax)}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
