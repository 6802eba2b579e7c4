"""Serving driver: load a ZipNN-compressed checkpoint, batch requests,
greedy-decode.

CPU demo:
  python -m repro.launch.serve --arch repro_gpt_100m --reduced \
      --ckpt-dir /tmp/ckpt --batch 4 --prompt-len 16 --gen 32
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointConfig, CheckpointManager
from repro.configs import get_config
from repro.models import build_model
from repro.serve.step import greedy_generate


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default="repro_gpt_100m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt-dir", type=str, default=None)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if not cfg.has_decode:
        raise SystemExit(f"{cfg.name} is encoder-only — nothing to decode")
    model = build_model(cfg)

    if args.ckpt_dir:
        # 'auto' decodes on the device exactly when the process holds an
        # accelerator, and device_resident keeps the leaves there; on a CPU
        # host the restore stays the numpy path.
        mgr = CheckpointManager(CheckpointConfig(args.ckpt_dir, backend="auto"))
        step, tree = mgr.restore(device_resident=True)
        params = jax.tree_util.tree_map(jnp.asarray, tree["params"])
        print(f"[serve] restored step {step} from ZipNN checkpoint")
    else:
        params = model.init(jax.random.key(args.seed))
        print("[serve] random init (no --ckpt-dir)")

    rng = np.random.default_rng(args.seed)
    prompt = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)), jnp.int32
    )
    t0 = time.time()
    out, _ = greedy_generate(model, params, prompt, args.gen)
    dt = time.time() - t0
    print(f"[serve] generated {args.batch}×{args.gen} tokens in {dt:.1f}s "
          f"({args.batch*args.gen/dt:.1f} tok/s)")
    print("first sequence:", np.asarray(out[0]).tolist())


if __name__ == "__main__":
    main()
