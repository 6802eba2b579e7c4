"""Model state made on the device from the seed, in one jitted call.

The tree's structure (key names, shapes, dtypes) is the program's own, read
with ``jax.eval_shape`` from its model; the values are the benchmark's:
matrices N(0, 1/fan_in), embedding tables N(0, 0.02^2), biases
N(0, 0.02^2), norm gains 1 + N(0, 0.02^2).  The key is an argument of the
compiled call, so every seed runs the same program.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

# config-file key -> ModelConfig field
FIELDS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "num_hidden_layers": "n_layers",
    "vocab_size": "vocab_size",
    "num_clusters": "vocab_size",
    "max_position_embeddings": "max_position",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "layer_norm_eps": "norm_eps",
    "qkv_bias": "qkv_bias",
    "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "param_dtype",
    "conv_dim_last": "frontend_dim",
}


def model_config(config: Dict[str, Any], overrides: Optional[Dict[str, Any]] = None):
    """The program's registered config with the file's numbers applied."""
    from repro.configs import get_config

    cfg = get_config(config["registry"])
    values = {f: config[k] for k, f in FIELDS.items() if k in config}
    values.update(overrides or {})
    cfg = dataclasses.replace(cfg, **values)
    if "num_key_value_heads" not in config and "n_kv_heads" not in values:
        cfg = dataclasses.replace(cfg, n_kv_heads=cfg.n_heads)
    return cfg


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number (seeds may exceed 32 bits)."""
    if seed < 0:
        raise ValueError("seeds are whole numbers")
    key = jax.random.key(seed & 0xFFFFFFFF)
    for word in range(1, 3):
        key = jax.random.fold_in(key, (seed >> (32 * word)) & 0xFFFFFFFF)
    return key


def _path(p) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in p)


def _fill(key, path: str, s: jax.ShapeDtypeStruct):
    name = path.rsplit("/", 1)[-1]
    z = jax.random.normal(key, s.shape, jnp.float32)
    if name == "g":
        x = 1.0 + 0.02 * z
    elif name == "w":
        x = z * (s.shape[-2] ** -0.5)
    else:                               # tables, biases
        x = 0.02 * z
    return x.astype(s.dtype)


def make_filler(abstract):
    """A jitted ``key -> tree`` filling the shapes of ``abstract``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    @jax.jit
    def fill(key):
        leaves = [_fill(jax.random.fold_in(key, i), _path(p), s)
                  for i, (p, s) in enumerate(flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return fill


def abstract_params(cfg, subtrees: Optional[Sequence[str]] = None):
    from repro.models import build_model

    tree = build_model(cfg).abstract_params()
    if subtrees:
        tree = {k: tree[k] for k in subtrees}
    return tree


def tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree))


def flat_leaves(tree) -> Dict[str, Any]:
    """Leaves by '/'-joined key, the checkpoint manager's naming."""
    return {_path(p): x for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def make_train_state(cfg, grad_std: float = 1e-4):
    """``(init, update)``: ``init(key)`` makes fp32 params and zero AdamW
    moments; ``update(state, key, step)`` applies one update of the
    program's AdamW with gradients N(0, grad_std^2) drawn from ``key``."""
    from repro.optim import adamw

    fill = make_filler(abstract_params(cfg))
    opt = adamw.AdamWConfig()

    @jax.jit
    def init(key):
        params = fill(key)
        return {"params": params, "opt": adamw.init_opt_state(params)}

    @jax.jit
    def update(state, key, step):
        flat, treedef = jax.tree_util.tree_flatten(state["params"])
        grads = treedef.unflatten([
            grad_std * jax.random.normal(jax.random.fold_in(key, i), x.shape, jnp.float32)
            for i, x in enumerate(flat)
        ])
        params, opt_state, _ = adamw.apply_updates(opt, state["params"], grads, state["opt"], step)
        return {"params": params, "opt": opt_state}

    return init, update


@jax.jit
def mismatches(got, want) -> jax.Array:
    """Elements whose bits differ, summed over a tree; a leaf of another
    shape or dtype counts whole."""
    total = jnp.zeros((), jnp.int64 if jax.config.jax_enable_x64 else jnp.int32)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        u = jnp.dtype(f"uint{8 * b.dtype.itemsize}")
        total += jnp.sum(jax.lax.bitcast_convert_type(a, u) != jax.lax.bitcast_convert_type(b, u))
    return total
