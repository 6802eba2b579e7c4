"""Logical-axis sharding: one rule table maps logical tensor axes to mesh
axes; activations use :func:`lshard` constraints, parameters get their
PartitionSpec from name-pattern rules over the pytree paths.

Mesh axes: ('data', 'model') single-pod, ('pod', 'data', 'model') two-pod.
Batch shards over ('pod', 'data'); heads/ff/experts/vocab over 'model';
with ZeRO-3 (``zero3=True`` archs) the non-model parameter axis additionally
shards over 'data' (FSDP-style — GSPMD all-gathers at use sites).
"""

from __future__ import annotations

import contextlib
import re
import threading
from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P

_state = threading.local()


DEFAULT_RULES: Dict[str, Any] = {
    "batch": ("pod", "data"),   # filtered to existing mesh axes at use
    "seq": None,
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "ff": "model",
    "vocab": "model",
    "experts": "model",
    "experts_serve": "data",    # inference EP: experts live on the data axis
    "zero3": "data",            # secondary param axis under ZeRO-3
    "seq_sp": "model",          # sequence-parallel residual carry (cfg.sp)
}


def _current_mesh_axes() -> Optional[Tuple[str, ...]]:
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.axis_names:
        return tuple(mesh.axis_names)
    try:  # legacy `with mesh:` context (what launch/dryrun.py uses)
        from jax._src.mesh import thread_resources

        pm = thread_resources.env.physical_mesh
        if not pm.empty:
            return tuple(pm.axis_names)
    except Exception:
        pass
    return None


@contextlib.contextmanager
def axis_rules(rules: Dict[str, Any]):
    """Activate logical→mesh rules (launcher/dryrun scope)."""
    prev = getattr(_state, "rules", None)
    _state.rules = rules
    try:
        yield
    finally:
        _state.rules = prev


def active_rules() -> Optional[Dict[str, Any]]:
    return getattr(_state, "rules", None)


def resolve(logical: Optional[str], mesh_axes: Tuple[str, ...]) -> Any:
    rules = active_rules() or DEFAULT_RULES
    target = rules.get(logical) if logical else None
    if target is None:
        return None
    if isinstance(target, tuple):
        hit = tuple(a for a in target if a in mesh_axes)
        return hit if hit else None
    return target if target in mesh_axes else None


def axis_size(name: str) -> int:
    """Size of a mesh axis in the active mesh context (1 if absent)."""
    try:
        from jax._src.mesh import thread_resources

        pm = thread_resources.env.physical_mesh
        if not pm.empty:
            return dict(pm.shape).get(name, 1)
    except Exception:
        pass
    am = jax.sharding.get_abstract_mesh()
    if am.axis_names:
        return dict(am.shape).get(name, 1)
    return 1


def lshard(x, *logical_axes: Optional[str]):
    """with_sharding_constraint by logical axis names; no-op without mesh."""
    mesh_axes = _current_mesh_axes()
    if mesh_axes is None:
        return x
    spec = P(*[resolve(a, mesh_axes) for a in logical_axes])
    return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# Parameter PartitionSpec rules (name-pattern over pytree paths)
# ---------------------------------------------------------------------------

# (regex over '/'-joined path, logical axes per trailing dimension).
# Leading scan (layer-stack) axes are padded with None automatically.
# ORDER MATTERS: first match wins — expert rules must precede the generic
# MLP rules (expert paths end in the same leaf names).
_PARAM_RULES = [
    # experts dominate MoE parameter/optimizer bytes → ZeRO-3 shards their
    # d_model dim over 'data' on top of expert parallelism over 'model'
    (r"experts/(w_gate|w_up)$", (("experts",), ("zero3",), None)),
    (r"experts/w_down$", (("experts",), None, ("zero3",))),
    (r"(wq|wk|wv|w_q|w_uq|w_uk|w_uv)/w$", (("zero3",), ("heads",))),
    (r"(wq|wk|wv)/b$", (("heads",),)),
    (r"wo/w$", (("heads",), ("zero3",))),
    # SwiGLU/GELU MLP leaves are raw arrays (no trailing '/w')
    (r"(w_gate|w_up|w_in)$", (("zero3",), ("ff",))),
    (r"(w_down|w_out)$", (("ff",), ("zero3",))),
    (r"b_in$", (("ff",),)),
    (r"(embed|lm_head|cls_head)/table$", (("vocab",), ("zero3",))),
    (r"pos/table$", (None, ("ff",))),
    (r"frontend_proj/w$", (None, ("zero3",))),
    (r"router/w$", (None, None)),
    (r"(w_dq|w_dkv|w_kr)/w$", (("zero3",), None)),
    # SSM params
    (r"(in_proj|out_proj)/w$", (("zero3",), ("heads",))),
    (r"ssm/(A_log|D|dt_bias)$", (("heads",),)),
    (r"conv/w$", (None, ("heads",))),
]


def _axis_size(axis: Any, mesh_sizes: Dict[str, int]) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        n = 1
        for a in axis:
            n *= mesh_sizes.get(a, 1)
        return n
    return mesh_sizes.get(axis, 1)


def _spec_for(
    path: str,
    shape: Tuple[int, ...],
    zero3: bool,
    mesh_axes: Tuple[str, ...],
    mesh_sizes: Dict[str, int],
) -> P:
    ndim = len(shape)
    for pat, dims in _PARAM_RULES:
        if re.search(pat, path):
            axes = []
            for d in dims:
                if d is None:
                    axes.append(None)
                    continue
                logical = d[0] if isinstance(d, tuple) else d
                if logical == "zero3":
                    axes.append(resolve("zero3", mesh_axes) if zero3 else None)
                elif logical == "ff_inner":
                    # expert-parallel models shard E over 'model'; the inner
                    # ff dim stays unsharded to avoid double-cutting
                    axes.append(None)
                else:
                    axes.append(resolve(logical, mesh_axes))
            pad = ndim - len(axes)               # leading scan axes
            axes = [None] * pad + axes
            # divisibility guard: unshardable dims (odd vocab, few kv heads)
            # fall back to replicated on that dim
            axes = [
                a if shape[i] % _axis_size(a, mesh_sizes) == 0 else None
                for i, a in enumerate(axes)
            ]
            return P(*axes)
    return P(*([None] * ndim))   # norms, scalars, biases: replicated


def param_pspecs(params: Any, *, zero3: bool = False, mesh=None) -> Any:
    """PartitionSpec pytree matching ``params`` via the name rules."""
    if mesh is not None:
        mesh_axes = tuple(mesh.axis_names)
        mesh_sizes = dict(mesh.shape)
    else:
        mesh_axes = _current_mesh_axes() or ()
        mesh_sizes = {}

    def one(path_tuple, leaf):
        path = "/".join(
            str(getattr(k, "key", getattr(k, "idx", k))) for k in path_tuple
        )
        return _spec_for(path, tuple(leaf.shape), zero3, mesh_axes, mesh_sizes)

    return jax.tree_util.tree_map_with_path(one, params)


def device_put_tree(tree: Any, mesh, specs: Any) -> Any:
    """device_put every leaf of ``tree`` onto ``mesh`` per its PartitionSpec.

    ``specs`` is a prefix-pytree of PartitionSpecs (None = leave the leaf
    where it is).  This is the shard-restore back half shared by
    ``checkpoint.manager.CheckpointManager.shard_restore`` and any elastic
    rescale path: the saved layout never constrains the restored one.

    Leaves may be host numpy arrays *or* already device-resident
    ``jax.Array``\\ s (the zero-bounce restore path: device-decoded leaves
    arrive here without ever touching host memory) — ``jax.device_put``
    re-shards a device-resident leaf device-to-device, so the compressed
    payload remains the only host→device transfer of the whole restore.
    """
    from jax.sharding import NamedSharding

    leaves_t, treedef_t = jax.tree_util.tree_flatten(tree)
    leaves_s = (
        treedef_t.flatten_up_to(specs)
        if specs is not None
        else [None] * len(leaves_t)
    )
    out = [
        jax.device_put(l, NamedSharding(mesh, sp)) if sp is not None else l
        for l, sp in zip(leaves_t, leaves_s)
    ]
    return jax.tree_util.tree_unflatten(treedef_t, out)


def batch_pspec(mesh=None) -> P:
    mesh_axes = tuple(mesh.axis_names) if mesh is not None else (
        _current_mesh_axes() or ()
    )
    return P(resolve("batch", mesh_axes))
