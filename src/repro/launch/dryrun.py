import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
).strip()

"""Multi-pod dry-run: lower + compile every (architecture × shape × mesh)
cell and extract the roofline terms from the compiled artifact.

The two lines above run before ANY other import — jax locks the device
count at first init, and the dry-run needs 512 placeholder host devices to
build the production meshes.  Smoke tests and benchmarks never import this
module, so they keep seeing 1 device.

Per cell we record to experiments/dryrun/<cell>.json:
  * per-device argument/output/temp bytes (memory_analysis → proves it fits)
  * per-device HLO FLOPs and bytes accessed (cost_analysis)
  * collective bytes by opcode, parsed from the post-SPMD optimized HLO
  * MODEL_FLOPS (6·N·D dense / 6·N_active·D MoE) for the useful-compute ratio

Usage:
  python -m repro.launch.dryrun --arch yi_6b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both
"""

import argparse     # noqa: E402
import json         # noqa: E402
import re           # noqa: E402
import time         # noqa: E402
import traceback    # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

import jax          # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import SHAPES, get_config, list_archs, shape_cells  # noqa: E402
from repro.data import DataConfig, batch_specs  # noqa: E402
from repro.launch.mesh import make_production_mesh, n_chips  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.optim import AdamWConfig  # noqa: E402
from repro.serve.step import decode_state_specs, make_serve_step  # noqa: E402
from repro.train.step import (  # noqa: E402
    abstract_train_state,
    batch_pspecs,
    make_train_step,
    train_state_specs,
)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)


_SHAPE_RE = re.compile(
    r"(f64|f32|f16|bf16|s64|u64|s32|u32|s16|u16|s8|u8|pred|c64|c128)\[([\d,]*)\]"
)
_OP_RE = re.compile(
    r"^(?:ROOT\s+)?%[\w.\-]+\s*=\s*(\(?[^=]*?)\s*"
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\("
)
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dims = [int(x) for x in m.group(2).split(",") if x] or [1]
        sz = _DTYPE_BYTES[m.group(1)]
        for d in dims:
            sz *= d
        total += sz
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-device collective wire bytes, parsed from post-SPMD optimized HLO.

    This dialect prints no operand types inline, so we size each op from its
    RESULT type and convert to approximate per-device wire bytes with
    opcode-specific factors (ring schedules):
      all-gather        → result            (each device receives ≈ full)
      all-reduce        → 2 × result        (reduce-scatter + all-gather)
      reduce-scatter    → result × (gs − 1) (receives the other shards)
      all-to-all        → result            (sends/receives ≈ result)
      collective-permute→ result
    Async pairs count once (the -done line; -start skipped — its tuple type
    aliases both buffers)."""
    out = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = _OP_RE.match(ls)
        if not m:
            continue
        result_types, op, suffix = m.group(1), m.group(2), m.group(3)
        if suffix == "-start":
            continue
        rbytes = _shape_bytes(result_types)
        gm = _GROUPS_RE.search(ls)
        if gm:
            gs = int(gm.group(2))
        else:
            gl = _GROUPS_LIST_RE.search(ls)
            gs = len(gl.group(1).split(",")) if gl else 2
        if op == "all-reduce":
            wire = 2 * rbytes
        elif op == "reduce-scatter":
            wire = rbytes * max(gs - 1, 1)
        else:
            wire = rbytes
        out[op] += wire
        out["count"] += 1
    return out


def input_specs(arch: str, shape: str) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every input of the lowered step —
    weak-type-correct, shardable, zero allocation."""
    cfg = get_config(arch)
    cell = SHAPES[shape]
    model = build_model(cfg)
    dc = DataConfig(seq_len=cell.seq_len, global_batch=cell.global_batch)
    if cell.kind == "train":
        return {
            "state": abstract_train_state(model),
            "batch": batch_specs(cfg, dc),
        }
    if cell.kind == "prefill":
        return {
            "params": model.abstract_params(),
            "batch": batch_specs(cfg, dc),
        }
    # decode: one new token against a full cache
    state = jax.eval_shape(
        lambda: model.init_decode_state(cell.global_batch, cell.seq_len)
    )
    return {
        "params": model.abstract_params(),
        "dstate": state,
        "tokens": jax.ShapeDtypeStruct((cell.global_batch, 1), jnp.int32),
    }


def _shardings(tree, specs, mesh):
    return jax.tree_util.tree_map(
        lambda sp: NamedSharding(mesh, sp), specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def _with_dispatch_shards(cfg, cell, mesh):
    """MoE dispatch locality: one dispatch row per batch shard."""
    if not cfg.moe:
        return cfg
    bs = 1
    for a in ("pod", "data"):
        bs *= mesh.shape.get(a, 1)
    t = cell.global_batch * (cell.seq_len if cell.kind != "decode" else 1)
    if t % bs:
        bs = 1
    return dataclasses.replace(cfg, dispatch_shards=bs)


def _lower_and_compile(cfg, cell, mesh):
    """Lower + compile one step for a (possibly replaced) config."""
    cfg = _with_dispatch_shards(cfg, cell, mesh)
    model = build_model(cfg)
    dc = DataConfig(seq_len=cell.seq_len, global_batch=cell.global_batch)
    with mesh:
        if cell.kind == "train":
            state = abstract_train_state(model)
            batch = batch_specs(cfg, dc)
            sspecs = train_state_specs(model, mesh)
            bspecs = batch_pspecs(batch, mesh)
            step = make_train_step(model, AdamWConfig())
            jitted = jax.jit(
                step,
                in_shardings=(
                    _shardings(state, sspecs, mesh),
                    _shardings(batch, bspecs, mesh),
                ),
                donate_argnums=(0,),
            )
            lowered = jitted.lower(state, batch)
        elif cell.kind == "prefill":
            params = model.abstract_params()
            batch = batch_specs(cfg, dc)
            pspecs = model.param_specs(mesh)
            bspecs = batch_pspecs(batch, mesh)

            def prefill(p, b):
                return model.forward(p, b)[0]

            jitted = jax.jit(
                prefill,
                in_shardings=(
                    _shardings(params, pspecs, mesh),
                    _shardings(batch, bspecs, mesh),
                ),
            )
            lowered = jitted.lower(params, batch)
        else:  # decode
            from repro.serve.step import inference_param_specs

            params = model.abstract_params()
            dstate = jax.eval_shape(
                lambda: model.init_decode_state(cell.global_batch, cell.seq_len)
            )
            tokens = jax.ShapeDtypeStruct((cell.global_batch, 1), jnp.int32)
            pspecs = inference_param_specs(model, mesh)
            dspecs = decode_state_specs(model, dstate, mesh)
            serve = make_serve_step(model)
            jitted = jax.jit(
                serve,
                in_shardings=(
                    _shardings(params, pspecs, mesh),
                    _shardings(dstate, dspecs, mesh),
                    NamedSharding(
                        mesh,
                        P("data" if cell.global_batch % mesh.shape["data"] == 0 else None),
                    ),
                ),
                donate_argnums=(1,),
            )
            lowered = jitted.lower(params, dstate, tokens)
        compiled = lowered.compile()
    return compiled


def _costs_of(compiled) -> Dict[str, float]:
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    out = {"flops": float(cost.get("flops", 0.0)),
           "bytes_accessed": float(cost.get("bytes accessed", 0.0))}
    for k, v in coll.items():
        out[f"coll_{k}"] = float(v)
    return out


import dataclasses  # noqa: E402


def accounting_costs(cfg, cell, mesh) -> Dict[str, float]:
    """Trip-count-correct per-device cost terms.

    XLA's cost analysis counts a lax.scan body ONCE (verified empirically),
    so the production lowering (scanned layers + flash attention) massively
    undercounts FLOPs/collectives.  We lower an *accounting variant* —
    unrolled layer stack + dense masked attention (flop-identical to masked
    flash) — at 2–3 small depths and extrapolate linearly in depth, which is
    exact because layers are homogeneous.  Memory/compile-proof still come
    from the production variant.
    """
    def series(over) -> Dict[str, float]:
        fam = cfg.family
        if fam == "hybrid":
            f6 = _costs_of(_lower_and_compile(dataclasses.replace(cfg, n_layers=6, **over), cell, mesh))
            f7 = _costs_of(_lower_and_compile(dataclasses.replace(cfg, n_layers=7, **over), cell, mesh))
            f12 = _costs_of(_lower_and_compile(dataclasses.replace(cfg, n_layers=12, **over), cell, mesh))
            out = {}
            ng = cfg.n_layers // cfg.shared_attn_every      # 13 shared applications
            for k in f6:
                m = f7[k] - f6[k]                            # one mamba layer
                s = (f12[k] - f6[k]) - 6 * m                 # one shared block
                base = f6[k] - 6 * m - s
                out[k] = base + cfg.n_layers * m + ng * s
            return out
        if cfg.moe and cfg.first_k_dense:
            f2 = _costs_of(_lower_and_compile(dataclasses.replace(cfg, n_layers=2, **over), cell, mesh))
            f3 = _costs_of(_lower_and_compile(dataclasses.replace(cfg, n_layers=3, **over), cell, mesh))
            return {k: f2[k] + (cfg.n_layers - 2) * (f3[k] - f2[k]) for k in f2}
        f1 = _costs_of(_lower_and_compile(dataclasses.replace(cfg, n_layers=1, **over), cell, mesh))
        f2 = _costs_of(_lower_and_compile(dataclasses.replace(cfg, n_layers=2, **over), cell, mesh))
        return {k: f1[k] + (cfg.n_layers - 1) * (f2[k] - f1[k]) for k in f1}

    acct = series(dict(scan_layers=False, attn_impl="dense"))
    # The dense-attention series is flop/collective-exact but its
    # bytes_accessed materializes S×S scores the flash path never writes to
    # HBM.  For train/prefill of attention archs, a second flash series
    # provides the memory term (ideal-reuse lower bound; dense = upper).
    if cell.kind != "decode" and cfg.family != "ssm":
        flash = series(dict(scan_layers=False, attn_impl="flash"))
        acct["bytes_accessed_dense_ub"] = acct["bytes_accessed"]
        acct["bytes_accessed"] = flash["bytes_accessed"]
    return acct


def run_cell(
    arch: str, shape: str, *, multi_pod: bool, out_dir: Optional[str] = None,
    cfg_override=None, tag: str = "",
) -> Dict[str, Any]:
    cfg = cfg_override or get_config(arch)
    cell = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = n_chips(mesh)
    t0 = time.time()
    compiled = _lower_and_compile(cfg, cell, mesh)
    t_compile = time.time() - t0
    t_lower = 0.0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())

    # Accounting terms feed the single-pod roofline table only; the
    # multi-pod pass is the pod-axis shard proof (lower+compile+memory).
    if not multi_pod:
        t0 = time.time()
        acct = accounting_costs(cfg, cell, mesh)
        t_acct = time.time() - t0
    else:
        acct, t_acct = {}, 0.0

    n_params = cfg.param_count()
    n_active = cfg.active_param_count()
    if cell.kind == "train":
        tokens = cell.seq_len * cell.global_batch
        model_flops = 6 * n_active * tokens
    elif cell.kind == "prefill":
        tokens = cell.seq_len * cell.global_batch
        model_flops = 2 * n_active * tokens
    else:
        tokens = cell.global_batch          # one token per sequence
        model_flops = 2 * n_active * tokens

    result = {
        "arch": arch,
        "shape": shape,
        "kind": cell.kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "seq_len": cell.seq_len,
        "global_batch": cell.global_batch,
        "params": int(n_params),
        "active_params": int(n_active),
        "model_flops": float(model_flops),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "accounting_s": round(t_acct, 1),
        # production lowering (scan+flash): true memory picture; its
        # flops/collectives are scan-undercounted and kept for reference only
        "per_device_production_raw": {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
            "collectives_bytes": coll,
        },
        "per_device_memory": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            "peak_hint_bytes": int(
                mem.argument_size_in_bytes
                + mem.output_size_in_bytes
                + mem.temp_size_in_bytes
                - mem.alias_size_in_bytes
            ),
        },
        # trip-count-correct accounting (unrolled + dense attn, extrapolated)
        "per_device_accounting": acct,
        "status": "ok",
    }
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fn = os.path.join(out_dir, f"{arch}.{shape}.{result['mesh']}{tag}.json")
        with open(fn, "w") as f:
            json.dump(result, f, indent=1)
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", type=str, default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true", help="every applicable cell")
    ap.add_argument("--out", type=str, default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in list_archs():
            for cell in shape_cells(get_config(arch)):
                cells.append((arch, cell.name))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    failures = 0
    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch}.{shape}.{'2x16x16' if mp else '16x16'}"
            path = os.path.join(args.out, f"{tag}.json")
            if args.skip_existing and os.path.exists(path):
                print(f"[skip] {tag}")
                continue
            try:
                r = run_cell(arch, shape, multi_pod=mp, out_dir=args.out)
                acct = r["per_device_accounting"]
                mem = r["per_device_memory"]
                coll_sum = sum(
                    v for k, v in acct.items()
                    if k.startswith("coll_") and k != "coll_count"
                )
                print(
                    f"[ok]   {tag}: compile={r['compile_s']}s acct={r['accounting_s']}s "
                    f"flops/dev={acct.get('flops', 0):.3g} "
                    f"mem/dev={mem['peak_hint_bytes']/2**30:.2f}GiB "
                    f"coll/dev={coll_sum/2**20:.1f}MiB", flush=True,
                )
            except Exception as e:
                failures += 1
                print(f"[FAIL] {tag}: {type(e).__name__}: {e}")
                traceback.print_exc(limit=4)
                if args.out:
                    os.makedirs(args.out, exist_ok=True)
                    with open(path, "w") as f:
                        json.dump({"arch": arch, "shape": shape, "status": "fail",
                                   "error": f"{type(e).__name__}: {e}"}, f)
    if failures:
        raise SystemExit(f"{failures} dry-run cells failed")


if __name__ == "__main__":
    main()
