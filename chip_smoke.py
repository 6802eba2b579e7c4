#!/usr/bin/env python3
"""On-chip smoke of ZipNN's main path: save → restore → serve on one TPU.

    python chip_smoke.py [--seed 0] [--layers 4]
    python chip_smoke.py --chips 4          # the multi-chip restore only

One process drives every phase through the library's own entry points, at
the published widths of ``qwen15_4b`` (d_model 2560, d_ff 6912, 20 heads
of 128, vocab 151936, QKV bias) with only the depth cut to ``--layers``
(default 4 of 40): about 1.1 B random bf16 parameters from ``--seed``.

* device  — the default backend must be a TPU; on any other platform the
  run fails here, before any work, and prints no result.
* save    — ``CheckpointManager.save(blocking=True)`` with
  ``CodecOptions(backend="device")`` and the canonical ``huffman`` coder,
  so the plane producer and the Huffman bit-pack run as kernels.  Every
  leaf must resolve to the device on all four codec stages, no HUFF symbol
  may be uploaded from the host, and the blobs of the embedding and of
  every layer leaf must be byte-identical to the host codec's.
* restore — ``CheckpointManager.restore(device_resident=True)``: the
  Huffman decode and plane consumer kernels; every leaf must come back a
  TPU array bit-equal to what was saved.
* serve   — ``greedy_generate`` (the path ``launch/serve.py`` runs) with
  batch 4, prompt 16, 8 new tokens: restored params must generate the
  original params' tokens.  Then the compressed-resident ring
  (``CompressedParamStore.from_params(payload_feed=True)`` +
  ``make_compressed_serve_step(ring=2)``) decodes 4 steps whose logits
  must be bit-identical to ``model.decode_step`` run a layer at a time
  (``scan_layers=False``), with zero payload uploads after the warmup
  step.  On a TPU the scanned ``decode_step`` rounds differently; the
  largest logit difference from it is printed.

``--chips 4`` runs only the multi-chip restore and its reference: the same
checkpoint is saved (parameters generated on the host CPU device, so chip
0 holds only what the restore puts there), ``CheckpointManager.
shard_restore`` places it on a 1x4 ('data', 'model') mesh under the
``distributed/sharding.py`` rules, every shard is compared bit for bit
with the host ``restore()``, and each device's peak memory is printed.

Each phase prints one line: what ran, wall seconds with the seconds JAX
spent lowering and compiling split out, and bytes.  Any failure
exits non-zero.  The last line of a passing run is the JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.

The checkpoint is written to ``.smoke_ckpt/`` in the checkout and removed
at exit.  Compiled programs are cached where ``JAX_COMPILATION_CACHE_DIR``
points, or else in ``.jax_cache/`` in the checkout.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
CKPT_DIR = ROOT / ".smoke_ckpt"
ARCH = "qwen15_4b"
BATCH, PROMPT, NEW_TOKENS, RING_STEPS = 4, 16, 8, 4


def compile_s() -> float:
    """Seconds JAX has spent lowering and compiling, from the program's
    ``compile_s`` counter (JAX's own compile-duration events)."""
    from repro.core import tracing

    return tracing.counters()["compile_s"]


class Phase:
    """Times one phase and prints its line, with the seconds spent
    compiling split out."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> "Phase":
        self.t0 = time.perf_counter()
        self.c0 = compile_s()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is not None:
            print(f"phase={self.name} FAILED {exc_type.__name__}: {exc}",
                  flush=True)

    def done(self, what: str, **numbers) -> None:
        wall = time.perf_counter() - self.t0
        comp = compile_s() - self.c0
        fields = " ".join(f"{k}={v}" for k, v in numbers.items())
        print(
            f"phase={self.name} ok wall_s={wall:.2f} compile_s={comp:.2f} "
            f"run_s={wall - comp:.2f} {fields} | {what}",
            flush=True,
        )


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def device_phase(jax, chips: int):
    from repro.kernels import ops

    with Phase("device") as ph:
        devs = jax.devices()
        d0 = devs[0]
        check(d0.platform == "tpu",
              f"default backend is {d0.platform!r}, not a TPU: refusing to "
              "measure anything on it")
        check(len(devs) >= chips, f"{chips} chips asked, {len(devs)} found")
        check(not ops.interpret_mode(), "kernels would run interpreted")
        ph.done(
            "jax.devices() on the default backend",
            platform=d0.platform, kind=repr(d0.device_kind),
            count=len(devs), jax=jax.__version__,
        )
    return devs


def model_and_params(jax, layers: int, seed: int, on_host: bool = False):
    from repro.configs import get_config
    from repro.models import build_model

    full = get_config(ARCH)
    cfg = dataclasses.replace(full, n_layers=layers)
    model = build_model(cfg)
    init = jax.jit(model.init)
    if on_host:
        with jax.default_device(jax.devices("cpu")[0]):
            params = init(jax.random.key(seed))
    else:
        params = init(jax.random.key(seed))
    jax.block_until_ready(params)
    return full, cfg, model, params


def flat_leaves(jax, tree):
    """('a/b/c', leaf) pairs in the checkpoint manager's key format."""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        out.append((key, leaf))
    return out


def smoke_config():
    from repro.core import zipnn

    return zipnn.ZipNNConfig(backend="huffman")


def checkpoint_manager(directory, zcfg, *, device: bool):
    from repro.checkpoint import CheckpointConfig, CheckpointManager
    from repro.core import zipnn

    return CheckpointManager(CheckpointConfig(
        str(directory),
        async_save=False,
        zipnn=zcfg,
        options=zipnn.CodecOptions(backend="device") if device else None,
    ))


def save_phase(jax, full, cfg, params, ckpt_dir, zcfg, *, compare: bool = True):
    """Device save, with every stage checked to have run on the device."""
    import numpy as np

    from repro.core import bitlayout, device_entropy, device_plane, device_unplane, zipnn

    with Phase("save") as ph:
        state = {"params": params}
        leaves = flat_leaves(jax, state)
        for key, leaf in leaves:
            layout = bitlayout.LAYOUTS[leaf.dtype.name]
            cp = zcfg.plane_params(layout.itemsize)
            stages = {
                "plane": device_plane.resolve("device", layout, cp),
                "entropy": device_entropy.resolve("device", layout, cp),
                "unplane": device_unplane.resolve("device", layout),
                "decode": device_entropy.resolve_decode("device", cp.chunk_bytes),
            }
            check(set(stages.values()) == {"device"},
                  f"{key} would leave the device: {stages}")
        mgr = checkpoint_manager(ckpt_dir, zcfg, device=True)
        device_entropy.reset_transfer_stats()
        mgr.save(0, state, blocking=True)
        uploads = device_entropy.transfer_stats()
        check(uploads["payload_uploads"] == 0,
              f"HUFF symbols were uploaded from the host: {uploads}")
        with open(ckpt_dir / "step_0" / "manifest.json") as f:
            manifest = json.load(f)
        ph.done(
            f"CheckpointManager.save(blocking=True) of {ARCH} at published "
            f"widths, depth cut {full.n_layers}->{cfg.n_layers} layers, "
            f"{len(leaves)} leaves, plane producer + bit-pack kernels",
            raw_bytes=manifest["raw_bytes"], stored_bytes=manifest["comp_bytes"],
            ratio_pct=f"{100 * manifest['comp_bytes'] / manifest['raw_bytes']:.2f}",
            host_symbol_uploads=uploads["payload_uploads"],
        )
    if not compare:
        return mgr, manifest

    with Phase("save-vs-host") as ph:
        host = zipnn.ZipNNConfig(backend="huffman", threads=-1)
        entries = {e["key"]: e for e in manifest["entries"]}
        picked = [(k, leaf) for k, leaf in leaves
                  if k == "params/embed/table" or k.startswith("params/layers/")]
        data = (ckpt_dir / "step_0" / "data.bin").read_bytes()
        compared = 0
        for key, leaf in picked:
            e = entries[key]
            blob = data[e["offset"] : e["offset"] + e["size"]]
            want = zipnn.compress_array(np.asarray(jax.device_get(leaf)), host).blob
            check(blob == want, f"{key}: device blob differs from the host codec's")
            compared += len(blob)
        ph.done(
            f"{len(picked)} blobs (embedding + every layer leaf) byte-identical "
            "to the host codec",
            compared_bytes=compared,
        )
    return mgr, manifest


def peak_bytes(device):
    """``peak_bytes_in_use`` where the backend reports memory stats."""
    return (device.memory_stats() or {}).get("peak_bytes_in_use", "not-reported")


def bit_equal(jax, a, b) -> bool:
    import jax.numpy as jnp

    if a.shape != b.shape or a.dtype != b.dtype:
        return False
    u = jnp.dtype(f"uint{8 * a.dtype.itemsize}")
    return bool(jnp.all(
        jax.lax.bitcast_convert_type(a, u) == jax.lax.bitcast_convert_type(b, u)
    ))


def restore_phase(jax, mgr, params, manifest, d0):
    from repro.core import device_entropy

    with Phase("restore") as ph:
        device_entropy.reset_transfer_stats()
        step, tree = mgr.restore(device_resident=True)
        check(step == 0, f"restored step {step}, saved 0")
        uploads = device_entropy.transfer_stats()
        got = dict(flat_leaves(jax, tree))
        want = flat_leaves(jax, {"params": params})
        check(set(got) == {k for k, _ in want}, "restored tree has other keys")
        for key, leaf in want:
            r = got[key]
            check(isinstance(r, jax.Array) and r.devices() == {d0},
                  f"{key} restored as {type(r).__name__}, not a {d0} array")
            check(bit_equal(jax, r, leaf), f"{key} differs from what was saved")
        ph.done(
            "CheckpointManager.restore(device_resident=True): Huffman decode "
            "+ plane consumer kernels, every leaf a TPU array bit-equal to "
            "the saved one",
            raw_bytes=manifest["raw_bytes"],
            payload_uploads=uploads["payload_uploads"],
            payload_upload_bytes=uploads["payload_bytes"],
        )
    return tree["params"]


def serve_phase(jax, model, cfg, params, restored, zcfg, seed, d0):
    import jax.numpy as jnp

    from repro.core import device_entropy, zipnn
    from repro.models import build_model
    from repro.serve.compressed import CompressedParamStore
    from repro.serve.step import greedy_generate, make_compressed_serve_step

    with Phase("serve-generate") as ph:
        prompt = jax.random.randint(
            jax.random.key(seed + 1), (BATCH, PROMPT), 0, cfg.vocab_size,
            dtype=jnp.int32,
        )
        want, _ = greedy_generate(model, params, prompt, NEW_TOKENS)
        got, _ = greedy_generate(model, restored, prompt, NEW_TOKENS)
        check(got.shape == (BATCH, NEW_TOKENS), f"generated shape {got.shape}")
        check(bool(jnp.array_equal(got, want)),
              "restored params generate other tokens than the originals")
        ph.done(
            f"greedy_generate batch {BATCH}, prompt {PROMPT}, {NEW_TOKENS} new "
            "tokens: restored params generate the original tokens",
            tokens=BATCH * NEW_TOKENS,
        )

    with Phase("serve-ring") as ph:
        store = CompressedParamStore.from_params(
            params, zcfg, options=zipnn.CodecOptions(backend="device"),
            payload_feed=True,
        )
        step = make_compressed_serve_step(model, store, ring=2)
        # The ring runs a layer at a time, and so does decode_step with
        # scan_layers=False; on a TPU the scanned step rounds differently,
        # so its distance is reported, not required to be zero.
        ref = jax.jit(build_model(
            dataclasses.replace(cfg, scan_layers=False)
        ).decode_step)
        scanned = jax.jit(model.decode_step)
        state = model.init_decode_state(BATCH, RING_STEPS + 1, start_pos=0)
        ref_state = scan_state = state
        tok = jnp.ones((BATCH, 1), jnp.int32)
        scan_gap = 0.0
        for t in range(RING_STEPS + 1):
            logits, state = step(state, tok)
            ref_logits, ref_state = ref(params, ref_state, tok)
            check(bit_equal(jax, logits, ref_logits),
                  f"ring logits differ from decode_step at step {t}")
            scan_logits, scan_state = scanned(params, scan_state, tok)
            scan_gap = max(scan_gap, float(jnp.max(jnp.abs(
                logits.astype(jnp.float32) - scan_logits.astype(jnp.float32)
            ))))
            if t == 0:                         # warmup: compile + first ring
                device_entropy.reset_transfer_stats()
            tok = jnp.argmax(ref_logits[:, -1:], axis=-1).astype(jnp.int32)
        uploads = device_entropy.transfer_stats()
        check(uploads["payload_uploads"] == 0,
              f"payload uploads after warmup: {uploads}")
        check(store.peak_resident <= 2,
              f"ring held {store.peak_resident} decoded layers")
        peak = peak_bytes(d0)
        ph.done(
            f"CompressedParamStore(payload_feed=True) + ring=2 serve step, "
            f"{RING_STEPS} steps after warmup: logits bit-identical to "
            "model.decode_step layer at a time (scan_layers=False), zero "
            "payload uploads",
            payload_hbm_bytes=store.device_payload_bytes,
            raw_layer_bytes=store.raw_bytes, stored_layer_bytes=store.comp_bytes,
            post_warmup_uploads=uploads["payload_uploads"],
            max_abs_vs_scanned_step=f"{scan_gap:.3g}",
            peak_bytes_in_use=peak,
        )


def multichip_phase(jax, cfg, mgr, params, zcfg, chips: int):
    import numpy as np

    from repro.distributed import sharding
    from repro.launch.mesh import make_model_mesh

    devs = jax.devices()[:chips]
    with Phase("shard-restore") as ph:
        mesh = make_model_mesh(chips)
        specs = {"params": sharding.param_pspecs(params, zero3=cfg.zero3, mesh=mesh)}
        before = [peak_bytes(d) for d in devs]
        step, tree = mgr.shard_restore(None, mesh, specs)
        jax.block_until_ready(tree)
        peaks = [peak_bytes(d) for d in devs]
        ph.done(
            f"CheckpointManager.shard_restore onto a 1x{chips} ('data', "
            "'model') mesh under the distributed/sharding.py rules",
            peak_bytes_before=",".join(map(str, before)),
            peak_bytes_in_use=",".join(map(str, peaks)),
        )

    with Phase("shard-vs-host") as ph:
        host_mgr = checkpoint_manager(
            mgr.cfg.directory, dataclasses.replace(zcfg, threads=0), device=False
        )
        _, host_tree = host_mgr.restore(step)
        host = dict(flat_leaves(jax, host_tree))
        shards = 0
        for key, leaf in flat_leaves(jax, tree):
            want = host[key]
            check(isinstance(want, np.ndarray), f"host restore gave {type(want)}")
            u = np.dtype(f"uint{8 * want.dtype.itemsize}")
            for shard in leaf.addressable_shards:
                got = np.asarray(shard.data)
                check(got.view(u).tobytes() == want[shard.index].view(u).tobytes(),
                      f"{key} shard on {shard.device} differs from the host restore")
                shards += 1
        ph.done(
            f"every shard of {len(host)} leaves bit-equal to the host restore()",
            shards=shards,
        )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--layers", type=int, default=4,
                    help="depth cut of qwen15_4b (published: 40)")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the multi-chip restore and its reference")
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    sys.path.insert(0, str(ROOT / "src"))
    compile_s()                     # start counting compiles from here
    devs = device_phase(jax, args.chips)

    zcfg = smoke_config()
    shutil.rmtree(CKPT_DIR, ignore_errors=True)
    try:
        if args.chips == 1:
            full, cfg, model, params = model_and_params(jax, args.layers, args.seed)
            mgr, manifest = save_phase(jax, full, cfg, params, CKPT_DIR, zcfg)
            restored = restore_phase(jax, mgr, params, manifest, devs[0])
            serve_phase(jax, model, cfg, params, restored, zcfg, args.seed, devs[0])
        else:
            full, cfg, _, params = model_and_params(
                jax, args.layers, args.seed, on_host=True
            )
            mgr, _ = save_phase(jax, full, cfg, params, CKPT_DIR, zcfg,
                                compare=False)
            multichip_phase(jax, cfg, mgr, params, zcfg, args.chips)
    finally:
        shutil.rmtree(CKPT_DIR, ignore_errors=True)

    print(f"total_wall_s={time.perf_counter() - t_start:.2f}", flush=True)
    d0 = devs[0]
    print(json.dumps({
        "ok": True,
        "device": {"platform": d0.platform, "kind": d0.device_kind,
                   "count": len(devs)},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
