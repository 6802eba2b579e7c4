"""ZipNN-compressed checkpointing with delta chains and periodic bases.

This is the paper's §2.1.3/§4.2 use case as a production subsystem:

* every checkpoint is ZipNN-compressed per tensor (exponent extraction +
  byte grouping + Huffman-only entropy coding);
* between periodic **bases** (every ``base_every`` saves), checkpoints are
  stored as XOR **deltas against the last base** — recovery cost is bounded
  at base+one-delta, never a chain (§4.2 "Periodic Base");
* **optimizer moments** (AdamW ``m``/``v`` trees — the fp32 bulk of a
  mixed-precision checkpoint) are instead stored as deltas **against the
  previous save**: moments are EMAs, so step-over-step deltas are far
  sparser than vs-base deltas.  Restore replays the chain (bounded at
  ``base_every`` links — bases always store moments in full) bit-exactly,
  memoizing each intermediate save so a chain of k loads each checkpoint
  once, not O(k²) times;
* §4.2 auto-detection picks Huffman vs LZ per chunk of each delta;
* saves are **async** (compression+IO off the training critical path),
  **atomic** (tmp dir + os.replace — a crash mid-save can never corrupt the
  latest valid checkpoint), and **CRC-verified** on load: restore() scans
  back to the newest *valid* checkpoint, skipping torn ones;
* restore returns host numpy trees; ``shard_restore`` device_puts them to
  any mesh/PartitionSpecs — the elastic-rescale path (the saved layout does
  not constrain the restored one).

Layout:  <dir>/step_<N>/{manifest.json, data.bin}
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import zlib
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.core import tracing, zipnn
from repro.optim.adamw import MOMENT_KEYS, is_moment_path

PyTree = Any


@dataclasses.dataclass
class CheckpointConfig:
    directory: str
    base_every: int = 5              # every k-th save is a full base (§4.2)
    keep_bases: int = 2              # retention: bases (+ their deltas)
    async_save: bool = True
    # Engine workers for per-tensor (plane, chunk) compression — stacks with
    # async_save: the save thread fans chunk work items across the pool.
    # 0/1 serial, N > 1 pool workers, -1 all cores (see core/engine.py).
    threads: int = 0
    # Plane-producer backend for the compression front half: 'host' |
    # 'device' | 'auto' (see core/device_plane.py).  'device' fuses
    # rotate+byte-group+probe into one Pallas dispatch per save batch AND
    # routes the entropy stage through the fused Huffman bit-pack dispatch
    # (core/device_entropy.py, canonical 'huffman' coder only);
    # checkpoint bytes are identical for every setting.
    backend: str = "host"
    # Entropy-stage override for mixed mode (None follows `backend`):
    # 'host' | 'device' | 'auto' — see core/device_entropy.py.
    entropy_backend: Optional[str] = None
    # The unified knob bag (core/options.py): non-None fields fold into the
    # three legacy fields above (which still win when set explicitly), then
    # everything merges into the carried ZipNNConfig as before.
    options: Optional[zipnn.CodecOptions] = None
    # Flat-key prefixes treated as optimizer moments (delta-vs-previous-save
    # chains).  () disables moment chaining entirely.
    moment_keys: Tuple[str, ...] = MOMENT_KEYS
    zipnn: zipnn.ZipNNConfig = dataclasses.field(default_factory=zipnn.ZipNNConfig)

    def __post_init__(self) -> None:
        if self.options is not None:
            if self.options.threads is not None and not self.threads:
                self.threads = self.options.threads
            if self.options.backend is not None and self.backend == "host":
                self.backend = self.options.backend
            if self.options.entropy_backend is not None and self.entropy_backend is None:
                self.entropy_backend = self.options.entropy_backend
        if self.threads and not self.zipnn.threads:
            self.zipnn = dataclasses.replace(self.zipnn, threads=self.threads)
        if self.backend != "host" and self.zipnn.plane_backend == "host":
            self.zipnn = dataclasses.replace(self.zipnn, plane_backend=self.backend)
        if self.entropy_backend is not None and self.zipnn.entropy_backend is None:
            self.zipnn = dataclasses.replace(
                self.zipnn, entropy_backend=self.entropy_backend
            )


def _flatten(tree: PyTree) -> Dict[str, np.ndarray]:
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        out[key] = np.asarray(tracing.fetch(leaf))
    return out


def _unflatten(flat: Dict[str, np.ndarray]) -> PyTree:
    root: Dict[str, Any] = {}
    for key, val in flat.items():
        parts = key.split("/")
        node = root
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = val
    return root


class CheckpointManager:
    def __init__(self, config: CheckpointConfig):
        self.cfg = config
        os.makedirs(config.directory, exist_ok=True)
        self._thread: Optional[threading.Thread] = None
        self._save_count = 0
        self._last_base_step: Optional[int] = None
        self._last_base_flat: Optional[Dict[str, np.ndarray]] = None
        # Moment-chain bookkeeping: the previous save's moment arrays (kept
        # in host RAM — fp32 moments of the model, one save's worth) and its
        # step.  Lost on restart, in which case the next save simply stores
        # moments vs-base/full again — chains never span a process restart.
        self._last_save_step: Optional[int] = None
        self._last_moment_flat: Optional[Dict[str, np.ndarray]] = None
        self._errors: List[BaseException] = []
        # resume bookkeeping from disk
        for step, kind, base in self._scan():
            self._save_count += 1
            if kind == "base":
                self._last_base_step = step

    # ------------------------------------------------------------------ save

    def save(self, step: int, state: PyTree, *, blocking: bool = False) -> None:
        """Snapshot is taken synchronously; compression+IO go async.

        A blocking save (``blocking=True`` or ``async_save=False``) raises
        its own error.  An async save's error is raised by the next
        :meth:`wait` or :meth:`save`.
        """
        with tracing.operation("znn.ckpt.save"):
            self._save(step, state, blocking)

    def _save(self, step: int, state: PyTree, blocking: bool) -> None:
        self.wait()
        with tracing.span("znn.ckpt.snapshot"):
            flat = _flatten(state)
        is_base = (
            self._save_count % self.cfg.base_every == 0
            or self._last_base_flat is None
            and self._last_base_step is None
        )
        self._save_count += 1
        base_flat = None if is_base else self._last_base_flat
        base_step = None if is_base else self._last_base_step
        if base_flat is None and not is_base:
            is_base = True                      # lost base in memory ⇒ full save
        prev_flat = None if is_base else self._last_moment_flat
        prev_step = None if is_base else self._last_save_step

        def work():
            self._write(
                step, flat, is_base, base_flat, base_step,
                prev_flat, prev_step,
            )
            if is_base:
                self._last_base_step = step
                self._last_base_flat = flat
            if self.cfg.moment_keys:
                self._last_moment_flat = {
                    k: v for k, v in flat.items()
                    if is_moment_path(k, self.cfg.moment_keys)
                }
                self._last_save_step = step
            self._gc()

        op = tracing.current_op()

        def work_async():
            try:
                with tracing.joined(op):
                    work()
            except BaseException as e:          # surfaced on next wait()
                self._errors.append(e)

        if blocking or not self.cfg.async_save:
            work()
        else:
            self._thread = threading.Thread(target=work_async, daemon=True)
            self._thread.start()

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._errors:
            err = self._errors[:]
            self._errors.clear()
            raise RuntimeError(f"async checkpoint save failed: {err[0]}") from err[0]

    def _write(
        self,
        step: int,
        flat: Dict[str, np.ndarray],
        is_base: bool,
        base_flat: Optional[Dict[str, np.ndarray]],
        base_step: Optional[int],
        prev_flat: Optional[Dict[str, np.ndarray]] = None,
        prev_step: Optional[int] = None,
    ) -> None:
        tmp = os.path.join(self.cfg.directory, f".tmp_step_{step}")
        final = os.path.join(self.cfg.directory, f"step_{step}")
        os.makedirs(tmp, exist_ok=True)
        keys = sorted(flat)
        # Optimizer moments delta against the PREVIOUS save (EMA state moves
        # a little every step, so vs-prev deltas are much sparser than
        # vs-base) — bases still store moments in full, which bounds the
        # restore chain at base_every links.
        prev_keys = [
            k for k in keys
            if prev_flat is not None
            and prev_step is not None
            and is_moment_path(k, self.cfg.moment_keys)
            and k in prev_flat
            and prev_flat[k].shape == flat[k].shape
            and prev_flat[k].dtype == flat[k].dtype
        ]
        prev_set = frozenset(prev_keys)
        # Delta leaves go through ONE batched call: with the device backend,
        # same-dtype (new, base) pairs pack into a single fused
        # XOR→byte-group→probe dispatch (produce_planes_batched(bases=...))
        # instead of a kernel launch + transfer per leaf.  Blobs are
        # identical to the leaf-at-a-time path on every backend.
        delta_keys = [
            k for k in keys
            if not is_base
            and k not in prev_set
            and k in base_flat
            and base_flat[k].shape == flat[k].shape
        ]
        delta_cts = dict(
            zip(
                delta_keys,
                zipnn.delta_compress_batched(
                    [flat[k] for k in delta_keys],
                    [base_flat[k] for k in delta_keys],
                    self.cfg.zipnn,
                ),
            )
        )
        moment_cts = dict(
            zip(
                prev_keys,
                zipnn.delta_compress_batched(
                    [flat[k] for k in prev_keys],
                    [prev_flat[k] for k in prev_keys],
                    self.cfg.zipnn,
                ),
            )
        )
        entries = []
        offset = 0
        with open(os.path.join(tmp, "data.bin"), "wb") as f:
            for key in keys:
                arr = flat[key]
                if key in moment_cts:
                    ct = moment_cts[key]
                    kind = "delta_prev"
                elif key in delta_cts:
                    ct = delta_cts[key]
                    kind = "delta"
                else:
                    ct = zipnn.compress_array(arr, self.cfg.zipnn)
                    kind = "full"
                with tracing.span("znn.ckpt.write"):
                    f.write(ct.blob)
                entries.append(
                    {
                        "key": key,
                        "kind": kind,
                        "dtype": ct.dtype,
                        "shape": list(ct.shape),
                        "offset": offset,
                        "size": len(ct.blob),
                        "crc": zlib.crc32(ct.blob),
                        "raw": int(arr.nbytes),
                    }
                )
                offset += len(ct.blob)
            with tracing.span("znn.ckpt.write"):
                f.flush()
                os.fsync(f.fileno())
        manifest = {
            "step": step,
            "kind": "base" if is_base else "delta",
            "base_step": base_step,
            "prev_step": prev_step if prev_keys else None,
            "comp_bytes": offset,
            "raw_bytes": sum(e["raw"] for e in entries),
            "entries": entries,
        }
        with tracing.span("znn.ckpt.write"):
            with open(os.path.join(tmp, "manifest.json"), "w") as f:
                json.dump(manifest, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, final)              # atomic publish

    # --------------------------------------------------------------- restore

    def _scan(self) -> List[Tuple[int, str, Optional[int]]]:
        out = []
        with tracing.span("znn.ckpt.scan"):
            for name in sorted(os.listdir(self.cfg.directory)):
                if not name.startswith("step_"):
                    continue
                mpath = os.path.join(self.cfg.directory, name, "manifest.json")
                try:
                    with open(mpath) as f:
                        m = json.load(f)
                    out.append((m["step"], m["kind"], m.get("base_step")))
                except (OSError, json.JSONDecodeError):
                    continue                    # torn checkpoint: skip
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        steps = self._scan()
        return steps[-1][0] if steps else None

    def _load_flat(
        self,
        step: int,
        device_resident: bool = False,
        _cache: Optional[Dict[int, Dict[str, np.ndarray]]] = None,
    ) -> Dict[str, np.ndarray]:
        # Memoize per restore call: a delta save references both its base
        # (weights) and the previous save (moments, "delta_prev"), and the
        # previous save references the base again — without the cache the
        # moment chain would re-decode every ancestor O(k^2) times.
        if _cache is None:
            _cache = {}
        if step in _cache:
            return _cache[step]
        d = os.path.join(self.cfg.directory, f"step_{step}")
        with tracing.span("znn.ckpt.read"):
            with open(os.path.join(d, "manifest.json")) as f:
                manifest = json.load(f)
            with open(os.path.join(d, "data.bin"), "rb") as f:
                data = f.read()
        base_flat = None
        if manifest["kind"] == "delta":
            # The base rides the same residence as the restore target: a
            # device-resident restore XORs against a device-resident base
            # (fused on device), never bouncing either through host memory.
            base_flat = self._load_flat(
                manifest["base_step"], device_resident=device_resident,
                _cache=_cache,
            )
        prev_flat = None
        if manifest.get("prev_step") is not None:
            prev_flat = self._load_flat(
                manifest["prev_step"], device_resident=device_resident,
                _cache=_cache,
            )
        out = {}
        full_entries = []
        full_cts = []
        for e in manifest["entries"]:
            blob = data[e["offset"] : e["offset"] + e["size"]]
            with tracing.span("znn.ckpt.entry_crc"):
                crc = zlib.crc32(blob)
            if crc != e["crc"]:
                raise IOError(f"CRC mismatch in step_{step}:{e['key']}")
            ct = zipnn.CompressedTensor(blob, e["dtype"], tuple(e["shape"]))
            if e["kind"] == "delta":
                out[e["key"]] = zipnn.delta_decompress(
                    ct, base_flat[e["key"]], self.cfg.zipnn,
                    device_resident=device_resident,
                )
            elif e["kind"] == "delta_prev":
                out[e["key"]] = zipnn.delta_decompress(
                    ct, prev_flat[e["key"]], self.cfg.zipnn,
                    device_resident=device_resident,
                )
            else:
                full_entries.append(e)
                full_cts.append(ct)
        if full_cts:
            # Whole-tree batched restore: one decompress_pytree call groups
            # same-layout leaves into batched device dispatches instead of
            # a dispatch per leaf, and with device_resident=True the
            # device-resolved leaves never bounce through host memory.
            import jax.tree_util as jtu

            arrays = zipnn.decompress_pytree(
                {
                    "treedef": jtu.tree_structure([0] * len(full_cts)),
                    "leaves": full_cts,
                },
                self.cfg.zipnn,
                device_resident=device_resident,
            )
            for e, arr in zip(full_entries, arrays):
                out[e["key"]] = arr
        _cache[step] = out
        return out

    def restore(
        self, step: Optional[int] = None, *, device_resident: bool = False
    ) -> Tuple[int, PyTree]:
        """Newest valid checkpoint ≤ step (or overall). Torn/corrupt saves
        are skipped — the crash-recovery contract.

        ``device_resident=True`` keeps restored leaves on device as
        ``jax.Array``\\ s when the configured decode backend resolves to
        device (see ``zipnn.decompress_array``) — bits identical, zero
        device→host bounce; host-resolved leaves still restore as numpy.
        """
        with tracing.operation("znn.ckpt.restore"):
            candidates = [
                s for s, _, _ in self._scan() if step is None or s <= step
            ]
            for s in reversed(candidates):
                try:
                    return s, _unflatten(
                        self._load_flat(s, device_resident=device_resident)
                    )
                except (IOError, OSError, KeyError):
                    continue
        raise FileNotFoundError(f"no valid checkpoint in {self.cfg.directory}")

    def shard_restore(self, step: Optional[int], mesh, specs: PyTree) -> Tuple[int, PyTree]:
        """Restore + device_put onto an arbitrary mesh (elastic rescale).

        With ``CheckpointConfig.backend='device'|'auto'`` the restore's
        full decode — the device Huffman entropy stage plus the fused
        un-byte-group + inverse rotate + delta XOR back half
        (``core/device_entropy.py`` / ``core/device_unplane.py``) — runs on
        device and leaves stay device-resident straight into the
        ``device_put`` re-shard: only compressed bytes cross host→device
        and nothing bounces back.  Host-resolved configs restore through
        numpy exactly as before.
        """
        from repro.distributed import sharding

        s, tree = self.restore(step, device_resident=True)
        return s, sharding.device_put_tree(tree, mesh, specs)

    # ------------------------------------------------------------- retention

    def _gc(self) -> None:
        saves = self._scan()
        bases = [s for s, k, _ in saves if k == "base"]
        if len(bases) <= self.cfg.keep_bases:
            return
        cutoff = bases[-self.cfg.keep_bases]
        for s, kind, base in saves:
            if s < cutoff:
                path = os.path.join(self.cfg.directory, f"step_{s}")
                for root, _, files in os.walk(path, topdown=False):
                    for fn in files:
                        os.unlink(os.path.join(root, fn))
                    os.rmdir(root)

    # --------------------------------------------------------------- metrics

    def stats(self) -> List[Dict[str, Any]]:
        out = []
        for s, kind, base in self._scan():
            with open(
                os.path.join(self.cfg.directory, f"step_{s}", "manifest.json")
            ) as f:
                m = json.load(f)
            out.append(
                {
                    "step": s,
                    "kind": kind,
                    "base_step": base,
                    "ratio_pct": 100.0 * m["comp_bytes"] / max(m["raw_bytes"], 1),
                }
            )
        return out
