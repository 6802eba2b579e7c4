"""ZipNN public API: lossless compression tailored to model weights.

Pipeline per tensor (paper §3):

    raw bytes ──rotate+byte-group──▶ planes ──chunk──▶ probe ──▶ entropy code
                                     │                     │
                                     └ plane 0 = exponent  └ STORE/ZERO/HUFF/ZLIB

Entry points:
  * :func:`compress_array` / :func:`decompress_array` — one numpy/JAX array.
  * :func:`compress_bytes` / :func:`decompress_bytes` — raw streams with an
    explicit dtype interpretation.
  * :func:`compress_pytree` / :func:`decompress_pytree` — whole model /
    optimizer states; returns a manifest + per-leaf blobs.
  * :func:`delta_compress` / :func:`delta_decompress` — §4.2 XOR deltas.
  * :func:`compress_file` / :func:`decompress_file` (re-exported from
    :mod:`.engine`) — bounded-memory streaming over files.

Every entry point takes a ``threads=`` override (default: the config's
``threads`` field).  With N > 1, (plane, chunk) work items fan out across a
shared thread pool (see :mod:`.engine`); output bytes are identical to the
serial path for any thread count.

Every *compression* entry point additionally takes a ``backend=`` override
(default: the config's ``plane_backend``): ``"host"`` runs the rotate/
byte-group/probe front half in numpy, ``"device"`` runs it as one fused
Pallas dispatch with a single device→host transfer of planed buffers +
probe stats (see :mod:`.device_plane`), ``"auto"`` picks device only for
accelerator-resident leaves.  Blobs are byte-identical across backends ×
thread counts — both knobs change wall-clock only.

``backend="device"`` now covers the **entropy stage** too: (plane, chunk)
work items planned as ``HUFF`` bit-pack on device in one fused dispatch
(see :mod:`.device_entropy`) instead of the vectorized host encoder, with
the canonical table still built on host and the expansion guard / container
framing unchanged.  The ``entropy_backend=`` override (also a
``ZipNNConfig`` field) decouples the two stages for mixed mode — e.g.
``backend="host", entropy_backend="device"`` probes on host but packs bits
on device.  The device entropy stage engages only for the canonical
``huffman`` coder; the ``hufflib`` (zlib) coder silently stays host-side.

Every *decompression* entry point takes the same ``backend=`` knob for the
decode back half (see :mod:`.device_unplane`): after the entropy stage
rebuilds the byte-group planes, ``"device"`` uploads them once and runs
un-byte-group + inverse rotate + inverse XOR-delta as one fused Pallas
dispatch; ``"auto"`` picks device only when an accelerator is attached (or
the delta base already lives on one).  Decoded bytes are bit-identical
across backends × thread counts — asserted by ``tests/parity.py``.

The ``entropy_backend=`` knob covers decode too: ``"device"`` decodes the
container's ``HUFF`` chunks in one fused Pallas dispatch (see
:mod:`.device_entropy` / :mod:`repro.kernels.huffdecode`) — only the
*compressed* payload crosses host→device, and when the plane backend is
also device the kernel-decoded symbols feed the fused consumer in place
(no uncompressed-plane upload).  Decode keys off the container, not the
config's coder: any blob with ``HUFF`` chunks qualifies, other blobs
silently stay host-side.  ``decompress_array`` / ``delta_decompress``
additionally take ``device_resident=True`` to keep the restored leaf on
device as a ``jax.Array`` (zero device→host bounce — the
``shard_restore`` path).  Decoded bits are identical across
``backend`` × ``entropy_backend`` × ``threads`` everywhere.

All of the above knobs also ride a single frozen bag: every entry point
takes ``options=CodecOptions(threads=..., backend=..., entropy_backend=...,
device_resident=...)`` (see :mod:`.options`), and :class:`ZipNNSession`
binds a config + options once for the whole surface.  The per-knob kwargs
keep working through a deprecation shim — an explicit legacy kwarg
overrides the options field and warns — and bytes are identical either
way.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import bitlayout, codec, container, engine, tracing
from .engine import (             # noqa: F401  (re-exported streaming API)
    CompressWriter,
    DecompressReader,
    compress_file,
    decompress_file,
)
from .options import (            # noqa: F401  (re-exported options API)
    CodecOptions,
    ZipNNSession,
    resolve_options as _resolve_options,
)

__all__ = [
    "ZipNNConfig",
    "CodecOptions",
    "ZipNNSession",
    "CompressedTensor",
    "ArrayFeed",
    "build_array_feed",
    "compress_array",
    "decompress_array",
    "compress_bytes",
    "decompress_bytes",
    "compress_pytree",
    "decompress_pytree",
    "delta_compress",
    "delta_compress_batched",
    "delta_decompress",
    "compress_file",
    "decompress_file",
    "CompressWriter",
    "DecompressReader",
    "compressed_size",
    "ratio",
]


@dataclasses.dataclass
class ZipNNConfig:
    """User-facing knobs (defaults = paper defaults)."""

    chunk_param_bytes: int = 1 << 18     # 256 KiB of parameters per chunk
    # Entropy backend. Both are Huffman-only coders (the ZipNN algorithm);
    # 'hufflib' uses zlib's C Huffman (as the paper used zstd's C Huffman)
    # for production speed, 'huffman' is our from-scratch vectorized
    # canonical coder (algorithm reference + Pallas-kernel oracle).
    backend: str = "hufflib"
    incompressible: float = 0.98
    skip_chunks: int = 8
    zlib_level: int = 6
    # Parallelism: 0/1 = serial, N > 1 = N pool workers, -1 = all cores
    # (the reference implementation's ``max_threads``).  Blob bytes are
    # identical for every setting.
    threads: int = 0
    # Plane-producer backend: 'host' (numpy rotate/split/probe), 'device'
    # (fused Pallas dispatch + single transfer, host fallback when the
    # layout/chunk combination is unsupported), or 'auto' (device only for
    # accelerator-resident jax arrays).  Blob bytes are identical for every
    # setting — see core/device_plane.py.
    plane_backend: str = "host"
    # Entropy-stage backend: None follows plane_backend; 'host' forces the
    # vectorized host Huffman encoder; 'device' bit-packs HUFF chunks as one
    # fused Pallas dispatch (canonical 'huffman' coder only — 'hufflib'
    # always encodes host-side); 'auto' device only for accelerator-resident
    # leaves.  Blob bytes are identical for every setting — see
    # core/device_entropy.py.
    entropy_backend: Optional[str] = None

    def plane_params(self, itemsize: int, delta: bool = False) -> codec.CodecParams:
        return codec.CodecParams(
            chunk_bytes=max(1, self.chunk_param_bytes // max(itemsize, 1)),
            incompressible=self.incompressible,
            skip_chunks=self.skip_chunks,
            delta_mode=delta,
            backend=self.backend,
            zlib_level=self.zlib_level,
        )


DEFAULT = ZipNNConfig()


@dataclasses.dataclass
class CompressedTensor:
    """A compressed leaf: blob + enough info to restore dtype/shape."""

    blob: bytes
    dtype: str
    shape: Tuple[int, ...]

    @property
    def nbytes(self) -> int:
        return len(self.blob)


# ---------------------------------------------------------------------------
# byte-stream compression
# ---------------------------------------------------------------------------

def _resolve_backend(
    backend: Optional[str],
    config: ZipNNConfig,
    layout: bitlayout.BitLayout,
    params: codec.CodecParams,
    leaf: Any = None,
) -> str:
    """Collapse the backend knob to 'host' or 'device' for one leaf."""
    requested = config.plane_backend if backend is None else backend
    if requested == "host":
        return "host"
    from . import device_plane  # lazy: pulls in jax/Pallas

    return device_plane.resolve(requested, layout, params, leaf=leaf)


def _resolve_entropy_backend(
    entropy_backend: Optional[str],
    backend: Optional[str],
    config: ZipNNConfig,
    layout: bitlayout.BitLayout,
    params: codec.CodecParams,
    leaf: Any = None,
) -> str:
    """Collapse the entropy-backend knob to 'host' or 'device' for one leaf.

    Precedence: explicit ``entropy_backend=`` argument, then the config's
    ``entropy_backend`` field, then the plane ``backend`` request — so
    ``backend="device"`` means plane *and* entropy on device unless the
    entropy knob overrides it (mixed mode).
    """
    requested = entropy_backend
    if requested is None:
        requested = config.entropy_backend
    if requested is None:
        requested = config.plane_backend if backend is None else backend
    if requested == "host":
        return "host"
    from . import device_entropy  # lazy: pulls in jax/Pallas

    return device_entropy.resolve(requested, layout, params, leaf=leaf)


def _entropy_stage(
    planes: Sequence[np.ndarray],
    probes: Sequence[Optional[codec.ProbeStats]],
    layout: bitlayout.BitLayout,
    body_bytes: int,
    rem: Optional[np.ndarray],
    params: codec.CodecParams,
    pool,
    delta: bool,
    entropy: str = "host",
) -> bytes:
    """Shared back half of every compression path: (plane, chunk) entropy
    work items + container packing.  ``planes`` may come from the host
    byte-split or the device plane producer; ``probes`` carry the device
    path's precomputed per-chunk statistics (None ⇒ host probe).

    ``entropy="device"`` routes the planned HUFF chunks of all planes
    through one fused bit-pack dispatch (:mod:`.device_entropy`); blobs are
    byte-identical either way."""
    tables: List[Optional[bytes]] = []
    entries: List[List[codec.ChunkEntry]] = []
    payloads: List[List[bytes]] = []
    if entropy == "device" and planes:
        from . import device_entropy

        entries, payloads, tables = device_entropy.encode_planes(
            planes, probes, params, pool=pool
        )
    else:
        for plane, probe in zip(planes, probes):
            e, p, t = codec.compress_plane(plane, params, pool=pool, probe=probe)
            entries.append(e)
            payloads.append(p)
            tables.append(t)
    blob = container.pack_stream(
        layout.name, body_bytes, params.chunk_bytes, tables, entries, payloads,
        delta=delta,
    )
    if rem is not None and rem.size:
        blob += b"TAIL" + bytes(rem)
    return blob


def compress_bytes(
    raw: bytes | np.ndarray,
    dtype_name: str,
    config: ZipNNConfig = DEFAULT,
    *,
    delta: bool = False,
    threads: Optional[int] = None,
    backend: Optional[str] = None,
    entropy_backend: Optional[str] = None,
    options: Optional[CodecOptions] = None,
) -> bytes:
    """Compress a raw little-endian byte stream interpreted as ``dtype_name``."""
    opts = _resolve_options(
        options, threads=threads, backend=backend, entropy_backend=entropy_backend
    )
    threads, backend, entropy_backend = (
        opts.threads, opts.backend, opts.entropy_backend,
    )
    buf = np.frombuffer(raw, dtype=np.uint8) if isinstance(raw, (bytes, memoryview, bytearray)) else np.ascontiguousarray(raw, dtype=np.uint8)
    layout = bitlayout.layout_for(dtype_name)
    tail = buf.size % layout.align
    body, rem = (buf[: buf.size - tail], buf[buf.size - tail :]) if tail else (buf, None)
    pool = engine.get_pool(config.threads if threads is None else threads)
    params = config.plane_params(layout.itemsize, delta)
    if body.size and _resolve_backend(backend, config, layout, params) == "device":
        from . import device_plane

        planes, probes = device_plane.produce_planes(body, layout, params)
    else:
        planes = bitlayout.to_planes(body, layout, pool=pool)
        probes = [None] * len(planes)
    entropy = (
        _resolve_entropy_backend(entropy_backend, backend, config, layout, params)
        if body.size
        else "host"
    )
    return _entropy_stage(
        planes, probes, layout, body.size, rem, params, pool, delta,
        entropy=entropy,
    )


def _resolve_decode_backend(
    backend: Optional[str],
    config: ZipNNConfig,
    layout: bitlayout.BitLayout,
    base: Any = None,
) -> str:
    """Collapse the decode-backend knob to 'host' or 'device'."""
    requested = config.plane_backend if backend is None else backend
    if requested == "host":
        return "host"
    from . import device_unplane  # lazy: pulls in jax/Pallas

    return device_unplane.resolve(requested, layout, base=base)


def _resolve_decode_entropy(
    entropy_backend: Optional[str],
    backend: Optional[str],
    config: ZipNNConfig,
    chunk_bytes: int,
    base: Any = None,
) -> str:
    """Collapse the decode-side entropy knob to 'host' or 'device'.

    Same precedence as the encode side (:func:`_resolve_entropy_backend`):
    explicit argument, then the config field, then the plane ``backend``
    request.  The envelope differs — decode keys off the *container's*
    chunk geometry, not the config's coder, and ``auto`` keys off
    accelerator attachment (or a device-resident delta base) — see
    :func:`repro.core.device_entropy.resolve_decode`.
    """
    requested = entropy_backend
    if requested is None:
        requested = config.entropy_backend
    if requested is None:
        requested = config.plane_backend if backend is None else backend
    if requested == "host":
        return "host"
    from . import device_entropy  # lazy: pulls in jax/Pallas

    return device_entropy.resolve_decode(requested, chunk_bytes, base=base)


def _entropy_decode(
    blob: bytes,
    config: ZipNNConfig,
    pool,
    entropy_backend: Optional[str] = None,
    backend: Optional[str] = None,
    base: Any = None,
    device_resident: Optional[bool] = None,
) -> Tuple[bitlayout.BitLayout, List[Any], bytes]:
    """Shared front half of every decompression path: parse the container
    and entropy-decode every (plane, chunk) payload (CRC-verified work
    items fanned across ``pool``).  Returns ``(layout, planes, tail)`` —
    the byte-group planes still await un-grouping by either backend.

    ``entropy_backend``/``backend`` are the unresolved decode knobs: the
    fused device decoder (:func:`repro.core.device_entropy.decode_planes`)
    engages only when the parsed stream actually has ``HUFF`` chunks and
    the resolution lands on device; everything else (and every fallback)
    decodes through the host work items — bytes identical either way.
    ``device_resident`` asks the device decoder for device-resident plane
    arrays; ``None`` decides from the un-plane backend resolution, so
    kernel-decoded symbols stay on device exactly when the fused consumer
    will eat them in place.
    """
    with tracing.span("znn.codec.parse"):
        meta, mv = container.unpack_stream(blob)
        layout = bitlayout.layout_by_name(meta.layout_name)
        params = codec.CodecParams(
            chunk_bytes=meta.chunk_bytes, backend=config.backend
        )
        payload_lists = [
            [
                container.payload_view(meta, mv, p, c)
                for c in range(len(meta.entries[p]))
            ]
            for p in range(meta.n_planes)
        ]
    use_device = any(
        e.method == codec.Method.HUFF for pe in meta.entries for e in pe
    ) and _resolve_decode_entropy(
        entropy_backend, backend, config, meta.chunk_bytes, base=base
    ) == "device"
    if use_device:
        from . import device_entropy

        if device_resident is None:
            device_resident = (
                _resolve_decode_backend(backend, config, layout, base=base)
                == "device"
            )
        planes = device_entropy.decode_planes(
            meta.entries, payload_lists, meta.tables, params,
            pool=pool, device_resident=device_resident,
        )
    else:
        planes = [
            codec.decompress_plane(
                meta.entries[p], payload_lists[p], meta.tables[p], params,
                pool=pool,
            )
            for p in range(meta.n_planes)
        ]
    # trailing unaligned bytes
    end = meta.payload_base + sum(
        e.comp_len for pe in meta.entries for e in pe
    )
    tail = blob[end:]
    return layout, planes, (tail[4:] if tail[:4] == b"TAIL" else b"")


def decompress_bytes(
    blob: bytes,
    config: ZipNNConfig = DEFAULT,
    *,
    threads: Optional[int] = None,
    backend: Optional[str] = None,
    entropy_backend: Optional[str] = None,
    options: Optional[CodecOptions] = None,
) -> bytes:
    """Decompress one ZNN1 blob back to its raw little-endian byte stream."""
    opts = _resolve_options(
        options, threads=threads, backend=backend, entropy_backend=entropy_backend
    )
    threads, backend, entropy_backend = (
        opts.threads, opts.backend, opts.entropy_backend,
    )
    pool = engine.get_pool(config.threads if threads is None else threads)
    layout, planes, tail = _entropy_decode(
        blob, config, pool, entropy_backend=entropy_backend, backend=backend
    )
    if (
        planes
        and planes[0].size
        and _resolve_decode_backend(backend, config, layout) == "device"
    ):
        from . import device_unplane

        body = device_unplane.consume_planes(planes, layout)
    else:
        body = bitlayout.from_planes(tuple(planes), layout, pool=pool)
    return body.tobytes() + tail


# ---------------------------------------------------------------------------
# array / pytree compression
# ---------------------------------------------------------------------------

def _to_numpy(arr: Any) -> np.ndarray:
    if hasattr(arr, "addressable_data"):      # jax.Array → host
        arr = np.asarray(arr)
    shape = np.shape(arr)
    # ascontiguousarray promotes 0-d → 1-d; restore the true shape
    return np.ascontiguousarray(arr).reshape(shape)


def _leaf_layout(arr: Any) -> Optional[bitlayout.BitLayout]:
    """Layout for an array-like leaf, or None when it has no ZipNN layout."""
    name = getattr(getattr(arr, "dtype", None), "name", None)
    return bitlayout.LAYOUTS.get(name) if name else None


def _leaf_nbytes(arr: Any) -> int:
    """Raw byte size without forcing a device→host transfer."""
    dt = getattr(arr, "dtype", None)
    if dt is not None:
        return int(np.size(arr)) * np.dtype(dt).itemsize
    return int(np.asarray(arr).nbytes)


def compress_array(
    arr: Any,
    config: ZipNNConfig = DEFAULT,
    *,
    threads: Optional[int] = None,
    backend: Optional[str] = None,
    entropy_backend: Optional[str] = None,
    options: Optional[CodecOptions] = None,
) -> CompressedTensor:
    opts = _resolve_options(
        options, threads=threads, backend=backend, entropy_backend=entropy_backend
    )
    threads, backend, entropy_backend = (
        opts.threads, opts.backend, opts.entropy_backend,
    )
    layout = _leaf_layout(arr)
    if layout is not None and np.size(arr):
        params = config.plane_params(layout.itemsize)
        if _resolve_backend(backend, config, layout, params, leaf=arr) == "device":
            from . import device_plane

            # Device leaves are planed in place: the only device→host
            # transfer is the planed uint8 buffers + probe stats.
            planes, probes = device_plane.produce_planes(arr, layout, params)
            pool = engine.get_pool(config.threads if threads is None else threads)
            n_bytes = int(np.size(arr)) * layout.itemsize
            entropy = _resolve_entropy_backend(
                entropy_backend, backend, config, layout, params, leaf=arr
            )
            blob = _entropy_stage(
                planes, probes, layout, n_bytes, None, params, pool, False,
                entropy=entropy,
            )
            name = arr.dtype.name
            return CompressedTensor(blob, name, tuple(np.shape(arr)))
        # Entropy may still go device (mixed mode): resolve it against the
        # leaf's accelerator residence before the plane request collapses.
        entropy_backend = _resolve_entropy_backend(
            entropy_backend, backend, config, layout, params, leaf=arr
        )
        backend = "host"             # resolved once; don't re-resolve below
    a = _to_numpy(arr)
    blob = compress_bytes(
        a.reshape(-1).view(np.uint8), a.dtype.name, config,
        options=CodecOptions(
            threads=threads, backend=backend, entropy_backend=entropy_backend
        ),
    )
    return CompressedTensor(blob, a.dtype.name, tuple(a.shape))


def _np_dtype(name: str) -> np.dtype:
    import ml_dtypes  # registered with numpy by jax

    return np.dtype(getattr(ml_dtypes, name, name))


def _decompress_array_device(
    ct: CompressedTensor,
    config: ZipNNConfig,
    threads: Optional[int],
    backend: Optional[str],
    entropy_backend: Optional[str],
) -> Optional[Any]:
    """Zero-bounce restore of one leaf: decode on device, stay on device.

    Returns a device-resident ``jax.Array`` (real dtype, real shape) built
    by bitcasting the fused consumer's element output in place — no
    ``device_get``, and with the device entropy stage only the *compressed*
    payload crosses host→device.  Returns ``None`` whenever any part of
    the leaf rides the host path (unsupported layout, empty leaf, tail
    bytes, host-resolved plane backend) — the caller falls back to the
    ordinary numpy restore.
    """
    layout = bitlayout.LAYOUTS.get(ct.dtype)
    if layout is None or not int(np.prod(ct.shape, dtype=np.int64)):
        return None
    if _resolve_decode_backend(backend, config, layout) != "device":
        return None
    pool = engine.get_pool(config.threads if threads is None else threads)
    blob_layout, planes, tail = _entropy_decode(
        ct.blob, config, pool,
        entropy_backend=entropy_backend, backend=backend,
        device_resident=True,
    )
    if tail or blob_layout.name != layout.name or not planes or not planes[0].size:
        return None                        # edge cases ride the host path
    import jax
    import jax.numpy as jnp

    from . import device_unplane

    elems = device_unplane.consume_planes(
        planes, layout, device_resident=True
    )
    return jax.lax.bitcast_convert_type(
        elems, jnp.dtype(_np_dtype(ct.dtype))
    ).reshape(ct.shape)


def decompress_array(
    ct: CompressedTensor,
    config: ZipNNConfig = DEFAULT,
    *,
    threads: Optional[int] = None,
    backend: Optional[str] = None,
    entropy_backend: Optional[str] = None,
    device_resident: Optional[bool] = None,
    options: Optional[CodecOptions] = None,
) -> Any:
    """Decompress one leaf back to its dtype/shape.

    Returns numpy by default.  ``device_resident=True`` (kwarg or options
    field) keeps the restored leaf on device as a ``jax.Array`` when the
    decode backend resolves to device (see :func:`_decompress_array_device`)
    — bits identical, zero device→host bounce; host-resolved leaves still
    come back as numpy.
    """
    opts = _resolve_options(
        options, threads=threads, backend=backend,
        entropy_backend=entropy_backend, device_resident=device_resident,
    )
    if opts.device_resident:
        out = _decompress_array_device(
            ct, config, opts.threads, opts.backend, opts.entropy_backend
        )
        if out is not None:
            return out
    raw = decompress_bytes(
        ct.blob, config,
        options=CodecOptions(
            threads=opts.threads, backend=opts.backend,
            entropy_backend=opts.entropy_backend,
        ),
    )
    return np.frombuffer(raw, dtype=_np_dtype(ct.dtype)).reshape(ct.shape).copy()


# ---------------------------------------------------------------------------
# device-resident payload feed (per-leaf)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ArrayFeed:
    """One leaf's device-resident decode plan: blob parsed once, payloads
    resident in device memory, :meth:`decode` re-runs the fused decoder from
    those buffers every call — zero host→device payload traffic per decode
    (see :class:`repro.core.device_entropy.PayloadFeed`).

    Build via :func:`build_array_feed`; residency changes wall-clock and
    memory only — decoded arrays are bit-identical to
    ``decompress_array(ct, device_resident=True)``.
    """

    dtype: str
    shape: Tuple[int, ...]
    _feed: Any
    _layout: bitlayout.BitLayout

    @property
    def device_bytes(self) -> int:
        """Resident HBM footprint of the compressed payload buffers."""
        return self._feed.device_bytes

    @property
    def dispatches(self) -> int:
        """Eager device ops one :meth:`decode` issues: the payload feed's,
        the plane consumer's, and the bitcast and reshape to the leaf."""
        from . import device_unplane

        n = int(np.prod(self.shape, dtype=np.int64))
        return (
            self._feed.dispatches
            + device_unplane.resident_dispatches(n, self._layout)
            + 1
            + (tuple(self.shape) != (n,))
        )

    def decode(self) -> Any:
        """The restored leaf as a device-resident ``jax.Array``."""
        import jax
        import jax.numpy as jnp

        from . import device_unplane

        planes = self._feed.decode()
        elems = device_unplane.consume_planes(
            planes, self._layout, device_resident=True
        )
        out = jax.lax.bitcast_convert_type(
            elems, jnp.dtype(_np_dtype(self.dtype))
        ).reshape(self.shape)
        tracing.count("feed_dispatches", self.dispatches - self._feed.dispatches)
        return out


def build_array_feed(
    ct: CompressedTensor,
    config: ZipNNConfig = DEFAULT,
    *,
    options: Optional[CodecOptions] = None,
) -> Optional[ArrayFeed]:
    """Parse one leaf's blob into a device-resident :class:`ArrayFeed`.

    The container parse, CRC + cursor integrity checks, word packing and
    payload upload all happen **here, once**; every later
    :meth:`ArrayFeed.decode` drives the fused decoder + consumer straight
    from device memory.  Returns ``None`` when the leaf cannot ride the
    device path end to end (unsupported layout, empty leaf, tail bytes,
    chunk geometry the kernels cannot decode, or no jax) — callers fall
    back to the per-call decode, which is always available.

    ``options`` carries the thread knob for the build-time host work items
    (non-HUFF chunk decode + CRC fan-out); it cannot change decoded bits.
    """
    opts = _resolve_options(options)
    layout = bitlayout.LAYOUTS.get(ct.dtype)
    if layout is None or not int(np.prod(ct.shape, dtype=np.int64)):
        return None
    from . import device_entropy, device_unplane

    if not device_unplane.supports(layout):
        return None
    meta, mv = container.unpack_stream(ct.blob)
    if meta.layout_name != layout.name:
        return None
    if not device_entropy.supports_decode(meta.chunk_bytes):
        return None
    end = meta.payload_base + sum(e.comp_len for pe in meta.entries for e in pe)
    if ct.blob[end:]:
        return None                            # tail bytes ride the host path
    if not meta.entries or not sum(e.raw_len for e in meta.entries[0]):
        return None
    payload_lists = [
        [
            container.payload_view(meta, mv, p, c)
            for c in range(len(meta.entries[p]))
        ]
        for p in range(meta.n_planes)
    ]
    params = codec.CodecParams(chunk_bytes=meta.chunk_bytes, backend=config.backend)
    pool = engine.get_pool(config.threads if opts.threads is None else opts.threads)
    feed = device_entropy.PayloadFeed(
        meta.entries, payload_lists, meta.tables, params, pool=pool
    )
    return ArrayFeed(ct.dtype, tuple(ct.shape), feed, layout)


def compress_pytree(
    tree: Any,
    config: ZipNNConfig = DEFAULT,
    *,
    threads: Optional[int] = None,
    backend: Optional[str] = None,
    entropy_backend: Optional[str] = None,
    options: Optional[CodecOptions] = None,
) -> Dict[str, Any]:
    """Compress every leaf of a pytree. Returns a manifest dict.

    Chunk-level parallelism applies within each leaf; leaves are walked in
    order so the manifest layout is deterministic.

    With the device backend, same-dtype leaves are packed into **batched
    multi-leaf dispatches** (see :mod:`.device_plane`): one kernel launch +
    one transfer covers many small tensors, so per-leaf dispatch overhead
    does not dominate real model trees.  Blobs per leaf are identical to
    compressing each leaf alone on either backend.
    """
    import jax

    opts = _resolve_options(
        options, threads=threads, backend=backend, entropy_backend=entropy_backend
    )
    threads, backend, entropy_backend = (
        opts.threads, opts.backend, opts.entropy_backend,
    )
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    comp: List[Optional[CompressedTensor]] = [None] * len(leaves)

    requested = config.plane_backend if backend is None else backend
    if requested != "host" and leaves:
        from . import device_plane

        groups: Dict[str, List[int]] = {}
        for i, leaf in enumerate(leaves):
            layout = _leaf_layout(leaf)
            if layout is None or not np.size(leaf):
                continue
            params = config.plane_params(layout.itemsize)
            if device_plane.resolve(requested, layout, params, leaf=leaf) == "device":
                groups.setdefault(leaf.dtype.name, []).append(i)
        pool = engine.get_pool(config.threads if threads is None else threads)
        for name, idxs in groups.items():
            layout = bitlayout.LAYOUTS[name]
            params = config.plane_params(layout.itemsize)
            produced = device_plane.produce_planes_batched(
                [leaves[i] for i in idxs], layout, params
            )
            for i, (planes, probes) in zip(idxs, produced):
                n_bytes = int(np.size(leaves[i])) * layout.itemsize
                entropy = _resolve_entropy_backend(
                    entropy_backend, backend, config, layout, params,
                    leaf=leaves[i],
                )
                blob = _entropy_stage(
                    planes, probes, layout, n_bytes, None, params, pool, False,
                    entropy=entropy,
                )
                comp[i] = CompressedTensor(blob, name, tuple(np.shape(leaves[i])))

    for i, leaf in enumerate(leaves):
        if comp[i] is None:
            # The plane path is host for these leaves, but a 'device'/'auto'
            # request still covers their entropy stage (mixed mode).
            comp[i] = compress_array(
                leaf, config,
                options=CodecOptions(
                    threads=threads, backend="host",
                    entropy_backend=(
                        entropy_backend if entropy_backend is not None else backend
                    ),
                ),
            )
    return {
        "treedef": treedef,
        "leaves": comp,
        "raw_bytes": sum(_leaf_nbytes(l) for l in leaves),
        "comp_bytes": sum(c.nbytes for c in comp),
    }


def decompress_pytree(
    manifest: Dict[str, Any],
    config: ZipNNConfig = DEFAULT,
    *,
    threads: Optional[int] = None,
    backend: Optional[str] = None,
    entropy_backend: Optional[str] = None,
    device_resident: Optional[bool] = None,
    options: Optional[CodecOptions] = None,
) -> Any:
    """Decompress every leaf of a :func:`compress_pytree` manifest.

    With the device backend, same-layout leaves are decoded through
    **batched multi-leaf dispatches** (see :mod:`.device_unplane`): each
    leaf's planes are entropy-decoded (host chunk work items, or the device
    Huffman decoder kernel under ``entropy_backend``), then one fused
    kernel launch + one transfer reconstruct the whole group.  With the
    device entropy stage the decoded planes are already device-resident,
    so only compressed bytes cross host→device.  Decoded arrays are
    bit-identical to decompressing each leaf alone on any backend combo.

    ``device_resident=True`` keeps leaves whose decode resolves to the
    device backend on device as ``jax.Array``\\ s (bitcast straight from the
    batched consumer's element output — zero device→host bounce); leaves
    that ride the host path still come back as numpy.  The compressed-
    resident serving store (:mod:`repro.serve.compressed`) decodes its ring
    slots through exactly this path.
    """
    import jax
    import jax.numpy as jnp

    opts = _resolve_options(
        options, threads=threads, backend=backend,
        entropy_backend=entropy_backend, device_resident=device_resident,
    )
    threads, backend, entropy_backend, device_resident = (
        opts.threads, opts.backend, opts.entropy_backend, opts.device_resident,
    )
    with tracing.span("znn.codec.decode_tree"):
        cts: List[CompressedTensor] = manifest["leaves"]
        arrays: List[Optional[Any]] = [None] * len(cts)

        requested = config.plane_backend if backend is None else backend
        if requested != "host" and cts:
            from . import device_plane, device_unplane

            pool = engine.get_pool(config.threads if threads is None else threads)
            groups: Dict[str, List[int]] = {}
            for i, ct in enumerate(cts):
                layout = bitlayout.LAYOUTS.get(ct.dtype)
                if (
                    layout is not None
                    and device_unplane.resolve(requested, layout) == "device"
                ):
                    groups.setdefault(layout.name, []).append(i)
            # Entropy-decode and dispatch one MAX_BATCH_BYTES window at a time:
            # peak host memory is one window of planes + the output arrays, not
            # every leaf's planes at once — the O(window) story of the file API
            # applied to tree restores.
            for name, idxs in groups.items():
                layout = bitlayout.layout_by_name(name)
                win_idx: List[int] = []
                win_planes: List[List[np.ndarray]] = []
                acc = 0

                def flush():
                    if device_resident:
                        elems = device_unplane.consume_planes_batched(
                            win_planes, layout, device_resident=True
                        )
                        for i, el in zip(win_idx, elems):
                            arrays[i] = jax.lax.bitcast_convert_type(
                                el, jnp.dtype(_np_dtype(cts[i].dtype))
                            ).reshape(cts[i].shape)
                    else:
                        raws = device_unplane.consume_planes_batched(
                            win_planes, layout
                        )
                        for i, raw in zip(win_idx, raws):
                            arrays[i] = (
                                np.frombuffer(raw.tobytes(), dtype=_np_dtype(cts[i].dtype))
                                .reshape(cts[i].shape)
                                .copy()
                            )
                    win_idx.clear()
                    win_planes.clear()

                for i in idxs:
                    blob_layout, planes, tail = _entropy_decode(
                        cts[i].blob, config, pool,
                        entropy_backend=entropy_backend, backend=backend,
                    )
                    if (
                        tail
                        or blob_layout.name != layout.name
                        or not planes
                        or not planes[0].size
                    ):
                        continue                   # edge cases ride the host path
                    win_idx.append(i)
                    win_planes.append(planes)
                    acc += planes[0].size * layout.itemsize
                    if acc >= device_plane.MAX_BATCH_BYTES:
                        flush()
                        acc = 0
                if win_idx:
                    flush()

        for i, ct in enumerate(cts):
            if arrays[i] is None:
                # Leaves the device batch skipped decode host-planed, but a
                # 'device'/'auto' request still covers their entropy stage.
                arrays[i] = decompress_array(
                    ct, config,
                    options=CodecOptions(
                        threads=threads, backend="host",
                        entropy_backend=(
                            entropy_backend if entropy_backend is not None else backend
                        ),
                        device_resident=device_resident,
                    ),
                )
        return jax.tree_util.tree_unflatten(manifest["treedef"], arrays)


# ---------------------------------------------------------------------------
# delta compression (§4.2)
# ---------------------------------------------------------------------------

def delta_compress(
    new: Any,
    base: Any,
    config: ZipNNConfig = DEFAULT,
    *,
    threads: Optional[int] = None,
    backend: Optional[str] = None,
    entropy_backend: Optional[str] = None,
    options: Optional[CodecOptions] = None,
) -> CompressedTensor:
    """XOR-delta two same-shape tensors and compress the delta stream.

    XOR is used (not subtraction) because it is exactly reversible with no
    extra bits (paper §4.2).  The delta stream is byte-grouped like a normal
    tensor — Fig. 8(b) shows per-byte-group change rates differ, so grouping
    helps deltas too — and the §4.2 Huffman/LZ auto-selection runs per chunk.

    On the device backend the XOR itself is fused into the plane-producer
    dispatch (rotation is a bit permutation, so it commutes with XOR): the
    delta never materializes host-side, only its planes do.
    """
    opts = _resolve_options(
        options, threads=threads, backend=backend, entropy_backend=entropy_backend
    )
    threads, backend, entropy_backend = (
        opts.threads, opts.backend, opts.entropy_backend,
    )
    if np.shape(new) != np.shape(base) or getattr(new, "dtype", None) != getattr(
        base, "dtype", None
    ):
        raise ValueError("delta requires matching shape/dtype")
    layout = _leaf_layout(new)
    if layout is not None and np.size(new):
        params = config.plane_params(layout.itemsize, delta=True)
        if _resolve_backend(backend, config, layout, params, leaf=new) == "device":
            from . import device_plane

            planes, probes = device_plane.produce_planes(
                new, layout, params, base=base
            )
            pool = engine.get_pool(config.threads if threads is None else threads)
            n_bytes = int(np.size(new)) * layout.itemsize
            entropy = _resolve_entropy_backend(
                entropy_backend, backend, config, layout, params, leaf=new
            )
            blob = _entropy_stage(
                planes, probes, layout, n_bytes, None, params, pool, True,
                entropy=entropy,
            )
            return CompressedTensor(blob, new.dtype.name, tuple(np.shape(new)))
        entropy_backend = _resolve_entropy_backend(
            entropy_backend, backend, config, layout, params, leaf=new
        )
        backend = "host"             # resolved once; don't re-resolve below
    a = _to_numpy(new)
    b = _to_numpy(base)
    if a.shape != b.shape or a.dtype != b.dtype:
        raise ValueError("delta requires matching shape/dtype")
    x = np.bitwise_xor(a.reshape(-1).view(np.uint8), b.reshape(-1).view(np.uint8))
    blob = compress_bytes(
        x, a.dtype.name, config, delta=True,
        options=CodecOptions(
            threads=threads, backend=backend, entropy_backend=entropy_backend
        ),
    )
    return CompressedTensor(blob, a.dtype.name, tuple(a.shape))


def delta_compress_batched(
    news: Sequence[Any],
    bases: Sequence[Any],
    config: ZipNNConfig = DEFAULT,
    *,
    threads: Optional[int] = None,
    backend: Optional[str] = None,
    entropy_backend: Optional[str] = None,
    options: Optional[CodecOptions] = None,
) -> List[CompressedTensor]:
    """Delta-compress many ``(new, base)`` pairs; returns blobs in order.

    With the device backend, same-dtype pairs are packed into **batched
    multi-leaf dispatches** through
    :func:`repro.core.device_plane.produce_planes_batched` (``bases=``):
    one fused XOR→rotate+byte-group→probe launch + one transfer covers many
    small tensors — the checkpoint manager's delta-save path.  Blobs per
    pair are identical to calling :func:`delta_compress` one pair at a time
    on either backend.
    """
    opts = _resolve_options(
        options, threads=threads, backend=backend, entropy_backend=entropy_backend
    )
    threads, backend, entropy_backend = (
        opts.threads, opts.backend, opts.entropy_backend,
    )
    if len(news) != len(bases):
        raise ValueError("news and bases must pair 1:1")
    out: List[Optional[CompressedTensor]] = [None] * len(news)

    requested = config.plane_backend if backend is None else backend
    if requested != "host" and news:
        from . import device_plane

        groups: Dict[str, List[int]] = {}
        for i, (a, b) in enumerate(zip(news, bases)):
            layout = _leaf_layout(a)
            if layout is None or not np.size(a):
                continue
            if np.shape(a) != np.shape(b) or getattr(a, "dtype", None) != getattr(
                b, "dtype", None
            ):
                continue                       # host path raises the clean error
            params = config.plane_params(layout.itemsize, delta=True)
            if device_plane.resolve(requested, layout, params, leaf=a) == "device":
                groups.setdefault(a.dtype.name, []).append(i)
        pool = engine.get_pool(config.threads if threads is None else threads)
        for name, idxs in groups.items():
            layout = bitlayout.LAYOUTS[name]
            params = config.plane_params(layout.itemsize, delta=True)
            produced = device_plane.produce_planes_batched(
                [news[i] for i in idxs], layout, params,
                bases=[bases[i] for i in idxs],
            )
            for i, (planes, probes) in zip(idxs, produced):
                n_bytes = int(np.size(news[i])) * layout.itemsize
                entropy = _resolve_entropy_backend(
                    entropy_backend, backend, config, layout, params,
                    leaf=news[i],
                )
                blob = _entropy_stage(
                    planes, probes, layout, n_bytes, None, params, pool, True,
                    entropy=entropy,
                )
                out[i] = CompressedTensor(blob, name, tuple(np.shape(news[i])))

    for i, (a, b) in enumerate(zip(news, bases)):
        if out[i] is None:
            # Pairs the device batch skipped take the host delta path; the
            # entropy stage still follows the request (mixed mode).
            out[i] = delta_compress(
                a, b, config,
                options=CodecOptions(
                    threads=threads, backend="host",
                    entropy_backend=(
                        entropy_backend if entropy_backend is not None else backend
                    ),
                ),
            )
    return out


def delta_decompress(
    ct: CompressedTensor,
    base: Any,
    config: ZipNNConfig = DEFAULT,
    *,
    threads: Optional[int] = None,
    backend: Optional[str] = None,
    entropy_backend: Optional[str] = None,
    device_resident: Optional[bool] = None,
    options: Optional[CodecOptions] = None,
) -> Any:
    """Invert :func:`delta_compress`: decode the delta stream and XOR it
    with ``base``.

    On the device backend the inverse XOR is fused into the plane-consumer
    dispatch (see :mod:`.device_unplane`): the decoded planes upload once
    (or, under the device entropy stage, are already device-resident —
    only compressed bytes cross host→device), un-group + inverse-rotate +
    XOR run on device against the base at its device residence, and only
    the reconstructed tensor bytes come back — the delta stream never
    materializes host-side.  ``device_resident=True`` additionally keeps
    the restored tensor on device as a ``jax.Array`` (zero device→host
    bounce) when the decode backend resolves to device; host-resolved
    decodes still return numpy.
    """
    opts = _resolve_options(
        options, threads=threads, backend=backend,
        entropy_backend=entropy_backend, device_resident=device_resident,
    )
    threads, backend, entropy_backend, device_resident = (
        opts.threads, opts.backend, opts.entropy_backend, opts.device_resident,
    )
    base_dtype = getattr(getattr(base, "dtype", None), "name", None)
    if tuple(ct.shape) != tuple(np.shape(base)) or ct.dtype != base_dtype:
        # Same clean contract as delta_compress: a mismatched base would
        # otherwise surface as an opaque numpy broadcast error (host path)
        # or an undefined kernel-shape failure (device path).
        raise ValueError("delta requires matching shape/dtype")
    layout = bitlayout.LAYOUTS.get(getattr(getattr(base, "dtype", None), "name", ""))
    if (
        layout is not None
        and np.size(base)
        and _resolve_decode_backend(backend, config, layout, base=base) == "device"
    ):
        pool = engine.get_pool(config.threads if threads is None else threads)
        blob_layout, planes, tail = _entropy_decode(
            ct.blob, config, pool,
            entropy_backend=entropy_backend, backend=backend, base=base,
        )
        if (
            not tail
            and blob_layout.name == layout.name
            and planes
            and planes[0].size
        ):
            from . import device_unplane

            if device_resident:
                import jax
                import jax.numpy as jnp

                elems = device_unplane.consume_planes(
                    planes, layout, base=base, device_resident=True
                )
                return jax.lax.bitcast_convert_type(
                    elems, jnp.dtype(_np_dtype(ct.dtype))
                ).reshape(ct.shape)
            raw = device_unplane.consume_planes(planes, layout, base=base)
            return (
                np.frombuffer(raw.tobytes(), dtype=_np_dtype(ct.dtype))
                .reshape(ct.shape)
                .copy()
            )
    b = _to_numpy(base)
    x = np.frombuffer(
        # The delta XOR happens host-side here, so the plane decode is
        # pinned to host; the entropy stage still follows the request.
        decompress_bytes(
            ct.blob, config,
            options=CodecOptions(
                threads=threads, backend="host",
                entropy_backend=(
                    entropy_backend if entropy_backend is not None else backend
                ),
            ),
        ),
        dtype=np.uint8,
    )
    raw = np.bitwise_xor(x, b.reshape(-1).view(np.uint8))
    return np.frombuffer(raw.tobytes(), dtype=_np_dtype(ct.dtype)).reshape(ct.shape).copy()


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def compressed_size(manifest_or_ct: Any) -> int:
    if isinstance(manifest_or_ct, CompressedTensor):
        return manifest_or_ct.nbytes
    return manifest_or_ct["comp_bytes"]


def ratio(raw_bytes: int, comp_bytes: int) -> float:
    """Compressed size in percent — lower is better (paper's metric)."""
    return 100.0 * comp_bytes / max(raw_bytes, 1)
