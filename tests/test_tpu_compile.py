"""The codec kernels compile for a TPU v5e at the codec's real sizes.

Interpret mode checks bytes, not whether the chip's compiler accepts a
kernel: block shapes that break the (8, 128) tiling, rank-1 blocks that are
not whole tiles, and fast-memory overruns pass every interpret-mode test and
are refused only here.  Each test lowers one kernel for a *described*
v5e (no chip attached; nothing runs) at a 4096 x 14336 leaf, the default
plane chunks (131,072 bytes for bf16, 65,536 for fp32) and 8 chunks per
dispatch, and checks that the program holds a Mosaic kernel.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library, and every test
worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import bitpack, fused_plane, fused_unplane, huffdecode

LEAF = (4096, 14336)
CHUNK_BYTES = {2: 1 << 17, 4: 1 << 16}   # ZipNNConfig.chunk_param_bytes // itemsize
DISPATCH_CHUNKS = 8
UINT = {2: jnp.uint16, 4: jnp.uint32}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # The compiler would log under the temp dir; these compiles cannot be
    # read back from the persistent cache without a chip, so keep them out.
    old_log = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:               # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        compilation_cache.reset_cache()
        if old_log is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = old_log


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize(
    "itemsize, delta", [(2, False), (4, False), (2, True)],
    ids=["bf16", "fp32", "bf16-delta"],
)
def test_plane_producer_compiles(one_chip, itemsize, delta):
    rows = LEAF[0] * LEAF[1] // 128
    x = jax.ShapeDtypeStruct((rows, 128), UINT[itemsize], sharding=one_chip)

    def produce(x, base):
        return fused_plane.plane_producer(
            x, base, itemsize=itemsize, chunk_elems=CHUNK_BYTES[itemsize],
            interpret=False,
        )

    _compile(produce, x, x if delta else None)


@pytest.mark.parametrize("itemsize", [2, 4], ids=["bf16", "fp32"])
def test_plane_consumer_compiles(one_chip, itemsize):
    rows = LEAF[0] * LEAF[1] // 128
    plane = jax.ShapeDtypeStruct((rows, 128), jnp.uint8, sharding=one_chip)

    def consume(*planes):
        return fused_unplane.plane_consumer(
            planes, itemsize=itemsize, interpret=False
        )

    _compile(consume, *([plane] * itemsize))


def test_bitpack_compiles(one_chip):
    cb = CHUNK_BYTES[2]

    def pack(syms, pids, lens, codes):
        return bitpack.bitpack_encode_chunks_multi(
            syms, pids, lens, codes, chunk_syms=cb, interpret=False
        )

    _compile(
        pack,
        jax.ShapeDtypeStruct((DISPATCH_CHUNKS * cb,), jnp.uint8, sharding=one_chip),
        jax.ShapeDtypeStruct((DISPATCH_CHUNKS,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((2, 256), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((2, 256), jnp.int32, sharding=one_chip),
    )


@pytest.mark.parametrize("itemsize, lut_bits", [(2, 9), (2, 15), (4, 15)])
def test_huffdecode_compiles(one_chip, itemsize, lut_bits):
    """A narrow LUT, and the widest (MAXL-bit codes) for every plane of a
    bf16 / fp32 leaf: the largest SMEM footprint the decoder can ask for."""
    cb = CHUNK_BYTES[itemsize]

    def decode(words, pids, counts, luts):
        return huffdecode.huffdecode_chunks_multi(
            words, pids, counts, luts, chunk_bytes=cb, interpret=False
        )

    _compile(
        decode,
        jax.ShapeDtypeStruct((DISPATCH_CHUNKS * cb // 4,), jnp.uint32, sharding=one_chip),
        jax.ShapeDtypeStruct((DISPATCH_CHUNKS,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((DISPATCH_CHUNKS,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((itemsize, 1 << lut_bits), jnp.int32, sharding=one_chip),
    )


@pytest.mark.parametrize("itemsize", [2, 4], ids=["bf16", "fp32"])
def test_plane_assembly_compiles(one_chip, itemsize):
    """The device plane assembly of a whole leaf: half its chunks in a
    Huffman launch window, half in host-decoded rows, every plane gathered
    from both and cut to its length."""
    from repro.core import device_entropy

    cb = CHUNK_BYTES[itemsize]
    n = LEAF[0] * LEAF[1]                     # bytes in each plane
    rows = itemsize * -(-n // cb)
    sources = tuple(
        jax.ShapeDtypeStruct((half, cb), jnp.uint8, sharding=one_chip)
        for half in (rows // 2, rows - rows // 2)
    )
    index = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip)
    compiled = (
        device_entropy._assembler()
        .lower(sources, index, (n,) * itemsize)
        .compile()
    )
    outs = compiled.out_info
    assert [o.shape for o in outs] == [(n,)] * itemsize
    assert all(o.dtype == jnp.uint8 for o in outs)
