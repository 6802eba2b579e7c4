"""zipnn-lint self-tests: must-flag / must-pass fixtures per rule family,
plus the repo-clean smoke (``python -m repro.analysis --strict`` exit 0).

Each fixture is an in-memory module analyzed under a *virtual* repo path
(rule scoping is path-prefix based), seeded with exactly one violation —
or its minimally-fixed twin, which must pass.
"""

import os
import subprocess
import sys
import textwrap

import pytest

from repro.analysis import analyze_source
from repro.analysis.base import Project, SourceFile, analyze_project
from repro.analysis import (
    container_spec,
    determinism,
    kernel_contract,
    knobs,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CORE = "src/repro/core/fake_mod.py"
KERN = "src/repro/kernels/fake_kern.py"


def lint(code, rel, families):
    return analyze_source(textwrap.dedent(code), rel, families=families)


def rules_of(violations):
    return {v.rule for v in violations}


# ---------------------------------------------------------------------------
# determinism
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "rule,bad,good",
    [
        (
            "det-wallclock",
            "import time\nstamp = time.time()\n",
            "import time\nstamp = time.perf_counter()\n",
        ),
        (
            "det-random",
            "import os\nnonce = os.urandom(16)\n",
            "import zlib\nnonce = zlib.crc32(b'seed')\n",
        ),
        (
            "det-random",
            "import random\nx = random.random()\n",
            "x = 0.5\n",
        ),
        (
            "det-hash",
            "key = hash('plane0')\n",
            "import zlib\nkey = zlib.crc32(b'plane0')\n",
        ),
        (
            "det-set-order",
            "out = []\nfor p in {'exp', 'frac'}:\n    out.append(p)\n",
            "out = []\nfor p in sorted({'exp', 'frac'}):\n    out.append(p)\n",
        ),
        (
            "det-set-order",
            "planes = list(set(['a', 'b']))\n",
            "planes = sorted(set(['a', 'b']))\n",
        ),
        (
            "det-id-key",
            "def f(cache, arr):\n    cache[id(arr)] = 1\n",
            "def f(cache, key, arr):\n    cache[key] = 1\n",
        ),
        (
            "det-fs-order",
            "import os\ndef f(d):\n    return [n for n in os.listdir(d)]\n",
            "import os\ndef f(d):\n    return [n for n in sorted(os.listdir(d))]\n",
        ),
        (
            "det-float-size",
            "def f(buf, n):\n    return buf[: n / 2]\n",
            "def f(buf, n):\n    return buf[: n // 2]\n",
        ),
        (
            "det-float-size",
            "def f(n):\n    return bytearray(n / 4)\n",
            "def f(n):\n    return bytearray(n // 4)\n",
        ),
    ],
)
def test_determinism_fixtures(rule, bad, good):
    assert rule in rules_of(lint(bad, CORE, [determinism]))
    assert not lint(good, CORE, [determinism])


def test_determinism_scope_excludes_benchmarks():
    code = "import time\nstamp = time.time()\n"
    assert not lint(code, "benchmarks/fake_bench.py", [determinism])


def test_perf_counter_allowed_everywhere():
    code = "import time\nt0 = time.perf_counter()\ndt = time.monotonic()\n"
    assert not lint(code, CORE, [determinism])


# ---------------------------------------------------------------------------
# knobs
# ---------------------------------------------------------------------------

KNOB_SCOPE = "src/repro/checkpoint/fake_knobs.py"  # in scope, not on SURFACE

_KNOB_BASE = """
    def inner(data, threads=None, backend=None):
        return data

    def outer(data, threads=None, backend=None):
        return inner(data{fwd})
"""


def test_knob_dropped():
    v = lint(_KNOB_BASE.format(fwd=""), KNOB_SCOPE, [knobs])
    assert {x.rule for x in v} >= {"knob-dropped"}
    # both threads and backend dropped
    assert sum(1 for x in v if x.rule == "knob-dropped") == 2


def test_knob_forwarded_ok():
    ok = _KNOB_BASE.format(fwd=", threads=threads, backend=backend")
    assert not lint(ok, KNOB_SCOPE, [knobs])


def test_knob_forwarded_positionally_ok():
    ok = _KNOB_BASE.format(fwd=", threads, backend")
    assert not lint(ok, KNOB_SCOPE, [knobs])


def test_knob_kwargs_forwarding_ok():
    code = """
    def inner(data, threads=None, backend=None):
        return data

    def outer(data, **kw):
        return inner(data, **kw)
    """
    assert not lint(code, KNOB_SCOPE, [knobs])


def test_knob_redefault():
    bad = _KNOB_BASE.format(fwd=", threads=threads, backend='host'")
    v = lint(bad, KNOB_SCOPE, [knobs])
    assert rules_of(v) == {"knob-redefault"}


def test_knob_none_is_not_redefault():
    # explicit None means "derive from config" on this surface
    ok = _KNOB_BASE.format(fwd=", threads=threads, backend=None")
    assert not lint(ok, KNOB_SCOPE, [knobs])


def test_knob_config_carried_caller_exempt():
    code = """
    def inner(data, threads=None, backend=None):
        return data

    def outer(data, config):
        return inner(data)
    """
    assert not lint(code, KNOB_SCOPE, [knobs])


def test_knob_instance_carried_method():
    code = """
    def inner(data, backend=None):
        return data

    class Writer:
        def __init__(self, backend=None):
            self._backend = backend

        def run(self, data):
            return inner(data)
    """
    v = lint(code, KNOB_SCOPE, [knobs])
    assert rules_of(v) == {"knob-dropped"}


def test_knob_suppression_with_reason():
    bad = """
    def inner(data, backend=None):
        return data

    def outer(data, backend=None):
        # zipnn: allow(knob-redefault): fixture exercises the suppression path
        return inner(data, backend='host')
    """
    assert not lint(bad, KNOB_SCOPE, [knobs])


def test_suppression_without_reason_is_flagged():
    bad = """
    def inner(data, backend=None):
        return data

    def outer(data, backend=None):
        return inner(data, backend='host')  # zipnn: allow(knob-redefault)
    """
    v = lint(bad, KNOB_SCOPE, [knobs])
    # the reasonless allow() does not suppress, and is itself a finding
    assert rules_of(v) == {"knob-redefault", "bad-suppression"}


def test_knob_surface_contract():
    # a knob-scope module that exists but lost a public entry point knob
    code = """
    def compress_file(src, dst, dtype_name, config, threads=None):
        return None
    """
    v = lint(code, "src/repro/core/engine.py", [knobs])
    surface = [x for x in v if x.rule == "knob-surface"]
    assert surface, "missing entry points / knobs must be flagged"


def test_knob_surface_requires_options_bag():
    # the full legacy knob set without options= now fails the contract
    code = """
    def simulate_transfer(data, dtype_name, channel, threads=None,
                          backend=None, entropy_backend=None):
        return None

    def simulate_file_transfer(path, dtype_name, channel, threads=None,
                               backend=None, entropy_backend=None,
                               options=None):
        return None
    """
    v = lint(code, "src/repro/checkpoint/hub.py", [knobs])
    surface = [x for x in v if x.rule == "knob-surface"]
    assert len(surface) == 1
    assert "simulate_transfer" in surface[0].message
    assert "options" in surface[0].message


def test_knob_options_bag_supersedes_legacy_edges():
    # binding options= (non-None) satisfies the legacy knobs on that edge
    code = """
    def inner(data, threads=None, backend=None, options=None):
        return data

    def outer(data, threads=None, backend=None, options=None):
        return inner(data, options=options)
    """
    assert not lint(code, KNOB_SCOPE, [knobs])


def test_knob_options_none_does_not_supersede():
    # an explicit options=None edge still checks the legacy knobs
    code = """
    def inner(data, threads=None, backend=None, options=None):
        return data

    def outer(data, threads=None, backend=None, options=None):
        return inner(data, options=None)
    """
    v = lint(code, KNOB_SCOPE, [knobs])
    assert {x.rule for x in v} == {"knob-dropped"}
    # threads + backend dropped (options itself was explicitly bound)
    assert sum(1 for x in v if x.rule == "knob-dropped") == 2


def test_knob_options_dropped_is_flagged():
    # the bag is a knob too: dropping it on an edge is caught
    code = """
    def inner(data, options=None):
        return data

    def outer(data, options=None):
        return inner(data)
    """
    v = lint(code, KNOB_SCOPE, [knobs])
    assert rules_of(v) == {"knob-dropped"}
    assert "options" in v[0].message


def test_knob_codec_options_constructor_exempt():
    # building the bag from knob locals/constants is the forwarding act —
    # CodecOptions(...) edges are never knob-checked
    code = """
    class CodecOptions:
        def __init__(self, threads=None, backend=None, entropy_backend=None):
            self.threads = threads

    def outer(data, threads=None, backend=None, entropy_backend=None):
        return CodecOptions(threads=threads, backend="host")
    """
    assert not lint(code, KNOB_SCOPE, [knobs])


def test_knob_surface_round_trip_real_repo():
    """Every SURFACE pin resolves against the real repo files: the declared
    entry points exist and accept their full knob sets (incl. options=)."""
    from repro.analysis.driver import find_repo_root, load_project

    project = load_project(find_repo_root())
    v = [x for x in knobs.check(project) if x.rule == "knob-surface"]
    assert not v, [f"{x.path}:{x.lineno} {x.message}" for x in v]
    # the pins themselves cover the redesigned surface
    assert "options" in knobs.KNOBS
    for rel in (
        "src/repro/core/options.py",
        "src/repro/serve/kvcache.py",
    ):
        assert rel in knobs.SURFACE


# ---------------------------------------------------------------------------
# container spec
# ---------------------------------------------------------------------------

ENGINE = "src/repro/core/engine.py"

_SPEC_OK_PREFIX = """
    import struct

    _STREAM_MAGIC = b"ZNS1"
    _SHDR = struct.Struct("<4sHH16sQ")
    _FRAME = struct.Struct("<BQQI")
"""


def test_spec_format_matches():
    v = lint(_SPEC_OK_PREFIX, ENGINE, [container_spec])
    assert not [x for x in v if x.rule in ("spec-format", "spec-magic")]


def test_spec_format_drift_flagged():
    bad = _SPEC_OK_PREFIX.replace('"<BQQI"', '"<BQII"')
    v = lint(bad, ENGINE, [container_spec])
    assert "spec-format" in rules_of(v)


def test_spec_undeclared_struct_flagged():
    bad = _SPEC_OK_PREFIX + "    _EXTRA = struct.Struct('<II')\n"
    v = lint(bad, ENGINE, [container_spec])
    assert "spec-format" in rules_of(v)


def test_spec_inline_struct_outside_owning_modules():
    code = "import struct\nhdr = struct.pack('<I', 1)\n"
    v = lint(code, CORE, [container_spec])
    assert rules_of(v) == {"spec-format"}


def test_spec_missing_magic():
    bad = _SPEC_OK_PREFIX.replace('    _STREAM_MAGIC = b"ZNS1"\n', "")
    v = lint(bad, ENGINE, [container_spec])
    assert "spec-magic" in rules_of(v)


def test_spec_pack_arity():
    bad = _SPEC_OK_PREFIX + "    rec = _FRAME.pack(1, 2, 3)\n"
    v = lint(bad, ENGINE, [container_spec])
    assert "spec-arity" in rules_of(v)


def test_spec_unpack_arity():
    bad = _SPEC_OK_PREFIX + """
    def parse(rec):
        kind, raw_len, comp_len = _FRAME.unpack(rec)
        return kind
    """
    v = lint(bad, ENGINE, [container_spec])
    assert "spec-arity" in rules_of(v)


_PARSE = _SPEC_OK_PREFIX + """
    def parse(fp):
        kind, raw_len, comp_len, crc = _FRAME.unpack(fp.read(_FRAME.size))
        {guard}body = fp.read(comp_len)
        return body
"""


def test_spec_unchecked_length_flagged():
    v = lint(_PARSE.format(guard=""), ENGINE, [container_spec])
    assert "spec-unchecked-length" in rules_of(v)


def test_spec_checked_length_passes():
    ok = _PARSE.format(
        guard="if comp_len > (64 << 20):\n"
        "            raise IOError('frame too large')\n        "
    )
    v = lint(ok, ENGINE, [container_spec])
    assert "spec-unchecked-length" not in rules_of(v)


def test_spec_min_clamp_passes():
    ok = _PARSE.format(guard="comp_len = min(comp_len, 64 << 20)\n        ")
    v = lint(ok, ENGINE, [container_spec])
    assert "spec-unchecked-length" not in rules_of(v)


# ---------------------------------------------------------------------------
# kernel contract
# ---------------------------------------------------------------------------

_KERNEL = """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    HIST_ROWS = 128
    LANES = 128

    def _hist_kernel(x_ref, out_ref):
        out_ref[...] = jnp.zeros_like(out_ref)

    def chunk_histogram_2d(x, *, interpret: bool = True):
        m = x.shape[0]
        return pl.pallas_call(
            _hist_kernel,
            grid=(m // HIST_ROWS,),
            in_specs=[pl.BlockSpec(({in_rows}, LANES), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((256,), {out_lam}),
            out_shape=jax.ShapeDtypeStruct((256,), jnp.{dtype}),
            interpret={interp},
        )(x)
"""

_GOOD = dict(in_rows="HIST_ROWS", out_lam="lambda i: (0,)", dtype="int32",
             interp="interpret")


def _kern(**over):
    return _KERNEL.format(**{**_GOOD, **over})


def test_kernel_clean_passes():
    assert not lint(_kern(), KERN, [kernel_contract])


def test_kernel_registry():
    code = _kern().replace("histogram_2d", "mystery_kernel_2d")
    v = lint(code, KERN, [kernel_contract])
    assert "kernel-registry" in rules_of(v)


def test_kernel_index_map_arity():
    v = lint(_kern(out_lam="lambda i, j: (0,)"), KERN, [kernel_contract])
    assert "kernel-index-map" in rules_of(v)


def test_kernel_index_map_rank():
    v = lint(_kern(out_lam="lambda i: (0, 0)"), KERN, [kernel_contract])
    assert "kernel-index-map" in rules_of(v)


def test_kernel_block_shape_mismatch():
    # FP32_ROWS block under a grid stepping by HIST_ROWS: copy-paste class
    code = "    FP32_ROWS = 256\n" + _kern(in_rows="FP32_ROWS")
    v = lint(textwrap.dedent(code), KERN, [kernel_contract])
    assert "kernel-block-shape" in rules_of(v)


def test_kernel_dtype_contract():
    v = lint(_kern(dtype="uint8"), KERN, [kernel_contract])
    assert "kernel-dtype" in rules_of(v)


def test_kernel_interpret_hardcoded():
    v = lint(_kern(interp="True"), KERN, [kernel_contract])
    assert "kernel-interpret" in rules_of(v)


def test_kernel_arity_mismatch():
    code = _kern().replace(
        "def _hist_kernel(x_ref, out_ref):",
        "def _hist_kernel(x_ref, y_ref, out_ref):",
    )
    v = lint(code, KERN, [kernel_contract])
    assert "kernel-arity" in rules_of(v)


_PREFETCH_KERNEL = """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def _decode_kernel({params}):
        pass

    def huffdecode_chunks_multi(ids, words, *, interpret: bool = True):
        return pl.pallas_call(
            _decode_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(8,),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=[
                    pl.BlockSpec(memory_space=pl.ANY),
                    pl.BlockSpec(memory_space=pltpu.SMEM),
                ],
                scratch_shapes=[
                    pltpu.SMEM((1024,), jnp.int32),
                    pltpu.SemaphoreType.DMA((2,)),
                ],
            ),
            out_shape=[
                jax.ShapeDtypeStruct((8192,), jnp.int32),
                jax.ShapeDtypeStruct((8,), jnp.int32),
            ],
            interpret=interpret,
        )(ids, words)
"""


@pytest.mark.parametrize(
    "params, flagged",
    [
        ("ids_ref, words_hbm, out_hbm, cur_ref, buf_s, sem", False),
        ("ids_ref, words_hbm, out_hbm, cur_ref, sem", True),     # no scratch
        ("words_hbm, out_hbm, cur_ref, buf_s, sem", True),      # no prefetch
    ],
)
def test_kernel_arity_counts_prefetch_and_scratch(params, flagged):
    """Refs of a grid_spec= call: scalar prefetch, inputs, outputs, scratch."""
    v = lint(_PREFETCH_KERNEL.format(params=params), KERN, [kernel_contract])
    assert ("kernel-arity" in rules_of(v)) is flagged
    assert "kernel-dtype" not in rules_of(v)


# ---------------------------------------------------------------------------
# whole-repo smoke
# ---------------------------------------------------------------------------

def test_repo_is_clean_strict():
    """`python -m repro.analysis --strict` exits 0 on the repo (the CI gate)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("GITHUB_ACTIONS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--strict", "--root", REPO],
        capture_output=True,
        text=True,
        env=env,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "clean" in proc.stdout


def test_real_repo_files_parse_into_project():
    from repro.analysis.driver import find_repo_root, load_project

    root = find_repo_root()
    project = load_project(root)
    rels = {f.rel for f in project.files}
    assert "src/repro/core/zipnn.py" in rels
    assert "src/repro/core/engine.py" in rels
    # scan order is sorted -> deterministic report order
    assert [f.rel for f in project.files] == sorted(rels)


def test_multifile_project_cross_module_knobs():
    """Knob edges resolve across files (zipnn -> engine style)."""
    callee = SourceFile.parse(
        "src/repro/checkpoint/fake_engine.py",
        "def get_pool(threads):\n    return None\n",
    )
    caller = SourceFile.parse(
        "src/repro/checkpoint/fake_zipnn.py",
        "def compress_bytes(raw, threads=None):\n"
        "    return get_pool()\n",
    )
    v = [
        x
        for x in analyze_project(Project([callee, caller]), [knobs])
        if x.rule == "knob-dropped"
    ]
    assert len(v) == 1 and v[0].path.endswith("zipnn.py")
