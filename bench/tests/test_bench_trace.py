"""trace.py against a synthetic trace with known intervals, and against a
small trace recorded on a TPU v5e (two restores of a 2 MB bf16 leaf)."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "restore_v5e.xplane.pb"


def _events(spec):
    return "\n".join(f"    events {{ metadata_id: {m} offset_ps: {a * 1000} duration_ps: {d * 1000} }}"
                     for m, a, d in spec)


def _synthetic():
    # device ops (ns): huffdecode [1000, 3000), copy [2000, 5000), huffdecode [7000, 8000)
    # host spans: window [0, 10000), restore [0, 6000), save [6000, 10000)
    return f'''
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
{_events([(1, 1000, 2000), (2, 2000, 3000), (1, 7000, 1000)])}
  }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0
{_events([(3, 0, 9000)])}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%huffdecode_chunks_multi.1 = s32[9] custom-call()" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "%copy.3 = u8[9] copy(u8[9] %p)" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "jit_restore(123)" }} }}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
{_events([(1, 0, 10000), (2, 0, 6000), (3, 6000, 4000), (4, 100, 50)])}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.window" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "bench.restore" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "bench.save" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "other.span" }} }}
}}
'''


def test_synthetic_trace():
    from jax.profiler import ProfileData

    red = trace.reduce_profile(ProfileData.from_text_proto(_synthetic()))
    assert red.window_s == pytest.approx(10e-6)
    assert red.busy_s == pytest.approx(5e-6)          # [1000, 5000) and [7000, 8000)
    assert red.kernel_s("huffdecode") == pytest.approx(3e-6)
    assert red.kernel_s("bitpack") == 0
    assert dict(red.device_ops()) == {"huffdecode_chunks_multi": pytest.approx(3e-6),
                                      "copy": pytest.approx(3e-6)}
    # gaps: [0,1000) restore, [5000,7000) mid 6000 -> save, [8000,10000) save
    assert red.idle_gaps == [("save", pytest.approx(2e-6)), ("save", pytest.approx(2e-6)),
                             ("restore", pytest.approx(1e-6))]


def test_op_name():
    assert trace.op_name('%huffdecode_chunks_multi.1 = (s32[4]) custom-call(s32[4] %a)') \
        == "huffdecode_chunks_multi"
    assert trace.op_name("%fusion.12 = f32[2] fusion(f32[2] %x)") == "fusion"


def test_no_window_span_is_an_error():
    from jax.profiler import ProfileData

    txt = _synthetic().replace('name: "bench.window"', 'name: "bench.other"')
    with pytest.raises(ValueError):
        trace.reduce_profile(ProfileData.from_text_proto(txt))


def test_recorded_v5e_trace():
    # known values from a sweep-line count over the same events
    red = trace.reduce_file(str(FIXTURE))
    assert red.chips == 1
    assert red.window_s == pytest.approx(0.089160225)
    assert red.busy_s == pytest.approx(0.040897132)
    assert red.kernel_s("huffdecode") == pytest.approx(0.040652863)
    assert red.idle_gaps[0] == ("restore", pytest.approx(0.005911592))
    assert red.kernel_s("plane_consumer") > 0
    assert red.device_ops()[0][0] == "huffdecode_chunks_multi"
    assert {label for label, _ in red.idle_gaps} <= {"restore", "no span"}
    assert red.kernel_s("huffdecode") <= red.busy_s
