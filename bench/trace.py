"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

A TPU trace holds one plane per chip (``/device:TPU:<n>``) whose
``XLA Ops`` line carries one event per device operation, named by its HLO
instruction (``%huffdecode_chunks_multi.1 = ... custom-call(...)``), and a
host plane (``/host:CPU``) whose ``python`` line carries the benchmark's
own ``TraceAnnotation`` spans (``bench.*``).  Both use one clock.

From them: the traced window (the ``bench.window`` span), the device's busy
time (the union of its operations' intervals inside the window, averaged
over the chips), the device time of each operation name (a kernel's time
is that of the operations whose name starts with its prefix), and the
longest idle gaps, each labelled by the innermost benchmark span that was
open on the host at the gap's middle.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclass
class Reduced:
    window_s: float
    busy_s: float                       # mean over chips
    chips: int
    op_s: Dict[str, float] = field(default_factory=dict)       # op name -> seconds
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    def kernel_s(self, prefix: str) -> float:
        """Device seconds of the operations whose name starts with ``prefix``."""
        return sum(s for n, s in self.op_s.items() if n.startswith(prefix))

    def device_ops(self, top: int = 10) -> List[Tuple[str, float]]:
        return sorted(self.op_s.items(), key=lambda t: -t[1])[:top]


def op_name(event_name: str) -> str:
    """``%huffdecode_chunks_multi.1 = (...) custom-call(...)`` ->
    ``huffdecode_chunks_multi``."""
    head = event_name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def find_xplane(directory: str) -> str:
    files = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return files[-1]


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(iv: List[Tuple[int, int]], lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def reduce_profile(profile, top: int = 10) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData``."""
    spans: List[Tuple[int, int, str]] = []
    devices: List[List[Tuple[int, int, str]]] = []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.start_ns, e.start_ns + e.duration_ns, op_name(e.name))
                               for e in line.events)
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    windows = [s for s in spans if s[2] == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(windows)}")
    lo, hi = windows[0][0], windows[0][1]
    if not devices or not any(devices):
        raise ValueError("the trace holds no device operation")
    busy = 0.0
    per_op: Dict[str, float] = {}
    gaps: List[Tuple[float, int, int]] = []
    for ops in devices:
        inside = [(max(a, lo), min(b, hi), n) for a, b, n in ops if b > lo and a < hi]
        for a, b, n in inside:
            per_op[n] = per_op.get(n, 0.0) + (b - a) * 1e-9
        u = _union([(a, b) for a, b, _ in inside])
        busy += sum(b - a for a, b in u) * 1e-9
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append(((b - a) * 1e-9, a, b))
    inner = [s for s in spans if s[2] != WINDOW_SPAN]

    def label(a: int, b: int) -> str:
        mid = (a + b) // 2
        open_ = [s for s in inner if s[0] <= mid < s[1]]
        if not open_:
            return "no span"
        return min(open_, key=lambda s: s[1] - s[0])[2][len(SPAN_PREFIX):]

    n = len(devices)
    gaps.sort(reverse=True)
    return Reduced(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy / n,
        chips=n,
        op_s={k: v / n for k, v in per_op.items()},
        idle_gaps=[(label(a, b), s) for s, a, b in gaps[:top]],
    )


def reduce_file(path: str, top: int = 10) -> Reduced:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), top)
