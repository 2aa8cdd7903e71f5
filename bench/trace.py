"""Reduce a profiler trace of one window to the numbers the metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler.trace`` writes, read
with ``jax.profiler.ProfileData``. Device planes are those named
``/device:TPU:<i>``: their ``XLA Ops`` line holds one event per operation
run on the chip, their ``XLA Modules`` line one per program. Host planes
hold the benchmark's own ``TraceAnnotation`` spans (``bench/...``), the
window's among them, on the same clock.

- busy time: the union of the device's operation intervals inside the
  window, averaged over the device planes; idle = 1 - busy / window;
- device time of a program: the summed duration of its ``XLA Modules``
  events, found by the module's name without its ``(id)`` suffix;
- idle gaps: the gaps in the busy union, each named by the innermost
  benchmark span open on the host at its middle.
"""
from __future__ import annotations

import collections
import dataclasses
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench/"


def find_xplane(trace_dir) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union_length(intervals: Iterable[Tuple[int, int]]) -> Tuple[int, list]:
    """Length of the union of ``[start, end)`` intervals, and the union as
    sorted disjoint intervals."""
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def module_name(event_name: str) -> str:
    """``jit_knn_graph(123)`` -> ``jit_knn_graph``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def op_name(event_name: str) -> str:
    """``%fusion.19 = f32[...] fusion(...)`` -> ``fusion.19``: an
    operation's events carry its whole HLO text."""
    return event_name.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    ops: Dict[str, float]             # device seconds per operation name
    modules: Dict[str, float]         # device seconds per program name
    spans: Dict[str, int]             # benchmark spans in the window
    gaps: List[Tuple[str, float]]     # idle gaps, longest first

    def idle_percent(self) -> Optional[float]:
        if self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def calls(self, span: str) -> int:
        return self.spans.get(span, 0)

    def module_seconds(self, name: str) -> float:
        return self.modules.get(name, 0.0)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:top]]}


def _events(plane, line_name: Optional[str] = None):
    for line in plane.lines:
        if line_name is None or line.name == line_name:
            for ev in line.events:
                yield line.name, ev


def summarize(path, window: str = "bench/window") -> Summary:
    """Reduce the trace at ``path`` over the host span named ``window``."""
    from jax.profiler import ProfileData
    return summarize_planes(ProfileData.from_file(str(path)).planes, window)


def summarize_planes(planes, window: str = "bench/window") -> Summary:
    planes = list(planes)
    host_spans = []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for _, ev in _events(plane):
            if ev.name.startswith(SPAN_PREFIX):
                host_spans.append((ev.start_ns, ev.end_ns, ev.name))
    wins = [(s, e) for s, e, n in host_spans if n == window]
    if not wins:
        raise ValueError(f"no host span {window!r} in the trace")
    w0, w1 = min(s for s, _ in wins), max(e for _, e in wins)
    devices = [p for p in planes if DEVICE_PLANE.match(p.name)]
    if not devices:
        raise ValueError("no device plane in the trace")
    busy, ops, modules = 0, collections.Counter(), collections.Counter()
    union0 = None
    for plane in devices:
        ivs = []
        for _, ev in _events(plane, OPS_LINE):
            s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
            if e > s:
                ivs.append((s, e))
                ops[op_name(ev.name)] += (e - s) * 1e-9
        length, merged = union_length(ivs)
        busy += length
        union0 = merged if union0 is None else union0
        for _, ev in _events(plane, MODULES_LINE):
            s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
            if e > s:
                modules[module_name(ev.name)] += (e - s) * 1e-9
    spans = collections.Counter(
        n for s, e, n in host_spans if n != window and s >= w0 and e <= w1)
    inner = [(s, e, n) for s, e, n in host_spans if n != window]
    return Summary(window_s=(w1 - w0) * 1e-9,
                   busy_s=busy / len(devices) * 1e-9,
                   ops=dict(ops), modules=dict(modules), spans=dict(spans),
                   gaps=_gaps(union0 or [], w0, w1, inner))


def _gaps(merged, w0: int, w1: int, spans) -> List[Tuple[str, float]]:
    """Idle gaps of the first device, longest first, each named by the
    shortest benchmark span around its middle (``host`` where none is)."""
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    out = []
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        mid = (s + e) // 2
        around = [(b - a, n) for a, b, n in spans if a <= mid < b]
        out.append((min(around)[1] if around else "host", (e - s) * 1e-9))
    return sorted(out, key=lambda g: -g[1])
