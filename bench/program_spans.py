"""Reduce the program's own spans in a profiler trace of one window.

The program opens a ``repro/<name>`` host span at each layer boundary
(``src/repro/spans.py``), on the same host plane and clock as the
benchmark's ``bench/`` spans and the device's operations. Over the window
span, this module gives

- per span name: how many spans lie wholly inside the window, their
  summed seconds, and their summed self seconds: a span's duration minus
  the union of the ``repro/`` spans nested in it on the same line;
- the device's idle gaps (those of ``bench/trace.py``), each named by the
  innermost span open at its middle, ``bench/`` or ``repro/``: a gap
  inside a plan build reads ``repro/build.order``, not ``bench/build``.

It reads the trace with ``bench/trace.py``'s own helpers and changes
nothing that module computes. A trace with no ``repro/`` span (a program
from before the spans) gives no totals and the gaps ``bench/trace.py``
gives.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Dict, List, NamedTuple, Optional, Tuple

from bench import trace

PREFIX = "repro/"


# the per-layer metrics the spans are for: (spans summed, span counted),
# in ms per counted span (PERF.md §3)
METRICS = {
    "admit_prefill_ms": (("repro/admit.prefill",), "repro/admit"),
    "admit_plans_ms": (("repro/admit.plans",), "repro/admit"),
    "admit_kv_ms": (("repro/admit.kv_out", "repro/admit.kv_in"),
                    "repro/admit"),
    "decode_tick_ms": (("repro/decode",), "repro/decode"),
    "host_claim_ms": (("repro/decode.claim",), "repro/decode"),
    "build_order_ms": (("repro/build.order",), "repro/build"),
    "build_tiles_ms": (("repro/build.tiles",), "repro/build"),
}


class SpanTotals(NamedTuple):
    count: int
    seconds: float
    self_seconds: float


@dataclasses.dataclass
class ProgramSpans:
    totals: Dict[str, SpanTotals]     # by span name, prefix included
    gaps: List[Tuple[str, float]]     # idle gaps, longest first

    def seconds(self, name: str) -> float:
        return self.totals.get(name, SpanTotals(0, 0.0, 0.0)).seconds

    def count(self, name: str) -> int:
        return self.totals.get(name, SpanTotals(0, 0.0, 0.0)).count

    def metric(self, name: str) -> Optional[float]:
        """``METRICS[name]`` in ms, or ``None`` where no span counts."""
        summed, per = METRICS[name]
        if not self.count(per):
            return None
        return sum(self.seconds(n) for n in summed) / self.count(per) * 1e3

    def lines(self) -> List[str]:
        """One ``span <name> n=… ms/call=… self ms/call=…`` line per span
        name, in name order."""
        return [f"span {name} n={t.count} "
                f"ms/call={t.seconds / t.count * 1e3:.4f} "
                f"self ms/call={t.self_seconds / t.count * 1e3:.4f}"
                for name, t in sorted(self.totals.items())]


def reduce(path, window: str = "bench/window") -> ProgramSpans:
    """Reduce the trace at ``path`` over the host span named ``window``."""
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(str(path)).planes, window)


def reduce_planes(planes, window: str = "bench/window") -> ProgramSpans:
    planes = list(planes)
    spans = []                        # (start, end, name, line)
    for plane in planes:
        if trace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith((trace.SPAN_PREFIX, PREFIX)):
                    spans.append((ev.start_ns, ev.end_ns, ev.name,
                                  (plane.name, line.name)))
    wins = [(s, e) for s, e, n, _ in spans if n == window]
    if not wins:
        raise ValueError(f"no host span {window!r} in the trace")
    w0, w1 = min(s for s, _ in wins), max(e for _, e in wins)
    program = [sp for sp in spans
               if sp[2].startswith(PREFIX) and sp[0] >= w0 and sp[1] <= w1]
    devices = [p for p in planes if trace.DEVICE_PLANE.match(p.name)]
    gaps = []
    if devices:
        ivs = []
        for _, ev in trace._events(devices[0], trace.OPS_LINE):
            s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
            if e > s:
                ivs.append((s, e))
        _, merged = trace.union_length(ivs)
        gaps = trace._gaps(merged, w0, w1, [(s, e, n) for s, e, n, _
                                            in spans if n != window])
    return ProgramSpans(totals=_totals(program), gaps=gaps)


def _totals(program) -> Dict[str, SpanTotals]:
    """Count, seconds and self seconds per name. Spans of one line nest
    (one thread opens and closes them in order), so a span's children are
    those whose nearest enclosing span it is."""
    kids = collections.defaultdict(list)
    by_line = collections.defaultdict(list)
    for sp in program:
        by_line[sp[3]].append(sp)
    for line in by_line.values():
        stack = []
        for sp in sorted(line, key=lambda sp: (sp[0], -sp[1])):
            while stack and stack[-1][1] <= sp[0]:
                stack.pop()
            if stack:
                kids[id(stack[-1])].append((sp[0], sp[1]))
            stack.append(sp)
    count = collections.Counter()
    seconds = collections.Counter()
    own = collections.Counter()
    for sp in program:
        s, e, name = sp[0], sp[1], sp[2]
        covered, _ = trace.union_length(
            (max(a, s), min(b, e)) for a, b in kids[id(sp)])
        count[name] += 1
        seconds[name] += (e - s) * 1e-9
        own[name] += (e - s - covered) * 1e-9
    return {n: SpanTotals(count[n], seconds[n], own[n]) for n in count}
