"""Where a cell's host time goes, read from the program's own spans, and
what tracing costs: one process sets the cell up once, then runs, per
seed, one window with the profiler off and one with it on, each with that
seed, alternating which goes first. Never run by the benchmark's own
runs.

    python3 bench/tools/spans.py --workload <cell> --seeds 1,2 --seconds 50

Each window prints one JSON line: the seed, whether it was traced, the
window's end-to-end metrics and what the kind reports of its state. A
traced window on a chip also prints, before its JSON line, one
``span <name> n=… ms/call=… self ms/call=…`` line per program span
(``bench/program_spans.py``) and its ten longest idle gaps, each named by
the innermost span open in it; its JSON line adds the per-layer metrics
the spans are for (``program_spans.METRICS``), the device's busy and idle
share and, per parent span, the share of its time its children cover.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def _window(kind, ctx, state, seconds, traced) -> dict:
    from bench import harness, program_spans, trace
    counter = harness.CompileCounter()
    trace_dir = Path(tempfile.mkdtemp(prefix="spans-trace-")) \
        if traced else None
    try:
        win = harness._measure(kind, ctx, state, seconds, counter,
                               trace_dir)
        out = {"seed": ctx.seed, "traced": int(traced), **win.e2e,
               **{k: v for k, v in state.info.items()
                  if isinstance(v, (int, float))},
               "window_compiles": counter.count}
        if not traced:
            return out
        path = trace.find_xplane(trace_dir)
        spans = program_spans.reduce(path)
        for line in spans.lines():
            ctx.say(line)
        if ctx.rehearse:                 # no device plane off the chip
            return out
        summary = trace.summarize(path)
        for name, s in spans.gaps[:10]:
            ctx.say(f"gap {name} {s * 1e3:.3f} ms")
        out.update({m: spans.metric(m) for m in program_spans.METRICS
                    if spans.metric(m) is not None})
        out.update(busy_s=summary.busy_s, window_s=summary.window_s,
                   idle_percent=summary.idle_percent(),
                   children_share={
                       name: 1.0 - t.self_seconds / t.seconds
                       for name, t in spans.totals.items()
                       if t.seconds > 0 and t.self_seconds < t.seconds})
        return out
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="2147483659")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any device (tests of the tool)")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    import jax

    from bench import harness
    from bench.tools import probe
    precision = harness.load_cell(ROOT, args.workload).config.get(
        "matmul_precision")
    scope = (jax.default_matmul_precision(precision) if precision
             else contextlib.nullcontext())
    with scope:
        _, kind, ctx, state, seeds = probe._service(args)
        for i, seed in enumerate(seeds):
            ctx.seed = seed
            for traced in ((False, True) if i % 2 == 0 else (True, False)):
                print(json.dumps(_window(kind, ctx, state, args.seconds,
                                         traced)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
