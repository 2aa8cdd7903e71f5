"""Probes that define a serving cell, run once on the chip when the cell is
written, never by the benchmark's own runs. Both set the service up once
and run many windows in one process, so set-up is paid once.

    python3 bench/tools/probe.py sweep  --workload <cell> --rates 0.5,1,2 --seconds 30
    python3 bench/tools/probe.py limits --workload <cell> --seeds 1,2,3 --seconds 20

``sweep`` offers each rate in turn (the cell's mix at that rate, one seed
per rate) and prints, per rate, the tail of time to first token and how
long the service took past the window's end to finish what was due: a
rate the service sustains finishes within a few ticks of the close.

``limits`` runs one window per seed, each with fresh weights, and prints
the numbers the cell compares, for the program and for each control, so
that a limit can be set between them. Each line is one JSON object.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def _service(args):
    sys.path.insert(0, str(ROOT))
    from bench import harness
    cell = harness.load_cell(ROOT, args.workload)
    harness.import_program(ROOT)
    if not args.rehearse:
        harness.require_chip(cell.chips)
    harness.enable_compile_cache(ROOT)
    kind_name = cell.workload["kind"]
    kind = harness._load_module(
        ROOT / "bench" / "traffic" / f"{kind_name}.py",
        f"bench_kind_{kind_name}")
    seeds = [int(s) for s in args.seeds.split(",")]
    ctx = harness.Ctx(cell=cell, seed=seeds[0], rehearse=args.rehearse,
                      control=None, fault=None)
    t = time.time()
    state = kind.setup(ctx)
    ctx.say(f"probe set-up {time.time() - t:.1f} s")
    return cell, kind, ctx, state, seeds


def sweep(args) -> None:
    cell, kind, ctx, st, seeds = _service(args)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        cell.workload["params"]["rate"] = rate
        cell.workload.get("rehearsal_params", {}).pop("rate", None)
        ctx.seed = seeds[i % len(seeds)] + i
        win = kind.window(ctx, st, args.seconds)
        print(json.dumps({"rate": rate, "seed": ctx.seed,
                          "attempted": win.attempted, "failed": win.failed,
                          "past_close_s": win.seconds - args.seconds,
                          **win.e2e, **win.counts,
                          "admit_step_ms": st.info.get("admit_step_ms")}),
              flush=True)


def limits(args) -> None:
    cell, kind, ctx, st, seeds = _service(args)
    controls = tuple(c for c in args.controls.split(",") if c)
    for seed in seeds:
        ctx.seed = seed
        kind.reseed(ctx, st)
        win = kind.window(ctx, st, args.seconds)
        out = kind.readings(ctx, st, win, controls)
        print(json.dumps({"seed": seed, "attempted": win.attempted,
                          "failed": win.failed, **win.e2e, **out}),
              flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("probe", choices=("sweep", "limits"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="2147483659")
    ap.add_argument("--rates", default="")
    ap.add_argument("--controls", default="fp8")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any device (tests of the probe)")
    args = ap.parse_args(argv)
    {"sweep": sweep, "limits": limits}[args.probe](args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
