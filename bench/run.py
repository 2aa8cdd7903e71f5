"""Run one cell of ``BENCHMARK.json`` on the chip and print its result.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Every part of a cell is found by name:

  BENCHMARK.json            the cell's configuration, traffic and metrics
  bench/configs/<c>.json    the configuration: source, sizes, cuts
  bench/workloads/<w>.json  the cell: its traffic kind and parameters
  bench/traffic/<kind>.py   the generator and timed window of that kind
  bench/metrics/<m>.py      one reader per per-layer metric
  bench/peaks.json          the chip's published peaks, by device kind

A run sets up (data from the seed, plan or weights, warm-up of every shape
the window uses), measures for ``--seconds``, then checks what the window
produced against the plain reference under ``bench/reference``. The last
line of standard output is one JSON object; the numbers compared, each
beside its limit, are the last lines of standard error and the last key
of that object. ``--trace 1`` runs the same window under the profiler and
reports the per-layer metrics in place of the end-to-end ones.

``--rehearse`` runs the cell at the tiny size its workload file names, on
whatever device JAX has (the CPU here, with the Pallas kernels
interpreted), and reports no metric: it proves control flow and checks,
never a time.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any device; reports no metric")
    ap.add_argument("--control", default=None,
                    help="put this control in the program's place")
    ap.add_argument("--fault", default=None,
                    help="plant this fault in the timed path")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench import harness

    try:
        return harness.run(args, root=ROOT, t_start=T_START)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2


if __name__ == "__main__":
    sys.exit(main())
