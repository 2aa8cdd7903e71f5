"""Benchmark harness: one module per paper table/figure (+ beyond-paper).
Prints ``name,us_per_call,derived`` CSV lines; ``--json out.json``
additionally records every line as a structured result (plus environment
metadata) so CI can upload the numbers as an artifact and later PRs can
diff them — the bench trajectory convention is ``BENCH_plan.json``.

  fig1_orderings   paper Fig. 1  (beta/gamma, four orderings)
  table1_gamma     paper Table 1 (gamma across orderings, SIFT/GIST-like)
  fig3_throughput  paper Fig. 3  (interaction throughput per ordering)
  micro_blas       paper §4.1    (banded best case vs scattered base case)
  attention_bench  beyond-paper  (cluster-sparse vs dense attention)
  bench_refresh    beyond-paper  (plan refresh vs rebuild, §3.2 drift)
  bench_shard      beyond-paper  (halo-exchange sharded matvec vs bsr)
  bench_stream     beyond-paper  (insert/delete churn vs rebuild-per-step)
  bench_batch      beyond-paper  (PlanBatch vmapped matvec vs plan loop)
  bench_serve      beyond-paper  (decode service vs per-call Morton sort)
  bench_kernels    beyond-paper  (analytic cost model vs probe ranking,
                                  batched Pallas parity)
  bench_solvers    beyond-paper  (batched block-Jacobi CG vs plain CG
                                  vs per-plan eager solve loop)

Gated suites assert their acceptance in-suite; a failed gate is recorded
per suite (the remaining suites still run, the JSON artifact carries the
failure) and the process exits non-zero — a red gate can no longer hide
behind a green artifact.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def merge(out: str, parts: list) -> None:
    """Combine several ``--json`` outputs into one trajectory file (CI
    runs suites under different env/mesh settings, then uploads one
    ``BENCH_plan.json`` artifact). Accepts both single-run docs
    (``env``) and already-merged docs (``envs``), so trajectories can be
    extended; each result is stamped with its run's device_count so the
    mesh context survives the flattening."""
    docs = [json.load(open(p)) for p in parts]
    suites, envs, results = [], [], []
    gate_failures = {}
    for d in docs:
        suites += d["suites"]
        part_envs = d.get("envs") or [d["env"]]
        envs += part_envs
        gate_failures.update(d.get("gate_failures") or {})
        dev = (part_envs[0].get("device_count")
               if len(part_envs) == 1 else None)
        for r in d["results"]:
            if dev is not None and "device_count" not in r:
                r = {**r, "device_count": dev}
            results.append(r)
    combined = {"schema": 1, "suites": suites, "envs": envs,
                "gate_failures": gate_failures, "results": results}
    with open(out, "w") as f:
        json.dump(combined, f, indent=2)
    print(f"# merged {len(parts)} files -> {out} "
          f"({len(results)} results)", file=sys.stderr)
    if gate_failures:
        # the merged artifact records the failures AND the merge step
        # itself goes red — a failed gate cannot ride a green upload
        for name, msg in gate_failures.items():
            print(f"# GATE FAILED {name}: {msg}", file=sys.stderr)
        sys.exit(1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of benchmark names")
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="also write results as structured JSON to OUT")
    ap.add_argument("--merge", nargs="+", default=None,
                    metavar=("OUT", "IN"),
                    help="merge JSON result files: OUT IN [IN ...]")
    args = ap.parse_args()

    if args.merge:
        if len(args.merge) < 2:
            ap.error("--merge needs OUT and at least one IN file")
        merge(args.merge[0], args.merge[1:])
        return

    from benchmarks import (attention_bench, bench_batch, bench_kernels,
                            bench_refresh, bench_serve, bench_shard,
                            bench_solvers, bench_stream, fig1_orderings,
                            fig3_throughput, micro_blas, table1_gamma)
    suites = {
        "fig1_orderings": fig1_orderings.run,
        "table1_gamma": table1_gamma.run,
        "fig3_throughput": fig3_throughput.run,
        "micro_blas": micro_blas.run,
        "attention_bench": attention_bench.run,
        "bench_refresh": bench_refresh.run,
        "bench_shard": bench_shard.run,
        "bench_stream": bench_stream.run,
        "bench_batch": bench_batch.run,
        "bench_serve": bench_serve.run,
        "bench_kernels": bench_kernels.run,
        "bench_solvers": bench_solvers.run,
    }
    chosen = (args.only.split(",") if args.only else list(suites))
    unknown = [c for c in chosen if c not in suites]
    if unknown:
        ap.error(f"unknown benchmark(s) {unknown}; "
                 f"available: {', '.join(suites)}")

    results = []

    def emit(line: str) -> None:
        print(line, flush=True)
        name, us, derived = (line.split(",", 2) + ["", ""])[:3]
        try:
            us_val = float(us)      # some suites emit "skipped" here
        except ValueError:
            us_val = None
        rec = {"name": name, "us_per_call": us_val}
        # derived is a ;-separated key=value bag (backend, speedup, ...)
        for kv in filter(None, derived.split(";")):
            k, _, v = kv.partition("=")
            rec[k] = v
        results.append(rec)

    gate_failures = {}
    print("name,us_per_call,derived")
    for name in chosen:
        t0 = time.time()
        try:
            suites[name](emit)
        except AssertionError as e:
            # an in-suite gate failed: record it, keep running the other
            # suites, and exit non-zero at the end so the run (and any
            # artifact built from it) is visibly red
            gate_failures[name] = str(e)
            print(f"# GATE FAILED {name}: {e}", file=sys.stderr)
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)

    if args.json:
        import platform

        import jax

        doc = {
            "schema": 1,
            "suites": chosen,
            "env": {
                "jax": jax.__version__,
                "backend": jax.default_backend(),
                "device_count": jax.device_count(),
                "python": platform.python_version(),
            },
            "gate_failures": gate_failures,
            "results": results,
        }
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"# wrote {len(results)} results to {args.json}",
              file=sys.stderr)

    if gate_failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
