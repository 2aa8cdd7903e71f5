"""Kernel-backend calibration: the analytic cost model vs the stopwatch.

The analytic-first autotune (``core.costmodel`` + ``core.autotune``) ranks
SpMV backends from a closed-form flops/bytes model and uses probes only to
calibrate constants. This suite keeps the model honest against hardware
truth on four calibration shapes spanning the planner's envelope (small /
medium / wide-block / large), and pins the Pallas batch-grid kernel's
agreement with the XLA path alongside the numbers:

  shapes      per shape: measured probe ranking (``autotune
              .probe_backends``) vs uncalibrated analytic ranking
              (``costmodel.rank_backends`` fed the true COO edge count —
              the shapes span block-fill regimes, so the blocked-vs-
              per-edge crossover is exactly what the model must get
              right). GATE: the two rankings agree (same winner) on
              >= 3 of the 4 shapes — a model that picks the wrong
              backend on the actual machine must go red here, not
              silently misroute ``backend="auto"``.
  auto        ``tune_backend`` end-to-end on the medium shape: probes are
              demoted to calibration, the memoized decision carries the
              machine-readable ``repro.cost/v1`` ranking report.
  parity      batched Pallas kernel (interpret mode on CPU) vs the
              ``bsr_ml`` batched path on a capacity-padded batch with
              streaming holes. GATE: equal to float32 rounding (1e-5;
              the kernel sums the ELL slots in chunks, bsr_ml in one
              contraction, so the order of the adds differs).

  PYTHONPATH=src:. python benchmarks/run.py --only bench_kernels
"""
from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from repro import api
from repro.core import autotune, costmodel

# (label, n, bs, sb, k, f) — the four calibration shapes
SHAPES = [
    ("small_n256_bs16", 256, 16, 4, 8, 1),
    ("medium_n1024_bs16", 1024, 16, 8, 8, 1),
    ("wide_n1024_bs32_f8", 1024, 32, 8, 8, 8),
    ("large_n4096_bs32", 4096, 32, 16, 8, 1),
]
BACKENDS = ("csr", "bsr", "bsr_ml")
GATE_AGREE = 3


def _plan(n, bs, sb, k, seed):
    rng = np.random.default_rng(seed)
    pts = jnp.asarray(rng.standard_normal((n, 8)), jnp.float32)
    return api.build_plan(pts, k=k, bs=bs, sb=sb, backend="bsr")


def run(emit) -> None:
    rng = np.random.default_rng(0)
    autotune.clear_tune_memo()
    autotune.clear_calibration()

    # -- per-shape: measured probe ranking vs analytic ranking -------------
    agree = 0
    for i, (label, n, bs, sb, k, f) in enumerate(SHAPES):
        plan = _plan(n, bs, sb, k, seed=i)
        shape = (plan.n,) if f == 1 else (plan.n, f)
        x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        measured = autotune.probe_backends(plan, x, backends=BACKENDS,
                                           warmup=1, iters=3)
        feat = costmodel.plan_features(plan.spec.shape_key, f=f,
                                       nnz=len(plan.coo[0]))
        report = costmodel.rank_backends(feat, tuple(measured))
        m_rank = sorted(measured, key=measured.get)
        a_rank = report["ranking"]
        ok = bool(m_rank and a_rank and m_rank[0] == a_rank[0])
        agree += ok
        best = m_rank[0] if m_rank else "none"
        emit(f"bench_kernels/{label},{measured.get(best, 0) * 1e6:.0f},"
             f"measured={best};analytic={report['winner']};agree={int(ok)}")

    emit(f"bench_kernels/ranking_gate,skipped,agree={agree}/{len(SHAPES)}")
    assert agree >= GATE_AGREE, (
        f"analytic ranking agrees with the measured probe ranking on only "
        f"{agree}/{len(SHAPES)} calibration shapes (need >= {GATE_AGREE}); "
        "the cost model no longer reflects this hardware — recalibrate the "
        "HardwareConfig knobs (gather_penalty / launch_overhead)")

    # -- auto resolution end-to-end: model decides, probes calibrate -------
    plan = _plan(*SHAPES[1][1:5], seed=1)
    t0 = time.perf_counter()
    winner, times = autotune.tune_backend(plan, device_count=1)
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    winner2, _ = autotune.tune_backend(plan, device_count=1)  # memo hit
    t_hit = time.perf_counter() - t0
    (memo_report,) = [r for r in autotune._TUNE_MEMO.values()
                      if r.get("kind") == "backend_rank"][:1]
    assert winner2 == winner == memo_report["winner"]
    assert memo_report["schema"] == costmodel.SCHEMA
    emit(f"bench_kernels/auto_medium,{t_first * 1e6:.0f},"
         f"winner={winner};memo_hit_us={t_hit * 1e6:.0f};"
         f"ranked={len(times)}")

    # -- batched Pallas parity: capacity padding + streaming holes ---------
    pts = [rng.standard_normal((120, 8)).astype(np.float32)
           for _ in range(4)]
    pb = api.build_plan_batch(pts, k=8, bs=16, sb=4, backend="bsr",
                              ell_slack=4, capacity=128)
    pb = pb.delete([rng.choice(120, 17, replace=False) for _ in range(4)])
    xs = jnp.asarray(
        rng.standard_normal((pb.batch, pb.capacity)), jnp.float32)
    want = np.asarray(jax.block_until_ready(
        api._batch_apply_kernel(pb.spec, pb.data, xs, "bsr_ml", "apply")))
    t0 = time.perf_counter()
    got = np.asarray(jax.block_until_ready(
        api._batch_apply_kernel(pb.spec, pb.data, xs, "pallas", "apply")))
    t_pallas = time.perf_counter() - t0
    err = float(np.abs(got - want).max())
    close = bool(np.allclose(got, want, rtol=1e-5, atol=1e-5))
    emit(f"bench_kernels/parity_batched_B4,{t_pallas * 1e6:.0f},"
         f"max_abs_err={err:.2e};holes=17")
    assert close, (
        f"batched pallas backend differs from bsr_ml by {err:.2e} on a "
        "capacity-padded batch with streaming holes")


if __name__ == "__main__":
    run(print)
