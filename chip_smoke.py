"""Smoke run of the plan path and the ClusterKV decode service on one TPU.

Drives the two hot paths once, through the entry points a user calls, at
real sizes, and checks every result against a plain reference:

  plan    ``api.build_plan`` over SIFT-shaped points (128-d, k=16,
          ``backend="pallas"``); ``plan.matvec`` on one charge vector and
          on a block of 128, against a float64 host SpMV over the plan's
          own COO edges; the kNN against a float64 brute force.
  batch   ``api.build_plan_batch`` of 64 members, one batched Pallas
          matvec, against the batched ``bsr`` path.
  decode  qwen2-0.5b at its published widths with seeded random bf16
          weights through ``ClusterKVEngine(mode="plan")`` and the Pallas
          decode kernel; then, at full cluster coverage in float32,
          against the dense ``Engine`` logit by logit.

With ``--four-chips`` it runs only the sharded plan path on a 4-device
mesh (halo-exchange matvec and sharded CG) against one device.

Times and memory printed on the way are smoke readings, not benchmark
results. The last line of standard output is one JSON object naming the
device; any failed check raises and exits non-zero before it.

  python chip_smoke.py               # one chip: plan, batch, decode
  python chip_smoke.py --four-chips  # four chips: the sharded plan path
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import api  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import ClusterKVConfig  # noqa: E402
from repro.data.pipeline import sift_like  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import model_api  # noqa: E402
from repro.serve import ClusterKVEngine  # noqa: E402
from repro.train.serve_loop import Engine, Request  # noqa: E402

# A float32 SpMV row sums k products; each add rounds at 2^-24 relative to
# the running sum, so |y - y64| stays below ~k * 2^-24 * sum|a||x|
# (~1e-6 for k = 16). 1e-5 of sum|a||x| per row leaves margin, and one
# missed or misplaced 32-wide block moves a row by O(1) of it.
SPMV_RTOL = 1e-5


def _say(msg: str) -> None:
    print(msg, flush=True)


def _median_ms(fn, reps: int = 5) -> float:
    jax.block_until_ready(fn())                      # compile + warm
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e3


def _peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def _check_spmv(name: str, got, want, scale) -> float:
    """``|got - want| <= SPMV_RTOL * scale`` elementwise (``scale`` is
    ``|A| |x|``); returns the worst ratio of error to bound."""
    got = np.asarray(got, np.float64)
    err = np.abs(got - want)
    bound = SPMV_RTOL * scale + 1e-30
    worst = float((err / bound).max())
    if not np.all(np.isfinite(got)) or worst > 1.0:
        i = np.unravel_index(int(np.argmax(err / bound)), err.shape)
        raise AssertionError(
            f"{name}: |got - want| = {err[i]:.3e} at {i} exceeds "
            f"{SPMV_RTOL} * sum|a||x| = {bound[i]:.3e}")
    return worst


# -- plan ---------------------------------------------------------------------


def plan_phase(n: int, *, k: int = 16, f: int = 128, queries: int = 1000,
               seed: int = 0) -> None:
    """Build a SIFT-shaped plan with the Pallas backend, check two
    matvecs against a float64 host SpMV and the kNN against brute force."""
    import scipy.sparse as sp

    x = sift_like(n=n, seed=seed)
    _say(f"plan: n={n} d={x.shape[1]} k={k}")
    t0 = time.perf_counter()
    plan = api.build_plan(x, k=k, backend="pallas")
    jax.block_until_ready(plan.bsr.vals)
    build_s = time.perf_counter() - t0
    b = plan.bsr
    _say(f"plan: smoke reading, not a benchmark: build {build_s:.1f} s, "
         f"n_rb={b.n_rb} max_nbr={b.max_nbr} fill={plan.fill:.4f}")

    rows, cols, vals = plan.host.coo                 # cluster order
    pi = plan.host.pi
    r0, c0 = pi[rows], pi[cols]                      # original order
    a64 = sp.csr_matrix((vals.astype(np.float64), (r0, c0)), shape=(n, n))
    abs_a = abs(a64)

    rng = np.random.default_rng(seed + 1)
    for width in (1, f):
        shape = (n,) if width == 1 else (n, width)
        xc = rng.standard_normal(shape).astype(np.float32)
        xd = jnp.asarray(xc)
        got = plan.matvec(xd)
        want = a64 @ xc.astype(np.float64)
        scale = abs_a @ np.abs(xc.astype(np.float64))
        worst = _check_spmv(f"plan matvec f={width}", got, want, scale)
        ms = _median_ms(lambda: plan.matvec(xd))
        _say(f"plan: matvec f={width} matches float64 host SpMV "
             f"(worst error {worst:.3f} of bound); smoke reading, not a "
             f"benchmark: median {ms:.3f} ms")

    _check_knn(x, r0, c0, k, queries, rng)
    _say(f"plan: smoke reading, not a benchmark: peak device bytes "
         f"{_peak_bytes()}")


def _check_knn(x: np.ndarray, r0: np.ndarray, c0: np.ndarray, k: int,
               queries: int, rng) -> None:
    """The plan's kNN rows are exact up to float32 ties: every returned
    neighbor lies within rounding of the true k-th distance."""
    n = x.shape[0]
    order = np.argsort(r0, kind="stable")
    if not np.array_equal(np.bincount(r0, minlength=n), np.full(n, k)):
        raise AssertionError("kNN pattern does not hold k edges per row")
    nbrs = c0[order].reshape(n, k)
    q = rng.choice(n, min(queries, n), replace=False)
    x64 = x.astype(np.float64)
    sq = (x64 ** 2).sum(1)
    d2 = sq[q, None] + sq[None, :] - 2.0 * x64[q] @ x64.T
    d2[np.arange(len(q)), q] = np.inf                 # exclude self
    kth = np.partition(d2, k - 1, axis=1)[:, k - 1]
    got = np.take_along_axis(d2, nbrs[q], axis=1)
    # float32 rounding of the expanded distance |q|^2 + |s|^2 - 2 q.s over
    # 128 dims is below 128 * 2^-24 * (|q|^2 + |s|^2) ~ 8e-6 of it
    slack = 1e-5 * (sq[q] + sq.max())
    if np.any(nbrs[q] == q[:, None]) or np.any(
            got > (kth + slack)[:, None]):
        raise AssertionError("kNN rows are not the exact nearest neighbors")
    true = np.argpartition(d2, k - 1, axis=1)[:, :k]
    recall = np.mean([len(np.intersect1d(a, b)) / k
                      for a, b in zip(true, nbrs[q])])
    _say(f"plan: kNN exact on {len(q)} sampled queries (recall {recall:.4f} "
         "against float64 brute force; misses are float32 ties)")


# -- batch --------------------------------------------------------------------


def batch_phase(members: int = 64, n: int = 4096, *, k: int = 16,
                seed: int = 0) -> None:
    """One batched Pallas matvec over a PlanBatch, against batched bsr."""
    xs = [sift_like(n=n, seed=seed + 100 + i) for i in range(members)]
    t0 = time.perf_counter()
    pb = api.build_plan_batch(xs, k=k, backend="pallas")
    jax.block_until_ready(pb.data.vals)
    build_s = time.perf_counter() - t0
    x = jnp.asarray(np.random.default_rng(seed + 2).standard_normal(
        (members, pb.capacity)), jnp.float32)
    got = pb.matvec(x, backend="pallas")
    # the TPU's default float32 matmul rounds inputs to bfloat16; hold the
    # XLA reference to float32 so the bound above applies to both. Values
    # are ones (the build default), so A|x| is the |A||x| bound.
    with jax.default_matmul_precision("float32"):
        want = np.asarray(pb.matvec(x, backend="bsr"), np.float64)
        scale = np.asarray(pb.matvec(jnp.abs(x), backend="bsr"), np.float64)
    worst = _check_spmv("batch matvec", got, want, scale)
    ms = _median_ms(lambda: pb.matvec(x, backend="pallas"))
    _say(f"batch: {members} members of n={n} in one Pallas matvec match "
         f"batched bsr (worst error {worst:.3f} of bound); smoke reading, "
         f"not a benchmark: build {build_s:.1f} s, median {ms:.3f} ms")


# -- decode -------------------------------------------------------------------


def _init_params(cfg, seed: int):
    return jax.jit(lambda key: model_api.init(cfg, key)[0])(
        jax.random.PRNGKey(seed))


def _requests(cfg, n_req: int, prompt: tuple, max_new: int, seed: int):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, tokens=rng.integers(
                0, cfg.vocab, int(rng.integers(prompt[0], prompt[1] + 1))
            ).astype(np.int32), max_new=max_new)
            for i in range(n_req)]


def _run(engine, prompts):
    reqs = [dataclasses.replace(r, output=[]) for r in prompts]
    for r in reqs:
        engine.submit(r)
    t0 = time.perf_counter()
    engine.run()
    return reqs, time.perf_counter() - t0


def _record_logits(engine, attr: str) -> dict:
    """Wrap the engine's jitted decode so every tick's logits are kept per
    (request id, decode step)."""
    fn, out = getattr(engine, attr), {}

    def call(*args):
        res = fn(*args)
        logits = np.asarray(res[0], np.float32).reshape(engine.slots, -1)
        for s, req in enumerate(engine.slot_req):
            if req is not None:
                out[(req.rid, len(req.output))] = logits[s]
        return res

    setattr(engine, attr, call)
    return out


def decode_phase(cfg, *, slots: int = 4, max_seq: int = 4096,
                 n_req: int = 8, prompt: tuple = (1000, 3000),
                 max_new: int = 16, bucket: int = 1024, seed: int = 0,
                 block_k: int = 128) -> None:
    """The decode service with the Pallas decode kernel forced, then a
    full-coverage float32 run against the dense engine."""
    ckv = ClusterKVConfig(enabled=True, block_k=block_k, block_q=block_k,
                          decode_backend="pallas")
    prompts = _requests(cfg, n_req, prompt, max_new, seed)
    _say(f"decode: {cfg.name} L={cfg.n_layers} d={cfg.d_model} "
         f"heads={cfg.n_heads}/{cfg.n_kv_heads} vocab={cfg.vocab}; "
         f"{n_req} requests, prompts {[len(r.tokens) for r in prompts]}, "
         f"{max_new} new tokens each, {slots} slots, max_seq={max_seq}")

    c16 = cfg.with_(dtype="bfloat16", param_dtype="bfloat16", clusterkv=ckv)
    svc = ClusterKVEngine(c16, _init_params(c16, seed), slots=slots,
                          max_seq=max_seq, prefill_bucket=bucket,
                          mode="plan")
    reqs, wall = _run(svc, prompts)
    rep = svc.report()
    if rep["decode_traces"] != 1:
        raise AssertionError(f"decode_traces={rep['decode_traces']} != 1")
    for r in reqs:
        if len(r.output) != max_new or not all(
                0 <= t < cfg.vocab for t in r.output):
            raise AssertionError(f"request {r.rid} output {r.output}")
    _say(f"decode: bf16 service, decode_backend=pallas, "
         f"{rep['decode_traces']} decode trace, {rep['tokens_out']} tokens; "
         f"smoke reading, not a benchmark: wall {wall:.1f} s with compiles")

    full = dataclasses.replace(ckv, decode_clusters=max_seq // block_k)
    c32 = cfg.with_(dtype="float32", param_dtype="float32", clusterkv=full)
    p32 = _init_params(c32, seed)
    # float32 products on the MXU (its default rounds f32 inputs to bf16),
    # so the two engines differ only by summation order
    with jax.default_matmul_precision("float32"):
        dense = Engine(c32, p32, slots=slots, max_seq=max_seq,
                       prefill_bucket=bucket, backend="flash")
        want_logits = _record_logits(dense, "_decode")
        want, _ = _run(dense, prompts)
        svc32 = ClusterKVEngine(c32, p32, slots=slots, max_seq=max_seq,
                                prefill_bucket=bucket, mode="plan")
        got_logits = _record_logits(svc32, "_plan_decode")
        got, _ = _run(svc32, prompts)
    if svc32.report()["decode_traces"] != 1:
        raise AssertionError("full-coverage service retraced its decode")
    _compare_decode(want, got, want_logits, got_logits)


# Float32 throughout: the service and the dense engine attend the same
# positions in different orders (plan-ordered tiles with an online softmax
# against flash blocks), so logits differ by rounding that grows through
# the layers to ~1e-5 of their scale. 1e-3 of the row's largest logit
# leaves margin; attending a wrong set of positions moves logits by O(1)
# of it. An argmax that flips is accepted only where the dense engine's
# top two logits lie within twice that bound (a tie under rounding); the
# request's later steps then see different tokens and are not compared.
LOGIT_RTOL = 1e-3


def _compare_decode(want, got, want_logits, got_logits) -> None:
    steps = ties = 0
    worst = 0.0
    for a, b in zip(want, got):
        if a.output[:1] != b.output[:1]:
            raise AssertionError(f"request {a.rid}: first token from the "
                                 f"same prefill differs: {a.output[:1]} "
                                 f"vs {b.output[:1]}")
        for j in range(1, len(a.output)):
            la, lb = want_logits[(a.rid, j)], got_logits[(b.rid, j)]
            tol = LOGIT_RTOL * max(1.0, float(np.abs(la).max()))
            err = float(np.abs(la - lb).max())
            if not np.isfinite(err) or err > tol:
                raise AssertionError(
                    f"request {a.rid} step {j}: logits differ by {err:.3e} "
                    f"> {tol:.3e}")
            worst = max(worst, err / tol)
            steps += 1
            if a.output[j] != b.output[j]:
                top2 = np.sort(la)[-2:]
                if top2[1] - top2[0] > 2 * tol:
                    raise AssertionError(
                        f"request {a.rid} step {j}: argmax {b.output[j]} "
                        f"!= {a.output[j]} with a top-2 gap of "
                        f"{top2[1] - top2[0]:.3e}")
                ties += 1
                break
    _say(f"decode: full-coverage float32 service matches the dense engine "
         f"on {steps} decode steps (worst logit error {worst:.3f} of "
         f"bound, {ties} argmax ties)")


# -- four chips ----------------------------------------------------------------


def sharded_phase(n: int, mesh, *, k: int = 16, seed: int = 0) -> None:
    """Halo-exchange matvec and sharded CG over the plan phase's data
    (symmetrized: CG needs a symmetric operator) against the same
    computed on one device."""
    x = sift_like(n=n, seed=seed)
    t0 = time.perf_counter()
    plan = api.build_plan(x, k=k, symmetrize=True, backend="bsr")
    sp = plan.shard(mesh)
    jax.block_until_ready(sp.vals)
    _say(f"four-chips: n={n} k={k} symmetrized; smoke reading, not a "
         f"benchmark: build and shard {time.perf_counter() - t0:.1f} s")
    for i, shard in enumerate(sp.vals.addressable_shards):
        rows = shard.index[0]
        _say(f"four-chips: shard {i} row blocks {rows.start}:{rows.stop} "
             f"on device {shard.device.id}")
    devs = {s.device.id for s in sp.vals.addressable_shards}
    if len(devs) != mesh.size:
        raise AssertionError(f"shards landed on devices {sorted(devs)}")
    _say(f"four-chips: exchange mode {sp.spec.mode}, transfer fraction "
         f"{sp.transfer_fraction:.4f}")

    rng = np.random.default_rng(seed + 3)
    xc = jnp.asarray(rng.standard_normal(n), jnp.float32)
    # values are ones (the build default), so A|x| is the |A||x| bound;
    # float32 products on both paths, so one bound covers both
    with jax.default_matmul_precision("float32"):
        got = np.asarray(sp.matvec(xc), np.float64)
        want = np.asarray(plan.matvec(xc), np.float64)
        scale = np.asarray(plan.matvec(jnp.abs(xc)), np.float64)
        worst = _check_spmv("sharded matvec", got, want, scale)
        ms = _median_ms(lambda: sp.matvec(xc))
        _say(f"four-chips: sharded matvec matches one device (worst error "
             f"{worst:.3f} of bound); smoke reading, not a benchmark: "
             f"median {ms:.3f} ms")

        # Gershgorin: |eig(A)| <= the largest row sum d, so A + 2d I has
        # its spectrum in [d, 3d] and condition number kappa <= 3
        rows, _, vals = plan.host.coo
        d = float(np.bincount(rows, weights=np.abs(vals)).max())
        b = jnp.asarray(rng.standard_normal(n), jnp.float32)
        tol, kappa = 1e-6, 3.0
        res_s = sp.solve(b, shift=2 * d, tol=tol, maxiter=200)
        res_1 = plan.solve(b, shift=2 * d, tol=tol, maxiter=200)
    xs, x1 = np.asarray(res_s.x, np.float64), np.asarray(res_1.x, np.float64)
    # each stops at relative residual <= tol, so each is within kappa * tol
    # of the exact solution; float32 rounding adds ~1e-7
    rel = float(np.linalg.norm(xs - x1) / np.linalg.norm(x1))
    if not (bool(res_s.converged) and bool(res_1.converged)) \
            or rel > 2 * kappa * tol + 1e-6:
        raise AssertionError(
            f"sharded CG: converged={bool(res_s.converged)}/"
            f"{bool(res_1.converged)}, relative difference {rel:.3e}")
    _say(f"four-chips: sharded CG matches one device (relative difference "
         f"{rel:.3e}; {int(res_s.iters)} and {int(res_1.iters)} "
         "iterations)")


# -- main ---------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded plan path on a 4-chip mesh")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {dev.platform} devices")
    if ops._interpret():
        raise SystemExit("the Pallas kernels would run in interpret mode")
    enable_compile_cache()
    _say(f"device: {dev.device_kind} x{len(devices)}")

    # ELL-BSR pads every row block to the widest one (max_nbr), and this
    # data's 64 Gaussian clusters spread each row block's neighbours over
    # its whole cluster, so the tiles grow faster than n: max_nbr 146 at
    # n = 2^17 (2.4 GB of tiles), 237 at 2^18 (7.95 GB). The Pallas SpMV
    # reads a copy of the tiles laid out as row-block panels, which at
    # 2^18 does not fit beside them in the v5e's 16 GB. 2^17 is the
    # largest power of two that runs.
    n = 1 << 17
    if args.four_chips:
        if len(devices) != 4:
            raise SystemExit(f"--four-chips needs 4 devices, found "
                             f"{len(devices)}")
        from repro.compat import make_mesh

        sharded_phase(n, make_mesh((4,), ("data",)))
    else:
        plan_phase(n)
        batch_phase()
        decode_phase(get_config("qwen2-0.5b"))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
