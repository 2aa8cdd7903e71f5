"""Autotuning: SpMV backend selection for plans + attention budget tuning.

``tune_backend`` resolves ``backend="auto"`` for ``repro.api`` plans. Since
the analytic cost model landed (``core.costmodel``) the stopwatch no longer
decides: backends are ranked by the model's calibrated predicted seconds on
the plan's structural shape, and probes run only as *calibration* — one
measurement per backend (globally memoized in ``_CALIB`` as the
measured/modeled ratio), after which every decision is pure arithmetic on
the hardware config. Changing the hardware config (``costmodel
.set_hardware`` / ``REPRO_HW_CONFIG``) plus ``clear_tune_memo()`` therefore
changes decisions without re-probing anything. Memoized decisions store the
full machine-readable ranking report (``schema repro.cost/v1``).

The attention-budget half below reuses the paper's γ-score idea to size
the cluster-sparse attention budget.

Patch-density-guided autotuning of the cluster-sparse attention budget.

The paper's γ-score measures how much interaction mass concentrates into
dense patches under an ordering (§2.3). The same quantity tunes the LM
backend: after cluster-sorting keys, the centroid score mass captured by
the top-B key tiles per query tile is a direct coverage estimate — pick
the smallest B whose estimated coverage exceeds the target. Models with
strongly clustered keys (high patch density) get small B (fast); diffuse
ones automatically fall back toward dense attention.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ClusterKVConfig
from repro.core import clusterkv as ckv
from repro.core import costmodel
from repro.core.registry import NotApplicable, backend_names, \
    get_backend, get_batched_backend


# ---------------------------------------------------------------------------
# SpMV backend autotuning (resolves plan backend="auto")
# ---------------------------------------------------------------------------

# structural memo of auto decisions, keyed by (shape_key, true nnz,
# charge ndim, backend set, device_count) — everything that determines
# which kernels compile plus the csr path's actual edge count; values
# are the full machine-readable ranking reports
# (costmodel.rank_backends envelopes) so a memo hit replays both the
# winner and the model's predicted seconds.
_TUNE_MEMO: Dict[tuple, dict] = {}

# calibration constants: backend name (or "batch:<name>") -> measured /
# modeled seconds ratio from ONE probe. inf marks a backend that was
# skipped (interpret-mode pallas on the CPU, a NotApplicable refusal, a
# wrong answer) — excluded from rankings. This is the only place the
# stopwatch touches the decision.
_CALIB: Dict[str, float] = {}


def clear_tune_memo() -> None:
    """Drop memoized auto-backend decisions (tests / fresh measurements).
    Calibration constants survive — re-decisions stay probe-free."""
    _TUNE_MEMO.clear()


def clear_calibration() -> None:
    """Drop probe calibration constants (forces fresh measurement)."""
    _CALIB.clear()


def _skip_interpret(fn) -> bool:
    """True when ``fn`` is a Pallas backend currently running interpret
    mode — a full compile + timed Python-loop runs per probe, and it can
    never win on this hardware."""
    gate = getattr(fn, "interpret_only", None)
    return bool(callable(gate) and gate())


def probe_backends(plan, x: Optional[jax.Array] = None,
                   backends: Optional[Iterable[str]] = None,
                   warmup: int = 1, iters: int = 3,
                   atol: float = 1e-3,
                   include_interpret: bool = False) -> Dict[str, float]:
    """Median wall time (s) per registered backend on the plan's shapes.

    Backends that refuse with :class:`NotApplicable` (missing COO, 1-D
    only) or disagree with the flat block path by more than ``atol``
    max-abs are skipped — a fast-but-wrong backend must never win the
    autotune. Any other exception is a fault and propagates: a kernel
    that fails to compile on the chip must not be quietly replaced by an
    XLA path. Interpret-mode Pallas backends (the CPU) are skipped by
    default (a compile + timed interpreter runs that can never win);
    pass ``include_interpret=True`` to time them anyway (tests).
    """
    if x is None:
        x = jnp.asarray(
            np.random.default_rng(0).standard_normal(plan.n), jnp.float32)
    names = tuple(backends) if backends is not None else backend_names()
    ref = np.asarray(jax.block_until_ready(get_backend("bsr")(plan, x)))
    times: Dict[str, float] = {}
    for name in names:
        fn = get_backend(name)
        if not include_interpret and _skip_interpret(fn):
            continue
        try:
            y = np.asarray(jax.block_until_ready(fn(plan, x)))
            if np.abs(y - ref).max() > atol:
                continue
            for _ in range(warmup):
                jax.block_until_ready(fn(plan, x))
            ts = []
            for _ in range(iters):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(plan, x))
                ts.append(time.perf_counter() - t0)
            times[name] = float(np.median(ts))
        except NotApplicable:
            continue
    return times


def _calibrate(names: Iterable[str], feat, plan, x, *,
               interpret: bool) -> None:
    """Probe every backend in ``names`` that has no calibration constant
    yet and store measured/modeled ratios in ``_CALIB``. A backend whose
    probe refuses, disagrees, or is interpret-mode Pallas calibrates to
    inf (excluded from rankings until ``clear_calibration``)."""
    missing = [n for n in names if n not in _CALIB]
    if not missing:
        return
    probed = probe_backends(plan, x, missing)
    for name in missing:
        meas = probed.get(name)
        if meas is None:
            _CALIB[name] = float("inf")
            continue
        model_s = costmodel.backend_cost(feat, name,
                                         interpret=interpret)["seconds"]
        _CALIB[name] = meas / model_s if model_s > 0 else float("inf")


def tune_backend(plan, x: Optional[jax.Array] = None,
                 backends: Optional[Iterable[str]] = None,
                 device_count: Optional[int] = None
                 ) -> Tuple[str, Dict[str, float]]:
    """Resolve ``backend="auto"`` for ``plan`` from the analytic model.

    Returns ``(name, calibrated predicted seconds per backend)``; the
    winner is the argmin of the returned dict. Falls back to ``"bsr"``
    when nothing is rankable (tracer plans, every probe refused).

    Probes are demoted to calibration: the first time a backend is seen
    it is timed once and the measured/modeled ratio memoized globally
    (``_CALIB``); every subsequent decision — any shape, any hardware
    config — is model arithmetic. ``clear_tune_memo()`` plus a changed
    hardware config therefore re-decides without re-probing.

    Device-count-aware: on a >=2-device mesh the ``dist`` path wins
    whenever it (a) calibrated healthy and (b) the exchange model prices
    its halo strictly under replication on the configured interconnect.
    Wall-clock probes on a single-host mesh (forced virtual devices,
    shared memory) mismeasure collective cost, so the model — not the
    stopwatch — decides between per-device paths; ``"dist"`` appears in
    the returned dict only when it is the decision.

    Single-device decisions are memoized on the plan's structural key
    (``PlanSpec.shape_key`` + true nnz + charge ndim + backend set); memo
    values are
    the full ranking reports. Multi-device decisions are NOT memoized:
    the dist-vs-replicate call depends on the plan's actual block
    structure (the halo analysis), which two same-shaped plans can
    disagree on.
    """
    ndev = device_count if device_count is not None else jax.device_count()
    names = tuple(backends) if backends is not None else backend_names()
    ndim = x.ndim if x is not None else 1
    concrete = plan.bsr is not None \
        and not isinstance(plan.bsr.vals, jax.core.Tracer)
    if not concrete:
        return "bsr", {}
    # true edge count (the csr path's work); plans built from_bsr have no
    # COO and fall back to the dense-equivalent estimate
    coo = getattr(plan.host, "coo", None)
    nnz = int(len(coo[0])) if coo is not None else None
    key = None
    if ndev < 2:
        key = (plan.spec.shape_key, nnz, ndim, names, ndev)
        hit = _TUNE_MEMO.get(key)
        if hit is not None:
            return hit["winner"], dict(hit["predicted_s"])
    f = x.shape[-1] if (x is not None and x.ndim == 2) else 1
    feat = costmodel.plan_features(plan.spec.shape_key, f=f, nnz=nnz)
    interp = _skip_interpret(get_backend("pallas")) \
        if "pallas" in names else False
    local = tuple(n for n in names if n != "dist")
    _calibrate(local, feat, plan, x, interpret=interp)
    if ndev >= 2 and "dist" in names and "dist" not in _CALIB:
        # dist needs a real mesh to calibrate; a refusal (e.g. (n, f)
        # charges) marks it non-viable here
        _calibrate(("dist",), feat, plan, x, interpret=False)
    report = costmodel.rank_backends(
        feat, local, calibration=_CALIB, interpret=interp, n_dev=ndev)
    winner = report["winner"] or "bsr"
    times = dict(report["predicted_s"])
    if ndev >= 2 and "dist" in names \
            and _CALIB.get("dist", float("inf")) != float("inf") \
            and not isinstance(plan.bsr.col_idx, jax.core.Tracer):
        from repro.core.shardplan import analyze_shards

        spec, _ = analyze_shards(plan.bsr, ndev)
        halo_s = costmodel.exchange_cost(spec.transfer_blocks, plan.bsr.bs)
        ag_s = costmodel.exchange_cost(spec.allgather_blocks, plan.bsr.bs)
        if halo_s is not None and ag_s is not None and halo_s < ag_s:
            dist_s = costmodel.backend_cost(
                feat, "dist", n_dev=ndev,
                exchange_blocks=spec.transfer_blocks)["seconds"]
            times["dist"] = _CALIB["dist"] * dist_s
            report = dict(report, winner="dist", predicted_s=times)
            winner = "dist"
    if key is not None:
        report = dict(report, winner=winner)
        _TUNE_MEMO[key] = report
    return winner, times


def tune_batch_backend(batch, x: Optional[jax.Array] = None,
                       backends: Optional[Iterable[str]] = None,
                       warmup: int = 1, iters: int = 3,
                       atol: float = 1e-3) -> Tuple[str, Dict[str, float]]:
    """One shared backend decision for a whole ``api.PlanBatch``.

    Same analytic-first shape as ``tune_backend``, but calibration runs
    the *batched* kernel itself (``api._batch_apply_kernel``) — the
    single-plan calibration does not transfer (batching changes the
    gather shapes and dispatch count), so batch backends calibrate under
    ``"batch:<name>"`` keys. Backends that refuse (:class:`NotApplicable`)
    or disagree with the batched ``bsr`` path calibrate to inf; any other
    exception propagates. The decision is memoized
    on ``(batch shape_key, B, charge ndim, backend set)`` with the full
    ranking report: spec-identical batches — every construction in a
    serving loop — tune once.
    """
    from repro import api

    names = (tuple(backends) if backends is not None
             else tuple(n for n in api._BATCHED_BACKENDS
                        if n in backend_names()))
    ndim = (x.ndim - 1) if x is not None else 1
    key = ("batch", batch.spec.shape_key, batch.batch, ndim, names)
    hit = _TUNE_MEMO.get(key)
    if hit is not None:
        return hit["winner"], dict(hit["predicted_s"])
    f = x.shape[-1] if (x is not None and x.ndim == 3) else 1
    feat = costmodel.plan_features(batch.spec.shape_key, f=f,
                                   batch=batch.batch)
    interp = False
    pfn = get_batched_backend("pallas") if "pallas" in names else None
    if pfn is not None:
        interp = _skip_interpret(pfn)
    missing = [n for n in names if ("batch:" + n) not in _CALIB]
    if missing:
        if x is None:
            x = jnp.asarray(np.random.default_rng(0).standard_normal(
                (batch.batch, batch.capacity)), jnp.float32)
        ref = np.asarray(jax.block_until_ready(api._batch_apply_kernel(
            batch.spec, batch.data, x, "bsr", "apply")))
        for name in missing:
            ckey = "batch:" + name
            bfn = get_batched_backend(name)
            if bfn is not None and _skip_interpret(bfn):
                _CALIB[ckey] = float("inf")
                continue
            try:
                y = np.asarray(jax.block_until_ready(
                    api._batch_apply_kernel(
                        batch.spec, batch.data, x, name, "apply")))
                if np.abs(y - ref).max() > atol:
                    _CALIB[ckey] = float("inf")
                    continue
                for _ in range(warmup):
                    jax.block_until_ready(api._batch_apply_kernel(
                        batch.spec, batch.data, x, name, "apply"))
                ts = []
                for _ in range(iters):
                    t0 = time.perf_counter()
                    jax.block_until_ready(api._batch_apply_kernel(
                        batch.spec, batch.data, x, name, "apply"))
                    ts.append(time.perf_counter() - t0)
                meas = float(np.median(ts))
                model_s = costmodel.backend_cost(
                    feat, name, interpret=interp)["seconds"]
                _CALIB[ckey] = meas / model_s if model_s > 0 \
                    else float("inf")
            except NotApplicable:
                _CALIB[ckey] = float("inf")
    cal = {n: _CALIB.get("batch:" + n, 1.0) for n in names}
    report = costmodel.rank_backends(feat, names, calibration=cal,
                                     interpret=interp)
    winner = report["winner"] or "bsr"
    report = dict(report, winner=winner)
    _TUNE_MEMO[key] = report
    return winner, dict(report["predicted_s"])


def coverage_curve(q: jax.Array, k: jax.Array, cfg: ClusterKVConfig
                   ) -> jax.Array:
    """Estimated softmax-mass coverage as a function of B (tiles kept).

    q (B,Hq,S,dh), k (B,Hkv,S,dh). Returns (nkb,) monotone curve: entry i =
    mean over query tiles of the softmax mass (at tile granularity)
    captured by the top-(i+1) key tiles under the cluster ordering.
    """
    b, hq, s, dh = q.shape
    hkv = k.shape[1]
    bq = min(cfg.block_q, s)
    bk = min(cfg.block_k, s)
    nqb, nkb = s // bq, s // bk

    perm = ckv.cluster_perm(k, d=cfg.embed_dim)
    k_s = jnp.take_along_axis(k, perm[..., None], axis=-2)
    cent = ckv.block_centroids(k_s, bk)                    # (B,Hkv,nkb,dh)
    qc = q.reshape(b, hkv, hq // hkv, nqb, bq, dh).mean(axis=(2, 4))
    scores = jnp.einsum("bhqd,bhkd->bhqk", qc.astype(jnp.float32),
                        cent.astype(jnp.float32)) / jnp.sqrt(float(dh))
    # tile-granularity softmax mass, sorted descending per query tile
    w = jax.nn.softmax(scores * bk, axis=-1)   # bk: tiles hold bk keys
    w_sorted = -jnp.sort(-w, axis=-1)
    return jnp.mean(jnp.cumsum(w_sorted, axis=-1), axis=(0, 1, 2))


def tune_blocks_per_query(q: jax.Array, k: jax.Array,
                          cfg: ClusterKVConfig,
                          target_coverage: float = 0.95
                          ) -> Tuple[ClusterKVConfig, float]:
    """Smallest B reaching the target estimated coverage (plus the always-
    kept local window). Returns (updated config, achieved coverage)."""
    curve = coverage_curve(q, k, cfg)
    nkb = curve.shape[0]
    b_needed = int(jnp.argmax(curve >= target_coverage)) + 1
    if float(curve[-1]) < target_coverage:
        b_needed = nkb
    b_needed = min(b_needed + cfg.local_window_blocks, nkb)
    return (dataclasses.replace(cfg, blocks_per_query=b_needed),
            float(curve[min(b_needed, nkb) - 1]))
