"""ClusterKV decode service vs the per-call Morton-sort decode path.

The service thesis: at serving time the cluster ordering of a session's
keys is PLAN STATE, not something to re-derive per token. The per-call
clusterkv decode (``mode="percall"``) re-sorts every slot's cache and
recomputes every centroid inside each decode step; the service
(``mode="plan"``) builds each session's per-layer ``PlanBatch`` once at
admission and insert-streams generated keys into it, so a decode tick is
one scatter + one tile refresh + the sparse attend.

Both modes run the SAME continuous-batching engine over the same request
trace: ``SLOTS`` concurrent sessions with churn (more requests than
slots, mixed prompt lengths, so slots retire and backfill mid-run).

GATES (ISSUE 6): with >= 8 concurrent sessions under churn,
  - service tokens/sec >= 3x the per-call path;
  - the service compiles exactly ONE decode kernel across all admissions
    (trace count asserted, not eyeballed).

GATE (ISSUE 9): the vectorized host claim pass (``claim_slots_batched``
over all L*B*H members, with the inserter's maintained block maxima)
is >= 5x faster than the per-member ``claim_slot`` loop it replaced, at
the serve shape (8 sessions x max_seq 8192). How a tick splits between
the decode dispatch and the host claims is read from a profiler trace
(spans ``repro/decode.dispatch`` and ``repro/decode.claim``).

  PYTHONPATH=src:. python benchmarks/run.py --only bench_serve
"""
from __future__ import annotations

import numpy as np

from repro.configs import reduced_config
from repro.configs.base import ClusterKVConfig

SLOTS = 8
N_REQ = 16            # churn: every slot retires + backfills at least once
MAX_SEQ = 8192        # percall pays O(S) sort+permute+centroids per tick;
                      # the service's decode cost is capacity-independent
MAX_NEW = 64
GATE_SPEEDUP = 3.0
GATE_CLAIM = 5.0      # batched host claim vs the per-member loop


def _requests(cfg, rng, rid0=0):
    from repro.train.serve_loop import Request

    lengths = rng.integers(128, 256, size=N_REQ)
    return [Request(rid=rid0 + i,
                    tokens=rng.integers(0, cfg.vocab, int(n)
                                        ).astype(np.int32),
                    max_new=MAX_NEW)
            for i, n in enumerate(lengths)]


def _drive(cfg, params, mode):
    """One long-lived engine per mode: the first request wave warms every
    compile, then a second wave, timed here, measures steady serving.
    Trace counters span BOTH waves — 2*N_REQ admissions must share one
    decode kernel. Returns the report and the second wave's tokens per
    second."""
    import time

    from repro.serve import ClusterKVEngine

    engine = ClusterKVEngine(cfg, params, slots=SLOTS, max_seq=MAX_SEQ,
                             prefill_bucket=256, mode=mode)
    rng = np.random.default_rng(0)
    for r in _requests(cfg, rng):
        engine.submit(r)
    engine.run()
    tokens0 = engine.tokens_out
    for r in _requests(cfg, rng, rid0=N_REQ):
        engine.submit(r)
    t0 = time.perf_counter()
    engine.run()
    wall = time.perf_counter() - t0
    return engine.report(), (engine.tokens_out - tokens0) / wall


def _claim_bench(emit) -> None:
    """ISSUE 9 gate: the stacked claim pass vs the per-member loop it
    replaced, exercised exactly as the inserter drives it (in-order
    code/alive mirrors plus maintained block maxima) under tick churn at
    the serve shape."""
    import time

    from repro.serve.streaming import (CLAIM_BLOCK, claim_slot,
                                       claim_slots_batched)

    layers, heads, ticks = 2, 2, 64
    m = layers * SLOTS * heads
    rng = np.random.default_rng(0)
    base_codes = np.sort(
        rng.integers(0, 1 << 30, (m, MAX_SEQ)).astype(np.uint64), axis=1)
    base_alive = rng.random((m, MAX_SEQ)) < 0.5
    arrivals = rng.integers(0, 1 << 30, (ticks, m)).astype(np.uint64)

    class _Host:                       # claim_slot's duck-typed host view
        __slots__ = ("pi", "codes", "alive")

    hosts = []
    for i in range(m):
        h = _Host()
        h.pi = np.arange(MAX_SEQ)
        h.codes = base_codes[i].copy()
        h.alive = base_alive[i].copy()
        hosts.append(h)
    t0 = time.time()
    loop_phys = np.zeros((ticks, m), np.int64)
    for t in range(ticks):
        for i, h in enumerate(hosts):
            p = claim_slot(h, arrivals[t, i])
            h.alive[p] = True
            h.codes[p] = arrivals[t, i]
            loop_phys[t, i] = p
    t_loop = time.time() - t0

    ci, ai = base_codes.copy(), base_alive.copy()
    bm = ci.reshape(m, -1, CLAIM_BLOCK).max(axis=2)
    rows = np.arange(m)
    t0 = time.time()
    vec_phys = np.zeros((ticks, m), np.int64)
    for t in range(ticks):
        pos = claim_slots_batched(ci, ai, arrivals[t], block_max=bm)
        ai[rows, pos] = True
        ci[rows, pos] = arrivals[t]
        blk = pos // CLAIM_BLOCK
        seg = ci[rows[:, None],
                 (blk * CLAIM_BLOCK)[:, None] + np.arange(CLAIM_BLOCK)]
        bm[rows, blk] = seg.max(axis=1)
        vec_phys[t] = pos
    t_vec = time.time() - t0

    assert (vec_phys == loop_phys).all(), (
        "batched claims diverged from the per-member claim_slot loop")
    ratio = t_loop / max(t_vec, 1e-9)
    emit(f"bench_serve/host_claim_m{m}_cap{MAX_SEQ},"
         f"{t_vec / ticks * 1e6:.0f},"
         f"loop_us={t_loop / ticks * 1e6:.0f};speedup={ratio:.1f}x")
    assert ratio >= GATE_CLAIM, (
        f"batched host claim {ratio:.2f}x < {GATE_CLAIM}x over the "
        f"per-member loop ({t_vec * 1e3:.1f}ms vs {t_loop * 1e3:.1f}ms "
        f"for {ticks} ticks x {m} members)")


def run(emit) -> None:
    import jax

    from repro.models import model_api

    # float32: the CPU-performant dtype for BOTH paths (bf16 scatter and
    # gather are emulated elementwise on CPU and would distort the ratio)
    cfg = reduced_config("qwen2-0.5b").with_(
        dtype="float32",
        clusterkv=ClusterKVConfig(enabled=True, block_q=128, block_k=128,
                                  blocks_per_query=4, decode_clusters=4))
    params, _ = model_api.init(cfg, jax.random.PRNGKey(0))

    reports, tok_s = {}, {}
    for mode in ("percall", "plan"):
        _drive(cfg, params, mode)              # warm the compile cache
        reports[mode], tok_s[mode] = _drive(cfg, params, mode)

    plan = reports["plan"]
    speedup = tok_s["plan"] / max(tok_s["percall"], 1e-9)
    for mode, rep in reports.items():
        us = 1e6 / max(tok_s[mode], 1e-9)               # us per token
        emit(f"bench_serve/{mode}_s{SLOTS}_seq{MAX_SEQ},{us:.0f},"
             f"tok_s={tok_s[mode]:.1f};ticks={rep['ticks']};"
             f"decode_traces={rep['decode_traces']}")
    emit(f"bench_serve/service_speedup,{0:.0f},"
         f"speedup={speedup:.2f}x;admits={plan['counters']['admits']};"
         f"appends={plan['insert_tiers']['appends']}")
    _claim_bench(emit)

    # ISSUE 6 acceptance gates
    assert plan["counters"]["admits"] == 2 * N_REQ and SLOTS >= 8
    assert plan["decode_traces"] == 1, (
        f"service compiled {plan['decode_traces']} decode kernels across "
        f"{2 * N_REQ} admissions; spec unification promises exactly one")
    assert plan["specs_seen"] == 1, (
        f"{plan['specs_seen']} distinct plan specs across admissions")
    assert speedup >= GATE_SPEEDUP, (
        f"plan-cached service {speedup:.2f}x < {GATE_SPEEDUP}x over the "
        f"per-call Morton-sort decode ({tok_s['plan']:.1f} vs "
        f"{tok_s['percall']:.1f} tok/s)")


if __name__ == "__main__":
    run(print)
