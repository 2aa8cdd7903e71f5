"""The program's spans (``repro.spans``) in a profiler trace.

A tiny plan build and a tiny decode service run under
``jax.profiler.trace``; the trace is read back with ``ProfileData``, as the
benchmark reads it, and the ``repro/`` spans must nest at the layer
boundaries they name, with their counts as event stats.
"""
import dataclasses

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import api
from repro.configs import reduced_config
from repro.configs.base import ClusterKVConfig
from repro.models import model_api
from repro.serve import ClusterKVEngine
from repro.train.serve_loop import Request

MAX_SEQ = 128
PROMPTS = [20, 40, 30]          # three admissions into two slots
ADMIT_CHILDREN = {"admit.prefill", "admit.first_token"}
PLAN_ADMIT_CHILDREN = ADMIT_CHILDREN | {"admit.kv_out", "admit.plans",
                                        "admit.kv_in"}


@dataclasses.dataclass
class Span:
    name: str                   # without the ``repro/`` prefix
    line: tuple
    start: int
    end: int
    stats: dict

    def holds(self, other: "Span") -> bool:
        return (other is not self and other.line == self.line
                and self.start <= other.start and other.end <= self.end)


def traced(trace_dir, fn):
    """Run ``fn`` under the profiler, with the host options the benchmark
    uses, and return its result and the ``repro/`` spans of the trace."""
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level, opts.python_tracer_level = 1, 0
    with jax.profiler.trace(str(trace_dir), profiler_options=opts):
        out = fn()
    path = sorted(trace_dir.rglob("*.xplane.pb"))[-1]
    spans = [Span(ev.name[len("repro/"):], (plane.name, line.name),
                  ev.start_ns, ev.end_ns, dict(ev.stats))
             for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for ev in line.events
             if ev.name.startswith("repro/")]
    return out, spans


def named(spans, name):
    return [s for s in spans if s.name == name]


def children(spans, parent):
    return {s.name for s in spans if parent.holds(s)}


@pytest.fixture(scope="module")
def service_cfg():
    cfg = reduced_config("qwen2-0.5b").with_(
        dtype="float32",
        clusterkv=ClusterKVConfig(enabled=True, block_q=32, block_k=32,
                                  blocks_per_query=8, decode_clusters=8))
    params, _ = model_api.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def serve(service_cfg, tmp_path_factory):
    """``serve(mode)``: a tiny service in ``mode`` that served ``PROMPTS``
    under the profiler, and its spans (each mode runs once)."""
    cfg, params = service_cfg
    runs = {}

    def run(mode):
        if mode not in runs:
            eng = ClusterKVEngine(cfg, params, slots=2, max_seq=MAX_SEQ,
                                  prefill_bucket=32, mode=mode)
            rng = np.random.default_rng(3)
            for i, n in enumerate(PROMPTS):
                eng.submit(Request(rid=i, max_new=3, tokens=rng.integers(
                    1, cfg.vocab, n).astype(np.int32)))
            _, spans = traced(tmp_path_factory.mktemp(mode), eng.run)
            runs[mode] = eng, spans
        return runs[mode]
    return run


def test_build_holds_knn_order_tiles_and_grow(tmp_path):
    x = np.random.default_rng(0).standard_normal((200, 8)).astype(np.float32)
    plan, spans = traced(tmp_path, lambda: api.build_plan(
        x, k=4, bs=8, sb=2, backend="bsr", capacity=256))
    assert plan.n == 256
    (build,) = named(spans, "build")
    assert build.stats == {"n": 200, "k": 4}
    assert children(spans, build) == {"build.knn", "build.order",
                                      "build.tiles", "build.grow"}
    (grow,) = named(spans, "build.grow")
    assert grow.stats == {"capacity": 256}


@pytest.mark.parametrize("mode", ["plan", "percall"])
def test_one_admission_span_per_request(serve, mode):
    eng, spans = serve(mode)
    admits = named(spans, "admit")
    assert len(admits) == len(PROMPTS)
    assert sorted(a.stats["rid"] for a in admits) == list(range(
        len(PROMPTS)))
    assert sorted(a.stats["blen"] for a in admits) == [32, 32, 64]
    want = PLAN_ADMIT_CHILDREN if eng.mode == "plan" else ADMIT_CHILDREN
    for a in admits:
        assert children(spans, a) >= want, a.stats


def test_admission_plans_hold_one_build_per_layer_and_head(serve):
    eng, spans = serve("plan")
    plans = named(spans, "admit.plans")
    assert len(plans) == len(PROMPTS)
    for p in plans:
        assert p.stats == {"layers": eng.L, "dim": eng.dh}
        builds = [s for s in named(spans, "build") if p.holds(s)]
        assert len(builds) == eng.L * eng.Hkv
        # one stacking of each layer's member plans into its batch
        stacks = [s for s in named(spans, "build.stack") if p.holds(s)]
        assert [s.stats for s in stacks] == [{"members": eng.Hkv}] * eng.L
        for b in builds:
            # capacity max_seq: every member grows into the shared spec
            assert children(spans, b) == {"build.knn", "build.order",
                                          "build.tiles", "build.grow"}


def test_kv_out_counts_the_fetched_bytes(serve):
    eng, spans = serve("plan")
    by_rid = {a.stats["rid"]: a for a in named(spans, "admit")}
    for kv in named(spans, "admit.kv_out"):
        (admit,) = [a for a in by_rid.values() if a.holds(kv)]
        blen = admit.stats["blen"]
        # k and v, float32, one request's (L, Hkv, blen, dh)
        assert kv.stats["bytes"] == 2 * eng.L * eng.Hkv * blen * eng.dh * 4


def test_one_decode_span_per_tick(serve):
    eng, spans = serve("plan")
    ticks = named(spans, "decode")
    assert len(ticks) == eng.ticks > 0
    for t in ticks:
        assert 1 <= t.stats["active"] <= eng.slots
        assert children(spans, t) == {"decode.dispatch", "decode.claim"}
    assert not any(t.holds(a) for t in ticks for a in named(spans, "admit"))
