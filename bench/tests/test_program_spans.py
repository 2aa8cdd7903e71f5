"""The reduction of the program's spans on a small hand-written trace."""
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from bench import program_spans, trace

US = 1_000_000                  # picoseconds


def _line(line_id, name, events):
    body = " ".join(f"events {{ metadata_id: {m} offset_ps: {int(a * US)} "
                    f"duration_ps: {int((b - a) * US)} }}"
                    for m, a, b in events)
    return (f'lines {{ id: {line_id} name: "{name}" timestamp_ns: 0 '
            f"{body} }}")


def _meta(names):
    return " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{n}" }} }}' for i, n in names.items())


# times in microseconds. The window is [0, 20). The device runs [2, 4)
# and [14, 15). Host thread 1: a bench build over a program build with
# its kNN, ordering and tiles; a bench admitting step over an admission
# whose plans hold one more build; a program span after the window.
# Host thread 2: one kNN span of its own, overlapping the first build.
HOST = {1: "bench/window", 2: "bench/build", 3: "repro/build",
        4: "repro/build.knn", 5: "repro/build.order", 6: "repro/build.tiles",
        7: "bench/step.admit", 8: "repro/admit", 9: "repro/admit.prefill",
        10: "repro/admit.plans"}
TEXT = f"""
planes {{
  id: 1 name: "/device:TPU:0"
  {_line(1, "XLA Ops", [(1, 2, 4), (1, 14, 15)])}
  {_meta({1: "fusion.1"})}
}}
planes {{
  id: 2 name: "/host:CPU"
  {_line(1, "python", [
      (1, 0, 20), (2, 1, 10), (3, 1.5, 9.5), (4, 2, 4), (5, 4.5, 7),
      (6, 7, 9), (7, 11, 19), (8, 11.5, 18.5), (9, 12, 13), (10, 13, 17),
      (3, 13.5, 16.5), (4, 14, 15), (8, 20.5, 21)])}
  {_line(2, "worker", [(4, 3, 8)])}
  {_meta(HOST)}
}}
"""


@pytest.fixture(scope="module")
def profile():
    return ProfileData.from_text_proto(TEXT)


@pytest.fixture(scope="module")
def spans(profile):
    return program_spans.reduce_planes(profile.planes)


@pytest.mark.parametrize("name,count,seconds,self_seconds", [
    # 8 us with 6.5 us of children, and 3 us holding a 1 us kNN
    ("repro/build", 2, 11, 3.5),
    # two on the first thread and one on the second, none with children
    ("repro/build.knn", 3, 8, 8),
    ("repro/build.order", 1, 2.5, 2.5),
    ("repro/build.tiles", 1, 2, 2),
    # 7 us over a 1 us prefill and 4 us of plans; the span after the
    # window is not counted
    ("repro/admit", 1, 7, 2),
    ("repro/admit.plans", 1, 4, 1),
    ("repro/admit.prefill", 1, 1, 1),
])
def test_span_totals(spans, name, count, seconds, self_seconds):
    t = spans.totals[name]
    assert t.count == count
    assert t.seconds == pytest.approx(seconds * 1e-6)
    assert t.self_seconds == pytest.approx(self_seconds * 1e-6)
    assert spans.count(name) == count
    assert spans.seconds(name) == pytest.approx(seconds * 1e-6)


@pytest.mark.parametrize("name,ms", [
    ("admit_prefill_ms", 0.001),
    ("admit_plans_ms", 0.004),
    ("admit_kv_ms", 0.0),                  # no K/V span in this trace
    ("build_order_ms", 0.00125),           # 2.5 us over two builds
    ("build_tiles_ms", 0.001),
    ("decode_tick_ms", None),              # no tick to count
    ("host_claim_ms", None),
])
def test_metrics(spans, name, ms):
    got = spans.metric(name)
    assert got == (None if ms is None else pytest.approx(ms))


def test_only_program_spans_are_totalled(spans):
    assert all(n.startswith("repro/") for n in spans.totals)
    assert spans.count("repro/other") == 0 and spans.seconds("x") == 0.0


def test_gaps_named_by_the_innermost_program_span(spans):
    # idle [0, 2) (middle 1: only the bench build has started), [4, 14)
    # (middle 9: inside the program build, which the bench build holds),
    # [15, 20) (middle 17.5: inside the admission)
    assert spans.gaps == [("repro/build", pytest.approx(10e-6)),
                          ("repro/admit", pytest.approx(5e-6)),
                          ("bench/build", pytest.approx(2e-6))]


def test_the_bench_reduction_is_unchanged(profile):
    s = trace.summarize_planes(profile.planes)
    assert s.calls("bench/build") == 1 and s.calls("bench/step.admit") == 1
    assert set(s.spans) == {"bench/build", "bench/step.admit"}
    assert s.busy_s == pytest.approx(3e-6)
    assert s.gaps == [("bench/build", pytest.approx(10e-6)),
                      ("bench/step.admit", pytest.approx(5e-6)),
                      ("bench/build", pytest.approx(2e-6))]


def test_lines(spans):
    lines = spans.lines()
    assert len(lines) == len(spans.totals)
    assert ("span repro/admit n=1 ms/call=0.0070 self ms/call=0.0020"
            in lines)


def test_a_trace_without_the_window_is_refused(profile):
    with pytest.raises(ValueError, match="no host span"):
        program_spans.reduce_planes(profile.planes, window="bench/other")


def test_a_recorded_v5e_trace_has_no_program_spans():
    """A trace from before the program's spans: nothing to total, and the
    gaps the bench reduction gives."""
    path = Path(__file__).parent / "data" / "tiny_v5e.xplane.pb"
    spans = program_spans.reduce(path)
    assert spans.totals == {} and spans.lines() == []
    assert spans.gaps == trace.summarize(path).gaps


BUILD = {"repro/build", "repro/build.knn", "repro/build.order",
         "repro/build.tiles"}


@pytest.mark.parametrize("cell,spans", [
    ("gauss64-128d.build-stream", BUILD),
    # every admission builds its plans at the service's capacity
    ("qwen2-0.5b.chat-open", BUILD | {
        "repro/build.grow", "repro/build.stack", "repro/admit", "repro/admit.prefill",
        "repro/admit.kv_out", "repro/admit.plans", "repro/admit.kv_in",
        "repro/admit.first_token", "repro/decode", "repro/decode.dispatch",
        "repro/decode.claim"}),
])
def test_the_spans_tool_at_the_rehearsal_size(cell, spans, tmp_path):
    """``bench/tools/spans.py`` on the CPU: one untraced and one traced
    window, the traced one with a line per program span."""
    import json
    import os
    import subprocess
    import sys
    root = Path(__file__).resolve().parents[2]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    out = subprocess.run(
        [sys.executable, "bench/tools/spans.py", "--workload", cell,
         "--seconds", "1", "--seeds", str(2**31 + 11), "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.splitlines()
    runs = [json.loads(x) for x in lines if x.startswith("{")]
    assert [r["traced"] for r in runs] == [0, 1]
    assert all(r["window_compiles"] == 0 for r in runs)
    named = {x.split()[2] for x in lines if x.startswith("bench: span ")}
    assert named == spans
