"""The trace reduction on a small hand-written trace."""
import pytest
from jax.profiler import ProfileData

from bench import trace

# one device, one host thread; times in ps from line starts at 1000 ns.
# window [1000, 11000) ns; ops [2000, 4000), [3000, 5000) overlapping,
# [8000, 9000), and [10500, 12000) cut at the window's end; one program
# over the first two ops, one over the last two; a matvec span over the
# first half, a build span over the second
TEXT = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 7000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 9500000 duration_ps: 1500000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 1000000 duration_ps: 3000000 }
    events { metadata_id: 5 offset_ps: 7000000 duration_ps: 4000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "dot.2" } }
  event_metadata { key: 3 value { id: 3 name: "sort.3" } }
  event_metadata { key: 4 value { id: 4 name: "jit_knn_graph(12)" } }
  event_metadata { key: 5 value { id: 5 name: "jit_spmv(7)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 500000 duration_ps: 5000000 }
    events { metadata_id: 3 offset_ps: 5500000 duration_ps: 4500000 }
    events { metadata_id: 4 offset_ps: 6000000 duration_ps: 100000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench/window" } }
  event_metadata { key: 2 value { id: 2 name: "bench/matvec" } }
  event_metadata { key: 3 value { id: 3 name: "bench/build" } }
  event_metadata { key: 4 value { id: 4 name: "PjitFunction(x)" } }
}
"""


@pytest.fixture(scope="module")
def summary():
    return trace.summarize_planes(ProfileData.from_text_proto(TEXT).planes)


def test_busy_is_the_union_inside_the_window(summary):
    # [2000, 5000) + [8000, 9000) + [10500, 11000) = 4500 ns
    assert summary.window_s == pytest.approx(10e-6)
    assert summary.busy_s == pytest.approx(4.5e-6)
    assert summary.idle_percent() == pytest.approx(55.0)


def test_program_time_by_module_name(summary):
    assert summary.module_seconds("jit_knn_graph") == pytest.approx(3e-6)
    # clipped at the window's end: [8000, 11000)
    assert summary.module_seconds("jit_spmv") == pytest.approx(3e-6)
    assert summary.module_seconds("jit_other") == 0.0


def test_ops_and_spans(summary):
    assert summary.ops["fusion.1"] == pytest.approx(3e-6)
    assert summary.ops["sort.3"] == pytest.approx(0.5e-6)
    assert summary.calls("bench/matvec") == 1
    assert summary.calls("bench/build") == 1


def test_gaps_named_by_the_innermost_span(summary):
    # idle: [1000, 2000) in matvec, [5000, 8000) across both spans (the
    # middle, 6500, lies in build), [9000, 10500) in build
    assert summary.gaps == [("bench/build", pytest.approx(3e-6)),
                            ("bench/build", pytest.approx(1.5e-6)),
                            ("bench/matvec", pytest.approx(1e-6))]
    out = summary.breakdown()
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(3e-6)]
    assert len(out["idle_gaps"]) == 3


def test_union_length():
    assert trace.union_length([(5, 7), (0, 2), (1, 3)]) == (5, [[0, 3],
                                                                [5, 7]])
    assert trace.union_length([]) == (0, [])


def test_a_trace_without_the_window_is_refused():
    planes = ProfileData.from_text_proto(TEXT).planes
    with pytest.raises(ValueError, match="no host span"):
        trace.summarize_planes(planes, window="bench/other")


def test_a_recorded_v5e_trace():
    """Two tiny jitted programs run twice each on one v5e, 10 ms apart,
    inside the window span (recorded with ``jax.profiler.trace``)."""
    from pathlib import Path
    path = Path(__file__).parent / "data" / "tiny_v5e.xplane.pb"
    s = trace.summarize(path)
    assert s.window_s == pytest.approx(0.024, rel=0.05)
    # busy is the union of the ops, which the two programs enclose
    assert 0 < s.busy_s <= s.module_seconds("jit__lambda") * (1 + 1e-9)
    assert s.idle_percent() > 99.0
    assert "sort.6" in s.ops and "copy-start" in s.ops
    assert s.gaps[0][0] == "host" and s.gaps[0][1] > 0.01
