"""The trace reduction on a hand-built trace whose answers are known."""
import jax
import pytest

import xplane

# Window 1,000-11,000 ns.  TPU:0's op line: a loop %while.3 at 1,000-4,500
# whose body ran %fusion.1 (1,000-2,000) and the kernel %knn_tile_topk.7
# (2,000-4,000); the kernel again at 6,000-7,000; %fusion.2 at
# 10,000-12,000, cut by the window.  Its programs: jit_brute at
# 1,000-8,000, jit_other at 9,500-12,000.  TPU:1 runs one op of 5,000 ns.
# Host: bench.query spans 1,000-5,500 and 5,600-11,000; a dispatch span
# sits inside the second at 7,500-9,000.  Another thread's long span is
# not the benchmark's.
KERNEL = ("%knn_tile_topk.7 = (f32[8,128,16]{2,1,0}) custom-call(f32[128,18]"
          "{1,0} %p), custom_call_target=\\\"tpu_custom_call\\\"")
TEXT = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 5 offset_ps: 0 duration_ps: 3500000 }
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 9000000 duration_ps: 2000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 0 duration_ps: 7000000 }
    events { metadata_id: 6 offset_ps: 8500000 duration_ps: 2500000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion()" } }
  event_metadata { key: 2 value { id: 2 name: "KERNEL" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.2 = f32[8]{0} fusion()" } }
  event_metadata { key: 4 value { id: 4 name: "jit_brute(123)" } }
  event_metadata { key: 5 value { id: 5 name: "%while.3 = (s32[]) while()" } }
  event_metadata { key: 6 value { id: 6 name: "jit_other(9)" } }
}
planes { id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.9 = f32[8]{0} fusion()" } }
}
planes { id: 3 name: "/host:CPU"
  lines { id: 1 name: "other thread" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 0 duration_ps: 20000000 } }
  lines { id: 2 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 4500000 }
    events { metadata_id: 2 offset_ps: 4600000 duration_ps: 5400000 }
    events { metadata_id: 3 offset_ps: 6500000 duration_ps: 1500000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.query" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(engine)" } }
  event_metadata { key: 4 value { id: 4 name: "other.work" } }
}
""".replace("KERNEL", KERNEL)


def reduce(text=TEXT):
    return xplane.reduce(jax.profiler.ProfileData.from_text_proto(text))


@pytest.fixture(scope="module")
def summary():
    return reduce()


def test_window_and_busy(summary):
    assert summary.window_s == pytest.approx(10_000e-9)
    # TPU:0 busy 1,000-4,500, 6,000-7,000 and 10,000-11,000 = 5,500 ns;
    # TPU:1 busy 5,000 ns; the mean over chips is 5,250 ns.
    assert summary.busy_s == pytest.approx(5_250e-9)


def test_kernel_time_counts_the_kernel_ops(summary):
    assert summary.kernel_s(r"^%knn_tile_topk\b") == pytest.approx(3_000e-9)
    assert summary.kernel_s(r"^%fusion") == pytest.approx(2_000e-9)
    assert summary.kernel_s(r"no_such_kernel") == 0.0
    op = summary.kernel(r"^%knn_tile_topk\b")[0]
    assert "f32[128,18]" in op.text


def test_idle_gaps_named_by_innermost_host_span(summary):
    # Holes on TPU:0: 4,500-6,000 (midpoint under bench.query) and
    # 7,000-10,000 (midpoint 8,500 inside the dispatch span).
    assert summary.gaps == [
        ("PjitFunction(engine)", pytest.approx(3_000e-9)),
        ("bench.query", pytest.approx(1_500e-9)),
    ]


def test_breakdown_lists_innermost_ops_by_program(summary):
    b = summary.breakdown()
    assert [n for n, _ in b["device_ops"]] == [
        "jit_brute/%knn_tile_topk.7", "jit_brute/%fusion.1",
        "jit_other/%fusion.2"]
    assert [s for _, s in b["device_ops"]] == pytest.approx(
        [3_000e-9, 1_000e-9, 1_000e-9])           # %fusion.2 cut at 11,000
    assert len(b["idle_gaps"]) == 2


def test_trace_without_window_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        reduce(TEXT.replace('"bench.window"', '"elsewhere"'))
