"""Trace reduction, work functions and the peak table, on a constructed
trace and hand counts."""

import pytest
from jax import profiler

import peaks
import tracereduce as tr
import work

# one TPU plane: a program of three ops (two overlap) then a second
# program; host: the benchmark's sync mark and a request annotation
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 40000000 }
    events { metadata_id: 2 offset_ps: 70000000 duration_ps: 20000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 10000000 duration_ps: 20000000 }
    events { metadata_id: 4 offset_ps: 20000000 duration_ps: 20000000 }
    events { metadata_id: 3 offset_ps: 45000000 duration_ps: 5000000 }
    events { metadata_id: 5 offset_ps: 70000000 duration_ps: 20000000 } }
  lines { id: 3 name: "Async XLA Ops" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 0 duration_ps: 100000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_run(11)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_run(22)" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.1 = f32[8] fusion()" } }
  event_metadata { key: 4 value { id: 4 name: "%copy.2 = f32[8] copy()" } }
  event_metadata { key: 5 value { id: 5 name: "%sort.3 = f32[8] sort()" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 5000000 duration_ps: 1000 }
    events { metadata_id: 2 offset_ps: 8000000 duration_ps: 90000000 }
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.sync" } }
  event_metadata { key: 2 value { id: 2 name: "bench.request" } }
  event_metadata { key: 3 value { id: 3 name: "other" } }
}
"""


@pytest.fixture(scope="module")
def trace():
    return tr.load(profiler.ProfileData.from_text_proto(XSPACE))


def test_loader_reads_device_ops_modules_and_benchmark_marks(trace):
    assert list(trace.ops) == ["/device:TPU:0"]
    assert len(trace.ops["/device:TPU:0"]) == 4       # async line left out
    assert [m[2] for m in trace.modules["/device:TPU:0"]] == [
        "jit_run(11)", "jit_run(22)"]
    assert [m[2] for m in trace.marks] == ["bench.sync", "bench.request"]


def test_busy_is_a_union_and_idle_gaps_cover_the_rest(trace):
    ops = trace.ops["/device:TPU:0"]
    # ops cover 10-40, 45-50, 70-90 us: 55 us of a 100 us window
    assert tr.busy_ns(ops, 0, 100_000) == 55_000
    assert tr.idle_gaps(ops, 0, 100_000) == [
        (0, 10_000), (40_000, 45_000), (50_000, 70_000), (90_000, 100_000)]
    assert tr.busy_ns(ops, 20_000, 47_000) == 22_000    # clipped


def test_modules_attributed_to_the_innermost_span(trace):
    spans = [(0, 100_000, "batch"), (5_000, 55_000, "stage1"),
             (60_000, 95_000, "fused_score_topk")]
    by = tr.module_time_by_span(trace.modules["/device:TPU:0"], spans,
                                0, 100_000)
    assert by == {"stage1": (40_000, 1), "fused_score_topk": (20_000, 1)}
    # device clock a little early: the program still belongs to its span
    skewed = [(12_000, 55_000, "stage1"), (71_000, 95_000, "fused")]
    by = tr.module_time_by_span(trace.modules["/device:TPU:0"],
                                [(0, 100_000, "batch")] + skewed, 0, 100_000)
    assert by == {"stage1": (40_000, 1), "fused": (20_000, 1)}


def test_gaps_labelled_by_host_span_and_top_ops(trace):
    ops = trace.ops["/device:TPU:0"]
    spans = [(0, 100_000, "batch"), (48_000, 75_000, "cache_fetch")]
    gaps = tr.labelled_gaps(ops, spans, 0, 100_000, n=2)
    assert gaps == [["cache_fetch", 20e-6], ["batch", 10e-6]]
    top = tr.top_ops(ops, trace.modules["/device:TPU:0"], 0, 100_000)
    assert top[0] == ["jit_run(11)/fusion.1", 25e-6]
    assert ["jit_run(22)/sort.3", 20e-6] in top


def test_clock_offset_from_sync_marks(trace):
    # the benchmark read perf_counter_ns = 1_005_000 entering the mark
    # that the trace puts at 5_000
    assert tr.clock_offset_ns(trace.marks, [1_005_000], "bench.sync") \
        == 1_000_000
    assert tr.clock_offset_ns(trace.marks, [], "bench.sync") is None


def test_work_functions_against_hand_counts():
    # 3 rows of 4 floats and 5 postings: 2*3*4 + 2*5 flops,
    # 3*4*4 + 5*8 bytes
    assert work.device_pipeline_work(3, 5, 4) == (34, 88)
    # 10 rows x 8 codes, LUTs 2 x 8 x 256 float32
    assert work.adc_work(10, 8, 2) == (80, 80 + 2 * 8 * 256 * 4)
    pk = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    # 100 flops take 1 s, 5 bytes 0.5 s: compute bound, 1 s of 2 s
    assert work.roofline_share(100, 5, 2.0, pk) == (50.0, "compute")
    # 10 flops take 0.1 s, 20 bytes 2 s: memory bound, 2 s of 4 s
    assert work.roofline_share(10, 20, 4.0, pk) == (50.0, "memory")


def test_peak_table_is_keyed_by_device_kind():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks_for("TPU v99")
