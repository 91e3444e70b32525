"""layers.py on a constructed trace: the XSpace reader, op scopes from
trace stats and from the op before, and the per-batch reductions against
hand counts."""

import pytest
from jax import profiler

import layers
import tracereduce

# times in us (offset_ps / 1e6). Device: program A (10-50) with a
# sparse_topk op 10-30 and a stage1 op 20-40 (they overlap; scopes from
# their metadata's stats), an op with no scope 45-50 (takes stage1 from
# the op before it); program B (70-90): a first op with no scope 70-85
# (nothing before it in B), a dense_score op 85-88 named by its own event
# stat, and a sort with no scope 88-90 (takes dense_score). Host: two
# batches (0-60, 62-100), each with a wait region.
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 40000000 }
    events { metadata_id: 2 offset_ps: 70000000 duration_ps: 20000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 10000000 duration_ps: 20000000 }
    events { metadata_id: 4 offset_ps: 20000000 duration_ps: 20000000 }
    events { metadata_id: 5 offset_ps: 45000000 duration_ps: 5000000 }
    events { metadata_id: 6 offset_ps: 70000000 duration_ps: 15000000 }
    events { metadata_id: 7 offset_ps: 85000000 duration_ps: 3000000
      stats { metadata_id: 20
              str_value: "jit(clusd_fused_adc)/dense_score/add" } }
    events { metadata_id: 8 offset_ps: 88000000 duration_ps: 2000000 } }
  lines { id: 3 name: "Steps" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 0 duration_ps: 100000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_clusd_stage1(11)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_clusd_fused_adc(22)" } }
  event_metadata { key: 3 value { id: 3 name: "%sort.1 = f32[8] sort()"
    stats { metadata_id: 20
            str_value: "jit(clusd_stage1)/sparse_topk/jit(sort)/sort" } } }
  event_metadata { key: 4 value { id: 4 name: "fusion.2"
    stats { metadata_id: 20 str_value: "jit(clusd_stage1)/stage1/dot" }
    stats { metadata_id: 21 int64_value: 7 } } }
  event_metadata { key: 5 value { id: 5 name: "copy.3" } }
  event_metadata { key: 6 value { id: 6 name: "fusion.4"
    stats { metadata_id: 22 ref_value: 23 } } }
  event_metadata { key: 7 value { id: 7 name: "add.5" } }
  event_metadata { key: 8 value { id: 8 name: "sort.6" } }
  stat_metadata { key: 20 value { id: 20 name: "tf_op" } }
  stat_metadata { key: 21 value { id: 21 name: "flops" } }
  stat_metadata { key: 22 value { id: 22 name: "hlo_category" } }
  stat_metadata { key: 23 value { id: 23 name: "convolution" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 60000000 }
    events { metadata_id: 2 offset_ps: 12000000 duration_ps: 40000000 }
    events { metadata_id: 1 offset_ps: 62000000 duration_ps: 38000000 }
    events { metadata_id: 3 offset_ps: 71000000 duration_ps: 25000000 }
    events { metadata_id: 4 offset_ps: 5000000 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "clusd.batch" } }
  event_metadata { key: 2 value { id: 2 name: "clusd.stage1_wait" } }
  event_metadata { key: 3 value { id: 3 name: "clusd.tail_wait" } }
  event_metadata { key: 4 value { id: 4 name: "bench.sync" } }
}
"""

@pytest.fixture(scope="module")
def data():
    return profiler.ProfileData.text_proto_to_serialized_xspace(XSPACE)


@pytest.fixture(scope="module")
def pd(data):
    return profiler.ProfileData.from_serialized_xspace(data)


def test_reader_takes_device_lines_with_metadata_stats(data, pd):
    dev = layers.read_device_lines(data)
    assert list(dev) == ["/device:TPU:0"]
    lines = dev["/device:TPU:0"]
    assert set(lines) == {"XLA Ops", "XLA Modules"}      # Steps left out
    ops = lines["XLA Ops"]
    assert [o[2] for o in ops] == ["%sort.1 = f32[8] sort()", "fusion.2",
                                   "copy.3", "fusion.4", "add.5", "sort.6"]
    assert ops[1][3] == {"tf_op": "jit(clusd_stage1)/stage1/dot",
                         "flops": 7}
    assert ops[3][3] == {"hlo_category": "convolution"}   # a ref_value
    assert ops[4][3]["tf_op"] == "jit(clusd_fused_adc)/dense_score/add"
    # the same times as jax.profiler.ProfileData and tracereduce give
    old = tracereduce.load(pd)
    assert [o[:2] for o in ops] == [o[:2] for o in old.ops["/device:TPU:0"]]
    assert [m[:3] for m in lines["XLA Modules"]] == \
        old.modules["/device:TPU:0"]


def test_host_regions_and_scopes(pd):
    regions = layers.host_regions(pd)
    assert [r[2] for r in regions] == ["clusd.batch", "clusd.stage1_wait",
                                       "clusd.batch", "clusd.tail_wait"]
    assert layers.scope_of("jit(clusd_stage1)/sparse_topk/jit(sort)/sort") \
        == "sparse_topk"
    assert layers.scope_of("jit(clusd_device_pipeline)/add") is None
    assert layers.scope_of(None) is None


def test_ops_without_a_scope_take_the_one_before_them(data):
    lines = layers.read_device_lines(data)["/device:TPU:0"]
    scoped = layers.scoped_ops(lines["XLA Ops"], lines["XLA Modules"])
    assert [(o[3], o[2]) for o in scoped] == [
        ("sort.1", "sparse_topk"), ("fusion.2", "stage1"),
        ("copy.3", "stage1"), ("fusion.4", None),
        ("add.5", "dense_score"), ("sort.6", "dense_score")]


def test_window_reduced_against_hand_counts(data, pd):
    out = layers.reduce_window(pd, layers.read_device_lines(data),
                               0, 100_000)
    assert out["batches"] == 2
    # per batch: sparse_topk 20 us, stage1 20 + 5 (the overlap 20-30
    # counts in both), dense_score 5, unscoped 15
    assert out["sparse_topk.device_ms"] == pytest.approx(0.010)
    assert out["stage1.device_ms"] == pytest.approx(0.0125)
    assert out["selector.device_ms"] == 0.0
    assert out["fuse_topk.device_ms"] == 0.0
    assert out["dense_score.device_ms"] == pytest.approx(0.0025)
    assert out["unscoped.device_ms"] == pytest.approx(0.0075)
    assert out["unscoped_ops"] == [["fusion.4", 0.015]]
    # busy: union 10-40, 45-50, 70-90 = 55 us over 2 batches, 40 scoped
    assert out["busy_ms"] == pytest.approx(0.0275)
    assert out["scoped_share"] == pytest.approx(40 / 55)
    # idle in batch 1 (0-60): 60 - 35 busy = 25; batch 2 (62-100): 38 - 20
    assert out["engine.idle_in_batch_ms"] == pytest.approx(
        (0.025 + 0.018) / 2)
    # stage1_wait 12-52: program A ended at 50 -> 2 us; tail_wait 71-96:
    # program B ended at 90 -> 6 us
    assert out["engine.wait_overrun_ms"] == pytest.approx(0.004)
    assert out["programs"] == ["jit_clusd_fused_adc", "jit_clusd_stage1"]
    # a window with no batch region reads nothing
    assert layers.reduce_window(pd, layers.read_device_lines(data),
                                200_000, 300_000) is None


def test_wait_without_a_program_ending_inside_adds_nothing():
    regions = [(0, 100, "clusd.batch"), (1, 9, "clusd.lock_wait"),
               (10, 20, "clusd.stage2_wait"), (30, 60, "clusd.tail_wait")]
    modules = [(0, 5, "p"), (25, 50, "q")]
    # lock_wait is no device sync; stage2_wait: no program ends in 10-20
    # -> 0; tail_wait ends 10 late
    assert layers.wait_overrun_ms(regions, modules, regions[:1]) == \
        pytest.approx(10 / 1e6)
    assert layers.wait_overrun_ms(regions, modules, []) is None
    assert layers.idle_in_batch_ms([(0, 5, "a")], []) is None


def test_counter_deltas_over_the_window():
    d = layers.counter_deltas({"serve.h2d_bytes": 100, "serve.batches": 1},
                              {"serve.h2d_bytes": 700, "serve.batches": 4,
                               "serve.clusters_selected": 9},
                              ("serve.h2d_bytes", "serve.batches",
                               "serve.clusters_selected"))
    assert d == {"serve.h2d_bytes": 600, "serve.batches": 3,
                 "serve.clusters_selected": 9}
