"""Observability tests: metrics-registry semantics + thread safety,
stage-span tracing (nesting, deterministic sampling, export schema
round-trip through benchmarks/check_trace.py), the bounded ServeStats
rewrite, engine span/stat integration on a tiny disk-backed engine, and
a loose bound on the tracing-disabled hot-path cost."""

import dataclasses
import json
import os
import tempfile
import threading
import time

import jax
import numpy as np
import pytest

from repro.obs import (
    NOOP_SPAN, NOOP_TRACE, MetricsRegistry, Tracer, write_metrics,
    write_trace)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("a.b")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    assert reg.counter("a.b") is c          # get-or-create returns the same
    g = reg.gauge("g")
    g.set(7)
    assert g.value == 7
    reg.reset()
    assert c.value == 0


def test_registry_kind_mismatch_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_counter_thread_safety():
    reg = MetricsRegistry()
    c = reg.counter("hits")
    h = reg.histogram("lat", ring=64)
    n, per = 8, 10_000

    def work():
        for _ in range(per):
            c.inc()
            h.observe(1.0)

    threads = [threading.Thread(target=work) for _ in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n * per
    snap = h.snapshot()
    assert snap["count"] == n * per
    assert snap["sum"] == pytest.approx(n * per)


def test_histogram_ring_bounded_and_percentiles_exact():
    reg = MetricsRegistry()
    h = reg.histogram("ms", ring=100)
    vals = np.arange(1000, dtype=np.float64)
    for v in vals:
        h.observe(float(v))
    # ring keeps only the most recent 100; lifetime count keeps all
    kept = np.asarray(h.values())
    assert len(kept) == 100
    np.testing.assert_array_equal(kept, vals[-100:])
    assert h.snapshot()["count"] == 1000
    # percentile matches np.percentile (linear interpolation) on the ring
    assert h.percentile(50) == pytest.approx(np.percentile(kept, 50))
    assert h.percentile(99) == pytest.approx(np.percentile(kept, 99))


def test_snapshot_and_prometheus_exposition(tmp_path):
    reg = MetricsRegistry()
    reg.counter("serve.queries").inc(5)
    reg.gauge("cache.hit_rate").set(0.75)
    reg.histogram("serve.batch_ms", buckets=(1.0, 10.0, float("inf")))
    reg.histogram("serve.batch_ms").observe(0.5)
    reg.histogram("serve.batch_ms").observe(5.0)
    snap = reg.snapshot()
    assert snap["counters"]["serve.queries"] == 5
    assert snap["gauges"]["cache.hit_rate"] == 0.75
    assert snap["histograms"]["serve.batch_ms"]["count"] == 2
    text = reg.to_prometheus()
    assert "serve_queries 5" in text
    assert "cache_hit_rate 0.75" in text
    # cumulative buckets: le="10.0" counts both observations
    assert 'serve_batch_ms_bucket{le="1.0"} 1' in text
    assert 'serve_batch_ms_bucket{le="10.0"} 2' in text
    assert 'serve_batch_ms_bucket{le="+Inf"} 2' in text
    # write_metrics picks the format by suffix
    pj, pp = str(tmp_path / "m.json"), str(tmp_path / "m.prom")
    write_metrics(reg, pj)
    write_metrics(reg, pp)
    assert json.load(open(pj))["counters"]["serve.queries"] == 5
    assert "serve_queries 5" in open(pp).read()


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------

def _one_trace(tracer):
    tr = tracer.trace("batch", size=4)
    with tr.span("stage1"):
        time.sleep(0.001)
    with tr.span("cache_fetch", n_blocks=3) as sp:
        with tr.span("disk_fetch"):
            time.sleep(0.001)
        sp.annotate(bytes=4096)
    tr.finish(compiled=False)
    return tr


def test_span_nesting_and_annotations():
    tracer = Tracer(sample_rate=1.0)
    tr = _one_trace(tracer)
    names = [sp.name for sp in tr.spans]
    assert names == ["batch", "stage1", "cache_fetch", "disk_fetch"]
    assert [sp.depth for sp in tr.spans] == [0, 1, 1, 2]
    assert [sp.parent for sp in tr.spans] == [-1, 0, 0, 2]
    fetch = tr.spans[2]
    assert fetch.annot == {"n_blocks": 3, "bytes": 4096}
    assert tr.spans[0].annot == {"size": 4, "compiled": False}
    # children lie inside the root's window
    assert all((sp.t0_ms + sp.dur_ms) <= tr.dur_ms + 0.1 for sp in tr.spans)
    totals = tracer.span_totals("batch")
    assert set(totals) == {"stage1", "cache_fetch", "disk_fetch"}
    assert totals["stage1"]["count"] == 1


def test_export_schema_roundtrip(tmp_path):
    from benchmarks import check_trace
    tracer = Tracer(sample_rate=1.0)
    for _ in range(3):
        _one_trace(tracer)
    jp = str(tmp_path / "t.jsonl")
    cp = str(tmp_path / "t.json")
    write_trace(tracer, jp)
    write_trace(tracer, cp)
    # JSONL: every line round-trips and passes the CI schema checker
    lines = [json.loads(ln) for ln in open(jp)]
    assert len(lines) == 3 * 4
    assert {ln["span"] for ln in lines} == \
        {"batch", "stage1", "cache_fetch", "disk_fetch"}
    bad, n_traces, names = check_trace.check_jsonl(jp)
    assert bad == [] and n_traces == 3
    # Chrome export: valid JSON, complete events, passes the checker
    doc = json.load(open(cp))
    assert all(ev["ph"] == "X" for ev in doc["traceEvents"])
    bad_c, _, names_c = check_trace.check_chrome(cp)
    assert bad_c == [] and "disk_fetch" in names_c
    # the checker's CLI contract: exit 0 on valid, 1 on a missing span
    assert check_trace.main([jp, "--require-spans", "stage1"]) == 0
    assert check_trace.main([jp, "--require-spans", "nonexistent"]) == 1


def test_sampling_deterministic_and_bounded():
    tracer = Tracer(sample_rate=0.25, capacity=2)
    kinds = []
    for _ in range(8):
        tr = tracer.trace("batch")
        kinds.append(tr is NOOP_TRACE)
        tr.finish()
    # accumulator sampling: exactly every 4th request is recorded
    assert kinds == [True, True, True, False] * 2
    assert tracer.started == 2 and tracer.skipped == 6
    # retention is bounded by capacity
    tracer2 = Tracer(sample_rate=1.0, capacity=2)
    for _ in range(5):
        tracer2.trace("t").finish()
    assert len(tracer2.traces) == 2 and tracer2.dropped == 3


def test_disabled_path_is_noop_and_cheap():
    tracer = Tracer(sample_rate=0.0)
    tr = tracer.trace("batch")
    assert tr is NOOP_TRACE
    assert tr.span("anything") is NOOP_SPAN
    with tr.span("x") as sp:
        sp.annotate(bytes=1)
    tr.finish()
    assert tracer.traces == []
    # loose micro-bound: the disabled hot path (trace + 3 spans) must stay
    # well under anything that could perturb a millisecond-scale batch
    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        t = tracer.trace("batch")
        with t.span("a"):
            pass
        with t.span("b"):
            pass
        with t.span("c"):
            pass
        t.finish()
    per_call_us = (time.perf_counter() - t0) / n * 1e6
    assert per_call_us < 50, f"disabled tracing costs {per_call_us:.1f}us"


# ---------------------------------------------------------------------------
# bounded ServeStats
# ---------------------------------------------------------------------------

def test_serve_stats_bounded_window():
    from repro.engine.server import ServeStats
    st = ServeStats(MetricsRegistry(), window=16)
    st.record(4, 4, True, 50.0)                 # compile batch: excluded
    for i in range(100):
        st.record(4, 4, False, float(i))
    assert st.n_batches == 101 and st.n_queries == 404
    assert st.n_compile_batches == 1
    # memory is bounded: the recent-batch ring holds `window` records
    assert len(st.batches) == 16
    assert len(st.batch_ms) == 16
    # percentiles computed over the steady ring, same fields as PR 6
    pct = st.latency_percentiles()
    assert set(pct) == {"p50_ms", "p99_ms", "mean_ms"}
    ring = np.asarray([float(i) for i in range(100)][-16:])
    assert pct["p50_ms"] == pytest.approx(
        round(float(np.percentile(ring, 50)), 3))
    st.reset()
    assert st.n_batches == 0 and st.latency_percentiles() == {}


# ---------------------------------------------------------------------------
# engine integration: spans + stats keys + reset semantics
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_engine_parts():
    from repro.configs import get_config
    from repro.core import clusd as cl
    from repro.data import synth_corpus, synth_queries
    cfg = dataclasses.replace(
        get_config("clusd-msmarco", "smoke"),
        n_docs=256, dim=32, n_clusters=16, vocab=256, max_postings=256,
        k_sparse=64, bins=(5, 15, 30, 64), n_candidates=8, max_selected=4,
        n_neighbors=8, u_bins=4, k_final=32)
    corpus = synth_corpus(0, cfg.n_docs, cfg.dim, cfg.vocab)
    index = cl.build_index(cfg, jax.random.key(0), corpus.embeddings,
                           corpus.doc_terms, corpus.doc_weights)
    qs = synth_queries(7, corpus, 8)
    return cfg, corpus, index, qs


def test_engine_spans_and_stats_contract(tiny_engine_parts):
    from repro.engine import DiskStore, RetrievalEngine
    cfg, corpus, index, qs = tiny_engine_parts
    tracer = Tracer(sample_rate=1.0)
    with tempfile.TemporaryDirectory() as d:
        store = DiskStore.create(os.path.join(d, "blocks.bin"),
                                 index.embeddings, index.cluster_docs)
        with RetrievalEngine(cfg, index, store=store, max_batch=8,
                             cache_capacity=8, tracer=tracer) as eng:
            for _ in range(3):
                eng.retrieve(qs.q_dense, qs.q_terms, qs.q_weights)
            st = eng.stats()
            # PR-6 stats() keys stay intact (byte-compatible contract)
            for key in ("n_queries", "n_batches", "p50_ms", "p99_ms",
                        "mean_ms", "qps_steady", "compiled_buckets", "io",
                        "cache", "use_adc", "reloads", "selector_reloads",
                        "prefetch_enqueued", "prefetch_errors",
                        "n_compile_batches"):
                assert key in st, f"stats() lost key {key!r}"
            assert st["n_queries"] == 24 and st["n_compile_batches"] >= 1
            # every serve stage appears as a span (lut_build is ADC-only);
            # compile batches are flagged on the root, not dropped
            totals = tracer.span_totals("batch")
            for span in ("pad", "stage1", "stage2_select", "fuse",
                         "cache_fetch", "disk_fetch", "fused_score_topk"):
                assert span in totals, f"serve never emitted span {span!r}"
            flags = [tr.spans[0].annot.get("compiled")
                     for tr in tracer.traces if tr.name == "batch"]
            assert flags[0] is True and flags[-1] is False
            # registry mirrors the serve counters
            snap = eng.metrics.snapshot()
            assert snap["counters"]["serve.queries"] == 24
            # reset_stats: counters to zero, serving keeps working
            eng.reset_stats()
            st2 = eng.stats()
            assert st2["n_queries"] == 0 and st2["io"]["n_ops"] == 0
            assert st2["cache"]["hits"] == 0
            eng.retrieve(qs.q_dense, qs.q_terms, qs.q_weights)
            assert eng.stats()["n_queries"] == 8


def test_engine_span_coverage_of_batch_wall(tiny_engine_parts):
    """Depth-1 stage spans must explain >=90% of the measured batch time
    (the pq-sharded acceptance bound, exercised here on the disk path)."""
    from repro.engine import DiskStore, RetrievalEngine
    cfg, corpus, index, qs = tiny_engine_parts
    tracer = Tracer(sample_rate=1.0)
    with tempfile.TemporaryDirectory() as d:
        store = DiskStore.create(os.path.join(d, "blocks.bin"),
                                 index.embeddings, index.cluster_docs)
        with RetrievalEngine(cfg, index, store=store, max_batch=8,
                             cache_capacity=8, prefetch=False,
                             tracer=tracer) as eng:
            for _ in range(6):
                eng.retrieve(qs.q_dense, qs.q_terms, qs.q_weights)
    batch_wall = covered = 0.0
    for t in tracer.traces:
        if t.spans[0].annot.get("compiled"):
            continue                # compile batches measure XLA, not serving
        batch_wall += float(t.spans[0].annot["batch_ms"])
        covered += sum(sp.dur_ms for sp in t.spans
                       if sp.depth == 1 and sp.name != "pad")
    assert batch_wall > 0
    assert covered / batch_wall >= 0.9, \
        f"spans cover {covered / batch_wall:.0%} of batch wall time"


# ---------------------------------------------------------------------------
# profiler timeline: the Tracer's annotate hook
# ---------------------------------------------------------------------------

def _recording_hook(events):
    """An `annotate` hook that records ("enter" | "exit", name)."""
    import contextlib

    @contextlib.contextmanager
    def annotate(name):
        events.append(("enter", name))
        yield
        events.append(("exit", name))
    return annotate


def _region_parents(events):
    """[(region, enclosing region or None)] in entry order; asserts the
    events nest (every exit closes the innermost open region)."""
    stack, out = [], []
    for kind, name in events:
        if kind == "enter":
            out.append((name, stack[-1] if stack else None))
            stack.append(name)
        else:
            assert stack and stack[-1] == name, (name, stack)
            stack.pop()
    assert stack == []
    return out


def test_annotate_hook_nests_with_spans_and_skips_noop():
    events = []
    tracer = Tracer(sample_rate=1.0, annotate=_recording_hook(events))
    tr = tracer.trace("batch", size=4)
    with tr.span("stage1"):
        with tr.span("stage1_dispatch"):
            pass
        with tr.region("stage1_wait"):
            pass
    tr.add_completed("grafted", t0_abs=time.perf_counter(), dur_ms=1.0)
    sp = tr.span("fused_score_topk")
    sp.end()
    tr.finish()
    # once per span (root included) plus the region, in nesting order;
    # the grafted span stays off the timeline and `region` records no span
    assert _region_parents(events) == [
        ("clusd.batch", None), ("clusd.stage1", "clusd.batch"),
        ("clusd.stage1_dispatch", "clusd.stage1"),
        ("clusd.stage1_wait", "clusd.stage1"),
        ("clusd.fused_score_topk", "clusd.batch")]
    assert len(events) == 10
    assert [s.name for s in tr.spans] == [
        "batch", "stage1", "stage1_dispatch", "grafted", "fused_score_topk"]
    # unsampled requests never reach the hook
    events.clear()
    tracer.sample_rate = 0.0
    tr = tracer.trace("batch")
    assert tr is NOOP_TRACE
    with tr.span("stage1"):
        with tr.region("stage1_wait"):
            pass
    tr.finish()
    assert events == []
    # without a hook a sampled trace records spans and regions are no-ops
    plain = Tracer(sample_rate=1.0).trace("batch")
    assert plain.region("x") is NOOP_SPAN
    plain.finish()


def _engine_timeline(eng, batches):
    """Serve `batches` through `eng` with a recording hook on its tracer;
    -> the (region, parent) pairs of the last batch."""
    events = []
    eng.tracer.annotate = _recording_hook(events)
    for qd, qt, qw in batches:
        events.clear()
        eng.retrieve(qd, qt, qw)
    return _region_parents(events)


def _host_batches(qs, sizes):
    """Batches of the given sizes, as host arrays, cycling over the 8
    queries."""
    out, lo = [], 0
    for n in sizes:
        rows = np.arange(lo, lo + n) % 8
        out.append(tuple(np.asarray(x)[rows] for x in
                         (qs.q_dense, qs.q_terms, qs.q_weights)))
        lo += n
    return out


def test_engine_host_path_child_spans_and_counters(tiny_engine_parts,
                                                   tmp_path):
    """Host path: the new child spans nest inside their stage spans and
    the export passes check_trace; the wait regions sit inside their
    stages on the timeline; serve.h2d_bytes and serve.clusters_selected
    equal hand counts."""
    from benchmarks import check_trace
    from repro.engine import DiskStore, RetrievalEngine
    from repro.engine import pipeline as pipe_lib
    cfg, corpus, index, qs = tiny_engine_parts
    tracer = Tracer(sample_rate=1.0)
    batches = _host_batches(qs, (5, 8, 3))
    with tempfile.TemporaryDirectory() as d:
        store = DiskStore.create(os.path.join(d, "blocks.bin"),
                                 index.embeddings, index.cluster_docs)
        with RetrievalEngine(cfg, index, store=store, max_batch=8,
                             cache_capacity=8, prefetch=False,
                             tracer=tracer) as eng:
            regions = _engine_timeline(eng, batches)
            st = eng.stats()
            cap, dim = store.cap, store.dim
    parents = {}
    for t in tracer.traces:
        for sp in t.spans[1:]:
            parents[sp.name] = t.spans[sp.parent].name
    for child, parent in (("lock_wait", "batch"),
                          ("stage1_dispatch", "stage1"),
                          ("prefetch_enqueue", "stage1"),
                          ("stage2_dispatch", "stage2_select"),
                          ("tail_pad", "fused_score_topk"),
                          ("tail_h2d", "fused_score_topk"),
                          ("tail_dispatch", "fused_score_topk")):
        assert parents.get(child) == parent, (child, parents.get(child))
    assert not {"stage1_wait", "stage2_wait", "tail_wait"} & set(parents)
    jp = str(tmp_path / "host.jsonl")
    write_trace(tracer, jp)
    assert check_trace.main([jp, "--require-spans",
                             "lock_wait,tail_h2d,stage2_dispatch"]) == 0
    assert ("clusd.stage1_wait", "clusd.stage1") in regions
    assert ("clusd.stage2_wait", "clusd.stage2_select") in regions
    assert ("clusd.tail_wait", "clusd.fused_score_topk") in regions
    assert ("clusd.lock_wait", "clusd.batch") in regions
    # hand counts: padded query inputs, the pow2-padded unique blocks and
    # pos of every batch; the selection over the real queries
    stage1, stage2 = pipe_lib.build_stage1_fn(cfg), \
        pipe_lib.build_stage2_fn(cfg)
    h2d = selected = queries = 0
    for qd, qt, qw in batches:
        n = len(qd)
        pad = (1 << (n - 1).bit_length()) - n          # pow2 bucket
        qd, qt, qw = (np.concatenate([x, np.repeat(x[-1:], pad, 0)])
                      for x in (qd, qt, qw))
        h2d += qd.nbytes + qt.nbytes + qw.nbytes
        _, _, cand, feats = stage1(eng.index, qd, qt, qw)
        sel, mask, _ = stage2(eng.index, cand, feats)
        sel, mask = np.asarray(sel), np.asarray(mask)
        u = len(np.unique(sel[mask])) if mask.any() else 1
        ub = 1 << (u - 1).bit_length()
        h2d += ub * cap * dim * 4 + sel.size * 4
        selected += int(mask[:n].sum())
        queries += n
    assert st["h2d_bytes"] == h2d
    assert st["clusters_selected"] == selected > 0
    assert st["selected_queries"] == queries == 16


def test_engine_device_path_child_spans_and_counters(tiny_engine_parts,
                                                     tmp_path):
    """Device path: `device_dispatch` nests in `device_pipeline` and the
    `device_wait` region inside it; h2d counts the padded query inputs of
    every batch; the selection is counted on sampled batches only."""
    from benchmarks import check_trace
    from repro.engine import RetrievalEngine
    from repro.engine import pipeline as pipe_lib
    cfg, corpus, index, qs = tiny_engine_parts
    tracer = Tracer(sample_rate=0.5)
    batches = _host_batches(qs, (5, 8, 3, 6))
    with RetrievalEngine(cfg, index, max_batch=8, tracer=tracer) as eng:
        regions = _engine_timeline(eng, batches)
        st = eng.stats()
        fn = pipe_lib.build_device_fn(cfg, k=eng.k)
        h2d = selected = queries = 0
        for i, (qd, qt, qw) in enumerate(batches):
            n = len(qd)
            pad = (1 << (n - 1).bit_length()) - n      # pow2 bucket
            qd, qt, qw = (np.concatenate([x, np.repeat(x[-1:], pad, 0)])
                          for x in (qd, qt, qw))
            h2d += qd.nbytes + qt.nbytes + qw.nbytes
            if i % 2:                   # rate 0.5: every second batch
                _, _, n_sel = fn(eng.index, eng.store, qd, qt, qw)
                selected += int(np.asarray(n_sel)[:n].sum())
                queries += n
    assert [t.spans[0].annot["size"] for t in tracer.traces] == [8, 6]
    for t in tracer.traces:
        names = {sp.name: t.spans[sp.parent].name for sp in t.spans[1:]}
        assert names["device_dispatch"] == "device_pipeline"
        assert names["lock_wait"] == "batch"
    assert ("clusd.device_wait", "clusd.device_pipeline") in regions
    jp = str(tmp_path / "device.jsonl")
    write_trace(tracer, jp)
    assert check_trace.main([jp, "--require-spans",
                             "device_dispatch,lock_wait"]) == 0
    assert st["h2d_bytes"] == h2d
    assert st["clusters_selected"] == selected > 0
    assert st["selected_queries"] == queries == 14


def _stage_program(name, cfg, index, qs):
    """(jitted program, its arguments) at the tiny engine's shapes."""
    from repro.engine import pipeline as pipe_lib
    from repro.engine import stores as stores_lib
    qd, qt, qw = (np.asarray(x) for x in
                  (qs.q_dense, qs.q_terms, qs.q_weights))
    B, S, cap = len(qd), cfg.max_selected, index.cluster_docs.shape[1]
    nsub = 4
    sid = np.zeros((B, cfg.k_sparse), np.int32)
    ss = np.zeros((B, cfg.k_sparse), np.float32)
    sel = np.zeros((B, S), np.int32)
    mask = np.ones((B, S), bool)
    pos = np.zeros((B, S), np.int32)
    fused = [index.cluster_docs, None, sid, ss, sel, mask, None, pos]
    if name == "stage1":
        return pipe_lib.build_stage1_fn(cfg), (index, qd, qt, qw)
    if name == "stage2":
        _, _, cand, feats = pipe_lib.build_stage1_fn(cfg)(index, qd, qt, qw)
        return pipe_lib.build_stage2_fn(cfg), (index, cand, feats)
    if name == "lut":
        books = np.zeros((nsub, 256, cfg.dim // nsub), np.float32)
        return pipe_lib.build_lut_fn(), (books, None, qd)
    if name == "device_pipeline":
        return (pipe_lib.build_device_fn(cfg, k=cfg.k_final),
                (index, stores_lib.store_for_index(index), qd, qt, qw))
    mode = name.rsplit("_", 1)[1]
    fused[1] = np.zeros((B, nsub, 256), np.float32) if mode == "adc" else qd
    fused[6] = np.zeros((2, cap, nsub), np.uint8) if mode == "adc" \
        else np.zeros((2, cap, cfg.dim), np.float32)
    return (pipe_lib.build_fused_scorer(cfg, index.n_docs, k=cfg.k_final,
                                        mode=mode), tuple(fused))


@pytest.mark.parametrize("name,scopes", [
    ("stage1", ("sparse_topk", "stage1")),
    ("stage2", ("selector",)),
    ("lut", ("dense_score",)),
    ("device_pipeline", ("sparse_topk", "stage1", "selector",
                         "dense_score", "fuse_topk")),
    ("fused_adc", ("dense_score", "fuse_topk")),
    ("fused_dot", ("dense_score", "fuse_topk")),
])
def test_stage_programs_are_named_and_scoped(tiny_engine_parts, name,
                                             scopes):
    """Every stage program compiles as `jit(clusd_<stage>)` and its ops
    carry the stage scopes in their op_name metadata."""
    cfg, _, index, qs = tiny_engine_parts
    fn, args = _stage_program(name, cfg, index, qs)
    text = fn.lower(*args).compile().as_text()
    for scope in scopes:
        assert f'op_name="jit(clusd_{name})/{scope}/' in text, (name, scope)
    assert "jit(run)" not in text
