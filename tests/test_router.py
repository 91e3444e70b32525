"""Multi-host scatter-gather serving tier tests (engine/router.py).

  * partial top-k merge is bitwise-identical to single-host lax.top_k
    over the union — property-tested over arbitrary host partitions,
    duplicate ids at any multiplicity, and exact score ties
  * healthy-fleet routing is bitwise-identical to the single-host engine
    (v1 float shards and v2 ADC alike, divisible or not)
  * fault injection: kill one host mid-stream -> the replica serves and
    failed_requests stays 0; kill ALL replicas of a shard -> requests
    complete degraded with the missing shard flagged, exactly equal to
    serving without that shard; timeouts retry with exponential backoff
  * rolling generation hops: a delta commit + reload_index rolls the
    fleet host-by-host under concurrent queries with zero failures,
    every response served from exactly one generation
  * router traces carry scatter/gather/merge stage spans
  * shard-subset stores refuse clusters they don't own
"""

import dataclasses
import json
import shutil
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
except ImportError:                      # fall back to deterministic sweeps
    from _hypothesis_stub import given, settings
    from _hypothesis_stub import strategies as st

from repro import index as index_lib
from repro.configs import get_config
from repro.core import clusd as cl
from repro.data import synth_corpus, synth_queries
from repro.engine import (
    MERGE_SENTINEL, HostDown, ShardPlacement, ShardRouter,
    merge_partial_topk)
from repro.launch.update_index import synth_delta


def _tiny_cfg():
    return dataclasses.replace(
        get_config("clusd-msmarco", "smoke"),
        n_docs=512, dim=16, n_clusters=32, vocab=256, max_postings=128,
        k_sparse=64, bins=(5, 15, 30, 64), n_candidates=8, max_selected=4,
        n_neighbors=8, u_bins=4, k_final=32, train_queries=24, epochs=2)


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    """Tiny corpus serialized as BOTH formats (3 shards) + queries."""
    cfg = _tiny_cfg()
    corpus = synth_corpus(0, cfg.n_docs, cfg.dim, cfg.vocab)
    index = cl.build_index(cfg, jax.random.key(0), corpus.embeddings,
                           corpus.doc_terms, corpus.doc_weights)
    root = tmp_path_factory.mktemp("router_idx")
    out_v1, out_v2 = str(root / "v1"), str(root / "v2")
    emb = np.asarray(corpus.embeddings)
    index_lib.write_index(out_v1, cfg, index, emb, n_shards=3)
    index_lib.write_index(out_v2, cfg, index, emb, n_shards=3,
                          format_version=2, pq_nsub=4)
    qs = synth_queries(7, corpus, 24)
    return cfg, corpus, out_v1, out_v2, qs


def _engine_ids(out, qs, max_batch=8):
    reader = index_lib.IndexReader.open(out)
    with reader.engine(max_batch=max_batch, prefetch=False) as eng:
        ids, scores = eng.retrieve(qs.q_dense, qs.q_terms, qs.q_weights)
    return np.asarray(ids), np.asarray(scores)


def _router(out, n_hosts, replication=1, **kw):
    reader = index_lib.IndexReader.open(out)
    return ShardRouter.local(reader, n_hosts=n_hosts,
                             replication=replication, max_batch=8, **kw)


# ---------------------------------------------------------------------------
# merge: property test vs the single-host lax.top_k oracle
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(st.integers(0, 10**9))
def test_merge_matches_topk_oracle(seed):
    """Arbitrary host partitions with duplicate ids (any multiplicity),
    exact score ties, and ragged pads merge bitwise-identically to
    lax.top_k over the union (ties: score desc, then doc id asc)."""
    rng = np.random.default_rng(seed)
    B = int(rng.integers(1, 4))
    n_docs = int(rng.integers(5, 40))
    k = int(rng.integers(1, 20))
    n_hosts = int(rng.integers(1, 5))
    # few distinct score values -> plenty of exact ties
    score_pool = np.asarray([0.0, 0.25, 0.5, 1.0, 2.0], np.float32)
    parts = []
    for _ in range(n_hosts):
        width = int(rng.integers(1, 16))
        ids = rng.integers(0, n_docs, (B, width)).astype(np.int64)
        ss = score_pool[rng.integers(0, len(score_pool), (B, width))]
        pad = rng.random((B, width)) < 0.25
        ids = np.where(pad, MERGE_SENTINEL, ids)
        ss = np.where(pad, -np.inf, ss).astype(np.float32)
        parts.append((ids, ss))
    got_ids, got_ss = merge_partial_topk(parts, k)

    # oracle: scatter every occurrence into an id-indexed buffer (slot
    # id*M + occurrence) and lax.top_k it — top_k breaks value ties by
    # lowest index, i.e. (score desc, id asc); //M erases the occurrence
    all_ids = np.concatenate([p[0] for p in parts], axis=1)
    all_ss = np.concatenate([p[1] for p in parts], axis=1)
    M = all_ids.shape[1]                       # max possible multiplicity
    # at least k slots: a k past the valid entries pads with sentinels
    buf = np.full((B, max(n_docs * M, k)), -np.inf, np.float32)
    for b in range(B):
        occ = {}
        for i, s in zip(all_ids[b], all_ss[b]):
            if i >= MERGE_SENTINEL or not np.isfinite(s):
                continue
            j = occ.get(int(i), 0)
            occ[int(i)] = j + 1
            buf[b, int(i) * M + j] = s
    vals, idx = jax.lax.top_k(jnp.asarray(buf), k)
    vals, idx = np.asarray(vals), np.asarray(idx)
    want_ids = np.where(np.isfinite(vals), idx // M, MERGE_SENTINEL)
    np.testing.assert_array_equal(got_ids, want_ids)
    np.testing.assert_array_equal(got_ss,
                                  np.where(np.isfinite(vals), vals, -np.inf))


def test_merge_underfull_and_duplicates():
    """Fewer real entries than k -> sentinel/-inf tail; duplicate ids keep
    their multiplicity (the fused tail scatter-adds duplicate slots, so
    the merge must not collapse them)."""
    ids = np.array([[3, 3, 7]], np.int64)
    ss = np.array([[1.0, 1.0, 2.0]], np.float32)
    got_ids, got_ss = merge_partial_topk([(ids, ss)], 6)
    np.testing.assert_array_equal(
        got_ids[0], [7, 3, 3, MERGE_SENTINEL, MERGE_SENTINEL,
                     MERGE_SENTINEL])
    np.testing.assert_array_equal(got_ss[0],
                                  [2.0, 1.0, 1.0, -np.inf, -np.inf, -np.inf])


# ---------------------------------------------------------------------------
# healthy-fleet parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt,n_hosts,replication", [
    ("v1", 3, 1), ("v1", 3, 2), ("v2", 3, 2),
    ("v2", 2, 1),        # 3 shards over 2 hosts: gappy subset ranges
])
def test_router_bitwise_matches_engine(built, fmt, n_hosts, replication):
    _, _, out_v1, out_v2, qs = built
    out = out_v1 if fmt == "v1" else out_v2
    ref_ids, ref_ss = _engine_ids(out, qs)
    with _router(out, n_hosts, replication) as router:
        ids, ss = router.retrieve(qs.q_dense, qs.q_terms, qs.q_weights)
        st = router.stats()
    np.testing.assert_array_equal(np.asarray(ids), ref_ids)
    np.testing.assert_array_equal(np.asarray(ss), ref_ss)
    assert st["failed_requests"] == 0
    assert st["degraded_requests"] == 0 and not st["degraded"]
    assert all(h["served"] > 0 for h in st["per_host"])


# ---------------------------------------------------------------------------
# fault injection
# ---------------------------------------------------------------------------

def test_kill_one_host_replica_serves(built):
    """R=2: killing a host mid-stream fails zero requests — its shards
    fail over to the surviving replica, results stay exact."""
    _, _, _, out_v2, qs = built
    ref_ids, _ = _engine_ids(out_v2, qs)
    with _router(out_v2, 3, replication=2) as router:
        ids_a, _ = router.retrieve(qs.q_dense[:8], qs.q_terms[:8],
                                   qs.q_weights[:8])
        router.hosts[0].kill()
        ids_b, _ = router.retrieve(qs.q_dense[8:], qs.q_terms[8:],
                                   qs.q_weights[8:])
        st = router.stats()
    ids = np.concatenate([np.asarray(ids_a), np.asarray(ids_b)])
    np.testing.assert_array_equal(ids, ref_ids)
    assert st["failed_requests"] == 0
    assert st["failovers"] > 0          # shards routed off their primary
    assert not st["degraded"] and st["missing_shards"] == []
    assert st["per_host"][0]["alive"] is False


def test_kill_all_replicas_degrades_exactly(built):
    """R=1: killing a shard's only host leaves requests completing in
    degraded mode — missing shard flagged in stats(), results EXACTLY
    equal to a fleet that never had that shard."""
    _, _, _, out_v2, qs = built
    with _router(out_v2, 3, replication=1) as router:
        router.hosts[1].kill()
        ids, ss = router.retrieve(qs.q_dense, qs.q_terms, qs.q_weights)
        st = router.stats()
        metas = list(router.last_batches)
    assert st["failed_requests"] == 0
    assert st["degraded"] and st["missing_shards"] == [1]
    assert st["degraded_requests"] == len(metas) > 0
    assert all(m["degraded"] and m["missing_shards"] == [1] for m in metas)

    # reference: placement where shard 1 has NO replica at all (serving
    # without that shard by construction)
    reader = index_lib.IndexReader.open(out_v2)
    pl = ShardPlacement(3, 2, replication=1,
                        replicas={0: [0], 1: [], 2: [1]})
    with ShardRouter.local(reader, n_hosts=2, placement=pl,
                           max_batch=8) as ref:
        ref_ids, ref_ss = ref.retrieve(qs.q_dense, qs.q_terms, qs.q_weights)
        assert ref.stats()["degraded"]
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ref_ids))
    np.testing.assert_array_equal(np.asarray(ss), np.asarray(ref_ss))


def test_timeout_retries_with_backoff(built):
    """A host that stalls past the timeout is retried with exponential
    backoff (injected sleep observes the waits) and the request still
    completes exactly, with zero failures."""
    _, _, out_v1, _, qs = built
    ref_ids, _ = _engine_ids(out_v1, qs)
    sleeps = []
    with _router(out_v1, 3, replication=1, host_timeout=0.1,
                 max_retries=4, backoff_ms=20.0,
                 sleep=lambda s: sleeps.append(s)) as router:
        # warm compile first so the stall hits a steady batch
        router.retrieve(qs.q_dense[:8], qs.q_terms[:8], qs.q_weights[:8])
        router.hosts[2].inject_delay(250.0, times=1)
        ids, _ = router.retrieve(qs.q_dense[:8], qs.q_terms[:8],
                                 qs.q_weights[:8])
        st = router.stats()
    np.testing.assert_array_equal(np.asarray(ids), ref_ids[:8])
    assert st["failed_requests"] == 0
    assert st["retries"] >= 1
    assert len(sleeps) >= 1             # backoff actually waited
    assert all(b >= a for a, b in zip(sleeps, sleeps[1:]))  # exponential
    assert sleeps[0] == pytest.approx(0.02)


def test_all_hosts_dead_fails_request(built):
    _, _, out_v1, _, qs = built
    with _router(out_v1, 2, replication=2) as router:
        for h in router.hosts:
            h.kill()
        # a direct submit to a dead host raises HostDown ...
        from repro.engine.router import HostRequest
        req = HostRequest(generation=0, mode="dot",
                          q_or_lut=np.zeros((1, 16), np.float32),
                          sel_ids=np.zeros((1, 1), np.int64),
                          mine=np.zeros((1, 1), bool),
                          uniq=np.zeros((0,), np.int64))
        with pytest.raises(HostDown):
            router.hosts[0].submit(req).result()
        # ... but the ROUTER still completes the batch, fully degraded
        ids, _ = router.retrieve(qs.q_dense[:4], qs.q_terms[:4],
                                 qs.q_weights[:4])
        st = router.stats()
    # every shard missing: the batch completes fully degraded (sparse side
    # only — dense side empty), nothing raises
    assert st["degraded"] and st["missing_shards"] == [0, 1, 2]
    assert st["failed_requests"] == 0 and st["degraded_requests"] == 1
    assert np.asarray(ids).shape == (4, _tiny_cfg().k_final)


# ---------------------------------------------------------------------------
# rolling generation hops
# ---------------------------------------------------------------------------

def test_rolling_reload_under_concurrent_queries(built, tmp_path):
    """Commit a delta and roll the 3-host fleet to the new generation
    while a second thread keeps serving: zero failed requests, every
    batch served from exactly one generation, post-hop results bitwise
    equal to a fresh single-host engine on the updated index."""
    _, _, _, out_v2, qs = built
    out = str(tmp_path / "live")
    shutil.copytree(out_v2, out)
    with _router(out, 3, replication=2) as router:
        router.retrieve(qs.q_dense[:8], qs.q_terms[:8], qs.q_weights[:8])
        assert router.stats()["generation"] == 0

        errors = []
        stop = threading.Event()

        def serve_loop():
            while not stop.is_set():
                try:
                    router.retrieve(qs.q_dense[:4], qs.q_terms[:4],
                                    qs.q_weights[:4])
                except Exception as e:          # pragma: no cover
                    errors.append(e)
                    return

        t = threading.Thread(target=serve_loop)
        t.start()
        try:
            delta, _ = synth_delta(router.reader, 12, 8, seed=3)
            index_lib.write_index_delta(out, delta)
            gen = router.reload_index()
            time.sleep(0.05)                   # a few post-hop batches
        finally:
            stop.set()
            t.join()
        assert not errors
        assert gen == 1
        ids, ss = router.retrieve(qs.q_dense, qs.q_terms, qs.q_weights)
        st = router.stats()
        metas = list(router.last_batches)
    assert st["failed_requests"] == 0 and st["degraded_requests"] == 0
    assert st["reloads"] == 1 and st["generation"] == 1
    # every batch came from exactly one generation, and only gens {0, 1}
    # ever served (the router asserts single-generation per batch)
    assert {m["generation"] for m in metas} <= {0, 1}
    assert metas[-1]["generation"] == 1
    # hosts retired the old generation through their serve queues
    for h in router.hosts:
        assert h.generations() == [1]
    ref_ids, ref_ss = _engine_ids(out, qs)
    np.testing.assert_array_equal(np.asarray(ids), ref_ids)
    np.testing.assert_array_equal(np.asarray(ss), ref_ss)


def test_selector_reload_noop_without_new_generation(built):
    _, _, out_v1, _, qs = built
    with _router(out_v1, 2) as router:
        router.retrieve(qs.q_dense[:4], qs.q_terms[:4], qs.q_weights[:4])
        assert router.reload_selector() == 0
        assert router.reload_index() == 0      # no new commit: no-op
        assert router.stats()["reloads"] == 0


# ---------------------------------------------------------------------------
# observability + subset stores
# ---------------------------------------------------------------------------

def test_router_traces_carry_scatter_gather_merge_spans(built):
    _, _, _, out_v2, qs = built
    with _router(out_v2, 3, replication=2, trace_sample_rate=1.0) as router:
        router.retrieve(qs.q_dense[:8], qs.q_terms[:8], qs.q_weights[:8])
        totals = router.tracer.span_totals("batch")
    for span in ("stage1", "lut_build", "stage2_select", "scatter",
                 "gather", "merge", "fuse"):
        assert span in totals, f"missing router span {span!r}"


def test_host_spans_graft_under_scatter(built, tmp_path):
    """Cross-host trace propagation: host-side spans (compact/score/
    partial_topk, block fetch) land nested under the router's scatter
    span, annotated host=i, and both export formats pass the extended
    check_trace rules (per-host Chrome lanes included)."""
    from benchmarks import check_trace
    from repro.obs import write_trace
    _, _, _, out_v2, qs = built
    with _router(out_v2, 3, replication=2, trace_sample_rate=1.0) as router:
        router.retrieve(qs.q_dense[:8], qs.q_terms[:8], qs.q_weights[:8])
        totals = router.tracer.span_totals("batch")
        for span in ("host_serve", "score", "partial_topk"):
            assert span in totals, f"host-side span {span!r} never grafted"
        traces = [t for t in router.tracer.traces if t.name == "batch"]
        hosts_seen = set()
        for tr in traces:
            by_index = {i: sp for i, sp in enumerate(tr.spans)}
            for sp in tr.spans:
                if sp.name == "host_serve":
                    parent = by_index[sp.parent]
                    assert parent.name == "scatter"
                    assert isinstance(sp.annot.get("host"), int)
                    hosts_seen.add(sp.annot["host"])
                    # grafted span sits inside the scatter window
                    assert sp.t0_ms + 0.1 >= parent.t0_ms
                    assert sp.t0_ms + sp.dur_ms <= \
                        parent.t0_ms + parent.dur_ms + 0.1
                if sp.name in ("score", "partial_topk", "compact",
                               "block_fetch"):
                    assert by_index[sp.parent].name == "host_serve"
                    assert sp.annot.get("host") == \
                        by_index[sp.parent].annot.get("host")
        assert len(hosts_seen) == 3         # every host contributed spans
        jp, cp = str(tmp_path / "r.jsonl"), str(tmp_path / "r.json")
        write_trace(router.tracer, jp)
        write_trace(router.tracer, cp)
    bad, _, names = check_trace.check_jsonl(jp)
    assert bad == [] and "host_serve" in names
    bad_c, n_lanes, _ = check_trace.check_chrome(cp)
    assert bad_c == []
    # host-annotated spans ride their own per-host Chrome lanes
    doc = json.load(open(cp))
    host_tids = {ev["tid"] for ev in doc["traceEvents"]
                 if (ev.get("args") or {}).get("host") is not None}
    assert len(host_tids) >= 3
    assert all(isinstance(t, str) and ".host" in t for t in host_tids)


def test_router_metrics_export_includes_per_host(built):
    """Satellite: per-host cache/IO counters from stats()["per_host"] are
    mirrored into the registry as namespaced gauges, so a /metrics scrape
    (or --metrics-out) captures the whole fleet, not just the router."""
    _, _, _, out_v2, qs = built
    with _router(out_v2, 3, replication=1) as router:
        router.retrieve(qs.q_dense[:8], qs.q_terms[:8], qs.q_weights[:8])
        router.hosts[2].kill()
        st = router.stats()                 # stats() syncs the gauges
        snap = router.metrics.snapshot()
        prom = router.metrics.to_prometheus()
    g = snap["gauges"]
    assert g["router.generation"] == 0
    assert g["router.hosts_alive"] == 2
    assert g["router.missing_shards"] == len(st["missing_shards"]) > 0
    for i, h in enumerate(st["per_host"]):
        assert g[f"host{i}.alive"] == int(h["alive"])
        assert g[f"host{i}.served"] == h["served"]
        for k, v in (h.get("cache") or {}).items():
            if isinstance(v, (int, float)):
                assert g[f"host{i}.cache.{k}"] == v
        for k, v in (h.get("io") or {}).items():
            if isinstance(v, (int, float)):
                assert g[f"host{i}.io.{k}"] == v
    assert "host0_served" in prom           # dots -> underscores


def test_router_healthz_flips_on_replica_loss_and_recovers(built):
    """Live endpoint semantics under fault injection: /healthz serves 200
    on a healthy fleet, 503 (shards_without_replicas) once a shard loses
    every replica, and recovers to 200 after revive()."""
    import urllib.error
    import urllib.request
    from repro.obs import MetricsExporter

    def get(port, path):
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", timeout=5) as r:
                return r.status, r.read().decode()
        except urllib.error.HTTPError as e:
            return e.code, e.read().decode()

    _, _, _, out_v2, qs = built
    with _router(out_v2, 3, replication=1) as router:
        router.retrieve(qs.q_dense[:8], qs.q_terms[:8], qs.q_weights[:8])
        with MetricsExporter(router, port=0) as exp:
            code, body = get(exp.port, "/healthz")
            assert code == 200 and json.loads(body)["ok"] is True
            code, text = get(exp.port, "/metrics")
            assert code == 200 and "router_hosts_alive 3" in text

            router.hosts[1].kill()          # R=1: shard 1 loses its only
            code, body = get(exp.port, "/healthz")
            reasons = json.loads(body)["reasons"]
            assert code == 503
            assert any("shards_without_replicas" in r for r in reasons)
            # serving continues degraded while health reports it
            router.retrieve(qs.q_dense[:4], qs.q_terms[:4],
                            qs.q_weights[:4])
            code, text = get(exp.port, "/metrics")
            assert code == 200 and "router_hosts_alive 2" in text

            router.hosts[1].revive()
            code, body = get(exp.port, "/healthz")
            assert code == 200 and json.loads(body)["ok"] is True


def test_router_explain_records_host_contrib(built):
    """Router-side explain telemetry: every sampled batch yields per-query
    records carrying the per-host score attribution (host_contrib) and
    the degraded flag, on top of the shared engine record fields."""
    from repro.obs import ExplainLogger
    cfg, _, _, out_v2, qs = built
    ex = ExplainLogger(sample_rate=1.0)
    with _router(out_v2, 3, replication=1, explain=ex) as router:
        ids, _ = router.retrieve(qs.q_dense[:8], qs.q_terms[:8],
                                 qs.q_weights[:8])
        router.hosts[1].kill()
        router.retrieve(qs.q_dense[8:12], qs.q_terms[8:12],
                        qs.q_weights[8:12])
    recs = ex.recent()
    assert len(recs) == 12
    assert [r["qid"] for r in recs] == list(range(12))
    healthy, degraded = recs[:8], recs[8:]
    assert all(r["degraded"] is False for r in healthy)
    assert all(r["degraded"] is True for r in degraded)
    k = np.asarray(ids).shape[1]
    for r in healthy:
        assert set(r) >= {"cand", "probs", "selected", "provenance",
                          "theta", "budget", "fusion_contrib",
                          "host_contrib"}
        assert len(r["probs"]) == len(r["cand"]) == len(r["provenance"])
        assert set(r["provenance"]) <= {"seed", "expand"}
        # host attribution covers at most the final top-k, never negative
        total = sum(r["host_contrib"].values())
        assert 0 <= total <= k
    # the killed host contributes to no degraded record
    assert all(r["host_contrib"].get("1", 0) == 0 for r in degraded)


def test_subset_store_owns_only_its_shards(built):
    _, _, out_v1, _, _ = built
    reader = index_lib.IndexReader.open(out_v1)
    full = reader.open_store()
    sub = reader.open_store(shards=[1])
    assert sub.is_subset and not full.is_subset
    (lo, hi), = sub.owned_ranges
    vecs_s, docs_s, valid_s = sub.fetch_blocks(np.arange(lo, hi))
    vecs_f, docs_f, valid_f = full.fetch_blocks(np.arange(lo, hi))
    np.testing.assert_array_equal(np.asarray(vecs_s), np.asarray(vecs_f))
    np.testing.assert_array_equal(docs_s, docs_f)
    with pytest.raises(KeyError):
        sub.fetch_blocks(np.asarray([0 if lo > 0 else hi]))
    with pytest.raises(ValueError):
        reader.open_store(shards=[99])
