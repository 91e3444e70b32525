"""Engine-layer tests: ClusterStore backend parity (in-memory / disk / PQ
with an identity quantizer return identical fused top-k), LRU block-cache
accounting, request bucketing, the stage-2 selection-budget bugfix, and
RetrievalEngine end-to-end (dedup'd I/O, cache hits, prefetch shutdown)."""

import dataclasses
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import clusd as cl
from repro.core import quant as quant_lib
from repro.engine import (
    BlockCache, DiskStore, InMemoryStore, PQStore, RetrievalEngine,
    bucket_size, pipeline)


@pytest.fixture(scope="module")
def tiny():
    """256-doc corpus (small enough for an exact identity PQ)."""
    cfg = dataclasses.replace(
        get_config("clusd-msmarco", "smoke"),
        n_docs=256, dim=32, n_clusters=16, vocab=256, max_postings=256,
        k_sparse=64, bins=(5, 15, 30, 64), n_candidates=8, max_selected=4,
        n_neighbors=8, u_bins=4, k_final=32)
    from repro.data import synth_corpus, synth_queries
    corpus = synth_corpus(0, cfg.n_docs, cfg.dim, cfg.vocab)
    index = cl.build_index(cfg, jax.random.key(0), corpus.embeddings,
                           corpus.doc_terms, corpus.doc_weights)
    qs = synth_queries(7, corpus, 12)
    return cfg, corpus, index, qs


# ---------------------------------------------------------------------------
# backend parity
# ---------------------------------------------------------------------------

def _stores(index, tmpdir):
    yield "inmemory", InMemoryStore(index.embeddings, index.cluster_docs)
    yield "disk", DiskStore.create(os.path.join(tmpdir, "blocks.bin"),
                                   index.embeddings, index.cluster_docs)
    yield "pq-identity", PQStore(quant_lib.identity_pq(index.embeddings, 8),
                                 index.cluster_docs)


def test_backend_parity_fused_topk(tiny):
    cfg, _, index, qs = tiny
    results = {}
    with tempfile.TemporaryDirectory() as d:
        for name, store in _stores(index, d):
            ids, scores, _ = pipeline.retrieve(cfg, index, store, qs.q_dense,
                                               qs.q_terms, qs.q_weights)
            results[name] = (np.asarray(ids), np.asarray(scores))
    ref_ids, ref_scores = results["inmemory"]
    for name in ("disk", "pq-identity"):
        ids, scores = results[name]
        np.testing.assert_array_equal(ids, ref_ids, err_msg=name)
        np.testing.assert_allclose(scores, ref_scores, rtol=1e-5, atol=1e-5,
                                   err_msg=name)


def test_backend_parity_fetch_blocks(tiny):
    _, _, index, _ = tiny
    cids = np.asarray([0, 3, 7, 3])
    with tempfile.TemporaryDirectory() as d:
        fetched = {name: store.fetch_blocks(jnp.asarray(cids)
                                            if not store.is_host else cids)
                   for name, store in _stores(index, d)}
    vecs_ref, docs_ref, valid_ref = map(np.asarray, fetched["inmemory"])
    for name in ("disk", "pq-identity"):
        vecs, docs, valid = map(np.asarray, fetched[name])
        np.testing.assert_array_equal(docs, docs_ref, err_msg=name)
        np.testing.assert_array_equal(valid, valid_ref, err_msg=name)
        np.testing.assert_allclose(vecs, vecs_ref, rtol=1e-5, atol=1e-5,
                                   err_msg=name)


def test_legacy_wrappers_match_pipeline(tiny):
    """core.clusd.retrieve / core.disk.ondisk_clusd_retrieve are thin
    wrappers — same ids as calling the pipeline directly."""
    from repro.core import disk as dk
    cfg, corpus, index, qs = tiny
    ids_mem, _, _ = cl.retrieve(cfg, index, qs.q_dense, qs.q_terms,
                                qs.q_weights)
    with tempfile.TemporaryDirectory() as d:
        blocks = dk.DiskClusterStore(os.path.join(d, "b.bin"),
                                     corpus.embeddings, index.cluster_docs)
        ids_dk, _, stats = dk.ondisk_clusd_retrieve(
            cfg, index, blocks, qs.q_dense, qs.q_terms, qs.q_weights)
    np.testing.assert_array_equal(np.asarray(ids_dk), np.asarray(ids_mem))
    # n_ops counts coalesced runs of adjacent blocks, bytes counts blocks
    n_blocks = stats.bytes // blocks.block_bytes
    assert 0 < stats.n_ops <= n_blocks
    assert stats.bytes == n_blocks * blocks.block_bytes


# ---------------------------------------------------------------------------
# backend parity matrix: all five stores on one fixture index
# ---------------------------------------------------------------------------

_MATRIX_SHAPES = {
    "base": dict(n_docs=256, n_clusters=16),
    # n_docs not divisible by cluster_cap, odd cluster count
    "ragged": dict(n_docs=237, n_clusters=7),
    "single-cluster": dict(n_docs=64, n_clusters=1),
    "empty-stage1": dict(n_docs=256, n_clusters=16),
}


@pytest.mark.parametrize("case", sorted(_MATRIX_SHAPES))
def test_backend_parity_matrix(case, tmp_path):
    """InMemoryStore / DiskStore / ShardedDiskStore agree exactly;
    PQStore / ShardedPQStore agree with each other and stay within a
    bounded MRR@10 delta of the exact backends — across odd geometries
    and an all-padding (empty Stage-I sparse input) query batch."""
    from repro import index as index_lib
    from repro.data import mrr_at, synth_corpus, synth_queries

    shape = _MATRIX_SHAPES[case]
    N = shape["n_clusters"]
    cfg = dataclasses.replace(
        get_config("clusd-msmarco", "smoke"),
        n_docs=shape["n_docs"], dim=32, n_clusters=N, vocab=128,
        max_postings=64, k_sparse=32, bins=(5, 15, 32),
        n_candidates=min(8, N), max_selected=min(4, N),
        n_neighbors=min(8, max(1, N - 1)), u_bins=4, k_final=16)
    corpus = synth_corpus(11, cfg.n_docs, cfg.dim, cfg.vocab)
    index = cl.build_index(cfg, jax.random.key(0), corpus.embeddings,
                           corpus.doc_terms, corpus.doc_weights)
    qs = synth_queries(13, corpus, 16)
    q_terms, q_weights = qs.q_terms, qs.q_weights
    if case == "empty-stage1":
        q_terms = jnp.full_like(qs.q_terms, -1)
        q_weights = jnp.zeros_like(qs.q_weights)

    emb = np.asarray(corpus.embeddings)
    pq = quant_lib.train_pq(jax.random.key(1), corpus.embeddings, nsub=8)
    v1 = str(tmp_path / "v1")
    v2 = str(tmp_path / "v2")
    index_lib.write_index(v1, cfg, index, emb, n_shards=min(3, N))
    index_lib.write_index(v2, cfg, index, emb, n_shards=min(3, N),
                          format_version=index_lib.FORMAT_VERSION_PQ, pq=pq)
    stores = {
        "inmemory": InMemoryStore(index.embeddings, index.cluster_docs),
        "disk": DiskStore.create(str(tmp_path / "blocks.bin"),
                                 index.embeddings, index.cluster_docs),
        "sharded-disk": index_lib.IndexReader.open(v1, verify="full")
        .open_store(cluster_docs=index.cluster_docs),
        "pq": PQStore(pq, index.cluster_docs),
        "sharded-pq": index_lib.IndexReader.open(v2, verify="full")
        .open_store(cluster_docs=index.cluster_docs),
    }
    results = {}
    for name, store in stores.items():
        ids, scores, _ = pipeline.retrieve(cfg, index, store, qs.q_dense,
                                           q_terms, q_weights)
        results[name] = (np.asarray(ids), np.asarray(scores))

    ref_ids, ref_scores = results["inmemory"]
    for name in ("disk", "sharded-disk"):       # exact backends: identical
        np.testing.assert_array_equal(results[name][0], ref_ids,
                                      err_msg=f"{case}:{name}")
        np.testing.assert_allclose(results[name][1], ref_scores,
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=f"{case}:{name}")
    ref_mrr = mrr_at(ref_ids, qs.rel_doc)
    for name in ("pq", "sharded-pq"):           # PQ backends: bounded delta
        got_mrr = mrr_at(results[name][0], qs.rel_doc)
        assert abs(got_mrr - ref_mrr) <= 0.02, (case, name, ref_mrr, got_mrr)
    # the two PQ encodings score the same quantized vectors
    np.testing.assert_allclose(results["sharded-pq"][1], results["pq"][1],
                               rtol=1e-4, atol=1e-4, err_msg=case)


@pytest.mark.parametrize("method", ("interp", "rrf"))
def test_fusion_mode_backend_parity(method, tmp_path):
    """Hybrid serving (fusion method x neighbor-graph expansion) holds the
    same backend-parity contract as the default pipeline: the three exact
    stores bitwise-identical, the two PQ encodings mutually exact — and
    explicit fusion="interp" + expand_depth=0 IS the default config, so
    current serving is reproduced bitwise by construction."""
    from repro import index as index_lib
    from repro.data import synth_corpus, synth_queries

    base = dataclasses.replace(
        get_config("clusd-msmarco", "smoke"),
        n_docs=256, dim=32, n_clusters=16, vocab=128, max_postings=64,
        k_sparse=32, bins=(5, 15, 32), n_candidates=4, max_selected=4,
        n_neighbors=8, u_bins=4, k_final=16)
    # the explicit defaults ARE the default config (depth-0 back-compat)
    assert dataclasses.replace(base, fusion="interp", expand_depth=0) == base
    assert base.n_candidates_total == base.n_candidates
    cfg = dataclasses.replace(base, fusion=method, expand_depth=2)
    assert cfg.n_candidates_total == 12
    corpus = synth_corpus(11, cfg.n_docs, cfg.dim, cfg.vocab)
    index = cl.build_index(cfg, jax.random.key(0), corpus.embeddings,
                           corpus.doc_terms, corpus.doc_weights)
    qs = synth_queries(13, corpus, 12)
    emb = np.asarray(corpus.embeddings)
    pq = quant_lib.train_pq(jax.random.key(1), corpus.embeddings, nsub=8)
    v1 = str(tmp_path / "v1")
    v2 = str(tmp_path / "v2")
    index_lib.write_index(v1, cfg, index, emb, n_shards=3)
    index_lib.write_index(v2, cfg, index, emb, n_shards=3,
                          format_version=index_lib.FORMAT_VERSION_PQ, pq=pq)
    stores = {
        "inmemory": InMemoryStore(index.embeddings, index.cluster_docs),
        "disk": DiskStore.create(str(tmp_path / "blocks.bin"),
                                 index.embeddings, index.cluster_docs),
        "sharded-disk": index_lib.IndexReader.open(v1, verify="full")
        .open_store(cluster_docs=index.cluster_docs),
        "pq": PQStore(pq, index.cluster_docs),
        "sharded-pq": index_lib.IndexReader.open(v2, verify="full")
        .open_store(cluster_docs=index.cluster_docs),
    }
    results = {}
    for name, store in stores.items():
        ids, scores, _ = pipeline.retrieve(cfg, index, store, qs.q_dense,
                                           qs.q_terms, qs.q_weights)
        results[name] = (np.asarray(ids), np.asarray(scores))
    ref_ids, ref_scores = results["inmemory"]
    for name in ("disk", "sharded-disk"):
        np.testing.assert_array_equal(results[name][0], ref_ids,
                                      err_msg=f"{method}:{name}")
        np.testing.assert_allclose(results[name][1], ref_scores,
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=f"{method}:{name}")
    np.testing.assert_allclose(results["sharded-pq"][1], results["pq"][1],
                               rtol=1e-4, atol=1e-4, err_msg=method)
    # depth 0 under the same fusion method only reorders by fused score;
    # it must run (static-shape path) and return valid ids
    ids0, _, _ = pipeline.retrieve(dataclasses.replace(cfg, expand_depth=0),
                                   index, stores["inmemory"], qs.q_dense,
                                   qs.q_terms, qs.q_weights)
    assert ((0 <= np.asarray(ids0)) & (np.asarray(ids0) < cfg.n_docs)).all()


def test_host_scoring_kernel_path_matches(tiny):
    """score_selected_host(use_kernel=True) routes the unique-block dots
    through the cluster_score Pallas kernel — same fused results."""
    cfg, corpus, index, qs = tiny
    with tempfile.TemporaryDirectory() as d:
        store = DiskStore.create(os.path.join(d, "b.bin"),
                                 index.embeddings, index.cluster_docs)
        ids_ref, _, _ = pipeline.retrieve(cfg, index, store, qs.q_dense,
                                          qs.q_terms, qs.q_weights)
        ids_k, _, _ = pipeline.retrieve(cfg, index, store, qs.q_dense,
                                        qs.q_terms, qs.q_weights,
                                        use_kernel=True)
    np.testing.assert_array_equal(np.asarray(ids_k), np.asarray(ids_ref))


# ---------------------------------------------------------------------------
# LRU block cache
# ---------------------------------------------------------------------------

def test_block_cache_hit_miss_accounting():
    c = BlockCache(capacity=4)
    assert c.get(1) is None
    c.put(1, np.ones(3))
    assert np.all(c.get(1) == 1.0)
    assert (c.hits, c.misses) == (1, 1)
    c.get(2)
    assert (c.hits, c.misses) == (1, 2)
    assert c.hit_rate() == pytest.approx(1 / 3)
    st = c.stats()
    assert st["size"] == 1 and st["capacity"] == 4 and st["evictions"] == 0


def test_block_cache_eviction_order():
    c = BlockCache(capacity=2)
    c.put(1, "a")
    c.put(2, "b")
    c.get(1)            # 1 becomes most-recent
    c.put(3, "c")       # evicts 2 (LRU), not 1
    assert 2 not in c and 1 in c and 3 in c
    assert c.evictions == 1
    assert c.keys() == [1, 3]
    c.put(4, "d")       # evicts 1
    assert c.keys() == [3, 4]
    assert c.evictions == 2


def test_block_cache_get_or_fetch_many_single_flight():
    c = BlockCache(capacity=8)
    calls = []

    def fetch(cids):
        calls.append(list(cids))
        return np.stack([np.full(2, cid, np.float32) for cid in cids])

    out = c.get_or_fetch_many([1, 2, 1], fetch)
    assert set(out) == {1, 2} and calls == [[1, 2]]
    # second call: all hits, no new fetch
    out2 = c.get_or_fetch_many([1, 2], fetch)
    assert len(calls) == 1 and np.all(out2[2] == 2.0)
    assert c.hits == 2 and c.misses == 2
    # record=False (prefetch path) doesn't touch hit/miss accounting
    c.get_or_fetch_many([3], fetch, record=False)
    assert (c.hits, c.misses) == (2, 2) and 3 in c and len(calls) == 2


def test_block_cache_rejects_bad_capacity():
    with pytest.raises(ValueError):
        BlockCache(0)
    with pytest.raises(ValueError):
        BlockCache()                              # no bound at all
    with pytest.raises(ValueError):
        BlockCache(4, capacity_bytes=1024)        # ambiguous double bound
    with pytest.raises(ValueError):
        BlockCache(capacity_bytes=0)


def test_block_cache_byte_budget_accounting():
    """capacity_bytes bounds the ACTUAL stored bytes: replacing a block
    re-charges it, eviction refunds it, and stats reports the live total."""
    blk = lambda n: np.zeros(n, np.uint8)         # nbytes == n
    c = BlockCache(capacity_bytes=100)
    c.put(1, blk(40))
    c.put(2, blk(40))
    assert c.cached_bytes == 80 and c.evictions == 0
    c.put(1, blk(10))                             # replace: 40 -> 10
    assert c.cached_bytes == 50 and len(c) == 2
    c.put(3, blk(60))                             # 110 > 100: evict LRU (2)
    assert 2 not in c and c.cached_bytes == 70 and c.evictions == 1
    st = c.stats()
    assert st["cached_bytes"] == 70
    assert st["capacity_bytes"] == 100 and st["capacity"] is None


def test_block_cache_byte_budget_density():
    """The point of code-caching: a byte budget sized for F float blocks
    holds ~4*dim/nsub times more (smaller) code blocks."""
    cap, dim, nsub = 8, 32, 8
    budget = 4 * cap * dim * 4                    # 4 float32 blocks
    floats = BlockCache(capacity_bytes=budget)
    for i in range(10):
        floats.put(i, np.zeros((cap, dim), np.float32))
    assert len(floats) == 4
    codes = BlockCache(capacity_bytes=budget)
    for i in range(100):
        codes.put(i, np.zeros((cap, nsub), np.uint8))
    assert len(codes) == 4 * (4 * dim // nsub)    # 16x more clusters
    assert codes.cached_bytes <= budget


# ---------------------------------------------------------------------------
# bucketing + stage-2 budget fix
# ---------------------------------------------------------------------------

def test_bucket_size_power_of_two():
    assert [bucket_size(n, 64) for n in (1, 2, 3, 5, 8, 9, 33)] == \
        [1, 2, 4, 8, 8, 16, 64]
    assert bucket_size(100, 32) == 32
    with pytest.raises(ValueError):
        bucket_size(0, 32)


def test_stage2_budget_keeps_picked_negative_scores(tiny):
    """Regression for the `-1.0` sentinel bug: selectors emitting scores
    outside [0, 1] (or theta <= 0) must not corrupt the selection mask."""
    from repro.core.lstm import SELECTORS
    cfg, _, index, _ = tiny
    raw = jnp.asarray([[0.9, -0.4, -0.6, 0.2, -2.0, 0.1, -0.3, -5.0]])
    SELECTORS["_raw_test"] = (None, lambda params, feats: params)
    try:
        cand = jnp.arange(8, dtype=jnp.int32)[None, :]
        feats = jnp.zeros((1, 8, 4))
        out = cl.stage2_select(cfg, index, cand, feats,
                               selector="_raw_test", theta=-0.5,
                               selector_params=raw)
    finally:
        del SELECTORS["_raw_test"]
    # picked = score >= -0.5 -> {0.9, -0.4, 0.2, 0.1, -0.3}; budget 4 keeps
    # the top 4 by score, ALL valid (old code masked out every negative one)
    sel = np.asarray(out["sel_ids"])[0][np.asarray(out["sel_mask"])[0]]
    assert set(sel.tolist()) == {0, 3, 5, 6}
    assert int(np.asarray(out["sel_mask"]).sum()) == 4


# ---------------------------------------------------------------------------
# RetrievalEngine end-to-end
# ---------------------------------------------------------------------------

def test_engine_device_bucketing_matches_direct(tiny):
    cfg, _, index, qs = tiny
    ref, _, _ = cl.retrieve(cfg, index, qs.q_dense, qs.q_terms, qs.q_weights)
    eng = RetrievalEngine(cfg, index, max_batch=8)
    out = []
    for lo, hi in ((0, 5), (5, 8), (8, 12)):      # ragged: buckets 8, 4
        ids, _ = eng.retrieve(qs.q_dense[lo:hi], qs.q_terms[lo:hi],
                              qs.q_weights[lo:hi])
        out.append(np.asarray(ids))
    np.testing.assert_array_equal(np.concatenate(out), np.asarray(ref))
    assert eng.stats()["compiled_buckets"] == [4, 8]
    assert eng.serve_stats.n_queries == 12


def test_engine_host_dedups_and_caches(tiny):
    cfg, corpus, index, qs = tiny
    from repro.core import disk as dk
    ref, _, diag = cl.retrieve(cfg, index, qs.q_dense, qs.q_terms,
                               qs.q_weights)
    naive_ops = int(np.asarray(diag["sel_mask"]).sum())
    with tempfile.TemporaryDirectory() as d:
        blocks = dk.DiskClusterStore(os.path.join(d, "b.bin"),
                                     corpus.embeddings, index.cluster_docs)
        with RetrievalEngine(cfg, index,
                             store=DiskStore(blocks, index.cluster_docs),
                             max_batch=16, cache_capacity=32) as eng:
            ids, _ = eng.retrieve(qs.q_dense, qs.q_terms, qs.q_weights)
            ops_first = eng.store.stats.n_ops
            # second identical pass: blocks already cached (incl. prefetch)
            ids2, _ = eng.retrieve(qs.q_dense, qs.q_terms, qs.q_weights)
        st = eng.stats()    # after close(): prefetch drained, counters final
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(ids2), np.asarray(ref))
    # dedup across the batch: strictly fewer reads than one per (q, cluster)
    assert 0 < ops_first < naive_ops
    assert st["cache"]["hits"] > 0
    # the second pass was served without growing serving-path reads beyond
    # the unique-cluster set (prefetch may add candidate blocks, n <= N)
    assert st["io"]["n_ops"] <= index.n_clusters + ops_first


# ---------------------------------------------------------------------------
# ADC serving: code-backed stores through the fused engine tail
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def v2_reader(tiny, tmp_path_factory):
    """A format-v2 (PQ code shard) index over the tiny corpus, with an OPQ
    rotation so the LUT folding is exercised."""
    from repro import index as index_lib
    cfg, corpus, index, _ = tiny
    pq = quant_lib.train_pq(jax.random.key(1), corpus.embeddings, nsub=8,
                            rotate=True)
    out = str(tmp_path_factory.mktemp("adc") / "v2")
    index_lib.write_index(out, cfg, index, np.asarray(corpus.embeddings),
                          n_shards=3,
                          format_version=index_lib.FORMAT_VERSION_PQ, pq=pq)
    return index_lib.IndexReader.open(out, verify="full")


def test_engine_adc_matches_decode_path(tiny, v2_reader):
    """Backend parity for the code path: the ADC engine (raw codes ->
    LUT scoring, zero host decode) returns the SAME fused top-k as the
    decode-then-score engine over the same v2 index — scores included."""
    _, _, _, qs = tiny
    res = {}
    for use_adc in (True, False):
        with v2_reader.engine(max_batch=16, cache_capacity=32,
                              use_adc=use_adc) as eng:
            ids, scores = eng.retrieve(qs.q_dense, qs.q_terms, qs.q_weights)
            st = eng.stats()
        res[use_adc] = (np.asarray(ids), np.asarray(scores), st)
    ids_adc, sc_adc, st_adc = res[True]
    ids_dec, sc_dec, st_dec = res[False]
    np.testing.assert_array_equal(ids_adc, ids_dec)
    np.testing.assert_allclose(sc_adc, sc_dec, rtol=1e-5, atol=1e-5)
    # the ADC path never decoded a float block on the host
    assert st_adc["use_adc"] and st_adc["decode_ms"] == 0.0
    assert "adc_ms" in st_adc and "lut_build_ms" not in st_adc
    assert not st_dec["use_adc"] and st_dec["decode_ms"] > 0.0
    # both paths read CODE bytes off disk (same shards)
    assert st_adc["io"]["bytes"] > 0
    # the cache holds code blocks under its byte budget
    assert 0 < st_adc["cache"]["cached_bytes"] \
        <= st_adc["cache"]["capacity_bytes"]


def test_engine_adc_auto_detection_and_validation(tiny, v2_reader):
    """use_adc=None auto-enables exactly for code-backed host stores;
    use_adc=True on a float store is a loud error."""
    cfg, corpus, index, _ = tiny
    with v2_reader.engine(max_batch=16) as eng:
        assert eng.use_adc                        # auto: v2 store is coded
    from repro.core import disk as dk
    with tempfile.TemporaryDirectory() as d:
        blocks = dk.DiskClusterStore(os.path.join(d, "b.bin"),
                                     corpus.embeddings, index.cluster_docs)
        store = DiskStore(blocks, index.cluster_docs)
        with RetrievalEngine(cfg, index, store=store, max_batch=16) as eng:
            assert not eng.use_adc
        with pytest.raises(ValueError):
            RetrievalEngine(cfg, index, store=store, use_adc=True)


def test_engine_adc_empty_selection(tiny, v2_reader):
    """All-padding sparse input (nothing selected) serves cleanly through
    the fused ADC tail with zero block I/O for scoring."""
    _, _, _, qs = tiny
    qt = np.full_like(np.asarray(qs.q_terms), -1)
    qw = np.zeros_like(np.asarray(qs.q_weights))
    with v2_reader.engine(max_batch=16, prefetch=False) as eng:
        ids, scores = eng.retrieve(qs.q_dense, qt, qw)
    assert np.asarray(ids).shape == (len(np.asarray(qs.q_dense)), eng.k)
    assert not np.isnan(np.asarray(scores)).any()


# ---------------------------------------------------------------------------
# index arrays are jit arguments, never compiled-in constants
# ---------------------------------------------------------------------------

def _nbytes(*trees):
    return sum(int(x.nbytes) for t in trees for x in jax.tree.leaves(t))


@pytest.mark.parametrize("path", ["device", "host"])
def test_compiled_programs_take_index_as_arguments(tiny, path):
    """The engine's compiled stage-1 and device programs report argument
    bytes covering the index arrays they read: nothing is captured as a
    constant (a captured array shows up as constant HLO and 0 argument
    bytes)."""
    cfg, corpus, index, qs = tiny
    with tempfile.TemporaryDirectory() as d:
        store = None if path == "device" else DiskStore.create(
            os.path.join(d, "blocks.bin"), index.embeddings,
            index.cluster_docs)
        eng = RetrievalEngine(cfg, index, store=store, max_batch=4,
                              prefetch=False)
        eng.retrieve(qs.q_dense[:4], qs.q_terms[:4], qs.q_weights[:4])
        qd, qt, qw = qs.q_dense[:4], qs.q_terms[:4], qs.q_weights[:4]
        stage1 = pipeline.build_stage1_fn(cfg).lower(
            eng.index, qd, qt, qw).compile()
        read = _nbytes(eng.index.sparse_index, eng.index.centroids,
                       eng.index.doc_cluster)
        assert stage1.memory_analysis().argument_size_in_bytes >= read
        if path == "device":
            assert ("device", 4) in eng._fns
            dev = eng._fns[("device", 4)].lower(
                eng.index, eng.store, qd, qt, qw).compile()
            read = _nbytes(eng.store.embeddings, eng.index.sparse_index,
                           eng.index.cluster_docs, eng.index.centroids)
            assert dev.memory_analysis().argument_size_in_bytes >= read
        eng.close()
