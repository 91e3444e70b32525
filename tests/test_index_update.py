"""Incremental index update tests (repro.index.update):

  * property (hypothesis, stub-compatible): ANY sequence of upsert/delete
    deltas applied on disk, followed by compaction, equals `write_index`
    of the same deltas applied in memory — byte-level for v1 block shards
    and arrays, code-level for v2 PQ shards (+ identical CSR postings)
  * a delta stamped for format v2 is rejected cleanly against a v1 index
    (and vice versa)
  * deletes rewrite ZERO shard bytes (tombstones) yet deleted docs vanish
    from dense fetch, sparse postings, and served top-k
  * atomic generations: commits bump the generation, archive the old
    manifest (still loadable + fully verifiable), refresh() adopts newer
    generations exactly once
  * RetrievalEngine.reload_index(): one engine serves across a commit with
    no failed requests, an invalidated block cache, and the new corpus
  * overflowing upserts trigger local shard re-clustering, preserving the
    compaction invariant
"""

import dataclasses
import os
import shutil

import jax
import numpy as np
import pytest

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
except ImportError:                      # fall back to deterministic sweeps
    from _hypothesis_stub import given, settings
    from _hypothesis_stub import strategies as st

from test_index_properties import _random_index

from repro import index as index_lib
from repro.core import quant as quant_lib
from repro.index import format as fmt

jnp = pytest.importorskip("jax.numpy")


def _random_delta(rng, doc_cluster, n_slots, dim, vocab, dmax=3):
    """Feasible random delta against the current state: up to `dmax` each
    of deletes, replacements, and appends (appends bounded by free
    capacity)."""
    doc_cluster = np.asarray(doc_cluster)
    D = len(doc_cluster)
    live = np.flatnonzero(doc_cluster >= 0)
    n_del = int(rng.integers(0, min(dmax, len(live)) + 1))
    dele = rng.choice(live, n_del, replace=False) if n_del else \
        np.zeros(0, np.int64)
    rest = np.setdiff1d(live, dele)
    n_rep = int(rng.integers(0, min(dmax, len(rest)) + 1))
    reps = rng.choice(rest, n_rep, replace=False) if n_rep else \
        np.zeros(0, np.int64)
    free = n_slots - (len(live) - n_del - n_rep)
    n_app = int(rng.integers(0, max(0, min(dmax, free - n_rep)) + 1))
    ids = np.concatenate([reps, np.arange(D, D + n_app)]).astype(np.int64)
    U, T = len(ids), 4
    terms = rng.integers(0, vocab, (U, T)).astype(np.int32)
    terms[rng.random((U, T)) < 0.25] = -1
    weights = rng.lognormal(0.0, 0.5, (U, T)).astype(np.float32)
    return index_lib.IndexDelta(
        upsert_ids=ids,
        upsert_embeddings=rng.standard_normal((U, dim)).astype(np.float32),
        upsert_terms=terms, upsert_weights=weights, delete_ids=dele)


def _assert_same_artifacts(dir_a, man_a, dir_b, man_b):
    """Byte-compare every array and every block shard of two indexes."""
    assert set(man_a["arrays"]) == set(man_b["arrays"])
    for name, rel in man_a["arrays"].items():
        with open(os.path.join(dir_a, rel), "rb") as f:
            a = f.read()
        with open(os.path.join(dir_b, man_b["arrays"][name]), "rb") as f:
            b = f.read()
        assert a == b, f"array {name} differs"
    assert len(man_a["block_shards"]) == len(man_b["block_shards"])
    for s1, s2 in zip(man_a["block_shards"], man_b["block_shards"]):
        with open(os.path.join(dir_a, s1["file"]), "rb") as f:
            a = f.read()
        with open(os.path.join(dir_b, s2["file"]), "rb") as f:
            b = f.read()
        assert a == b, f"shard {s1['file']} differs"


def _policy_vectors(index, delta):
    """What re-clustering sees on disk: a v2 index stores only codes, so
    existing docs are their PQ decodes and the delta's rows are exact
    (None for v1, where the stored floats ARE the embeddings)."""
    q = index.quantizer
    if q is None:
        return None
    codes = np.asarray(q.codes)
    vecs = quant_lib.decode_code_blocks(q.codebooks, codes, q.rotation)
    n_new = int((delta.upsert_ids >= len(codes)).sum())
    vecs = np.concatenate([vecs, np.zeros((n_new, vecs.shape[1]),
                                          np.float32)])
    vecs[delta.upsert_ids] = delta.upsert_embeddings
    return vecs


def _run_delta_sequence(tmp_root, seed, format_version, n_deltas=2):
    """Shared property body: random index -> write -> delta sequence on
    disk -> compact; vs the same deltas applied in memory -> write."""
    cfg, index, emb = _random_index(seed)
    cfg = dataclasses.replace(
        cfg, max_postings=int(np.asarray(
            index.sparse_index.postings_docs).shape[1]))
    n_shards = 1 + seed % 3
    pq = None
    if format_version == index_lib.FORMAT_VERSION_PQ:
        nsub = 4 if emb.shape[1] % 4 == 0 else 8
        pq = quant_lib.train_pq(jax.random.key(seed), jnp.asarray(emb), nsub,
                                iters=2)
        index.quantizer = pq
    out = str(tmp_root / "live")
    index_lib.write_index(out, cfg, index, emb, n_shards=n_shards,
                          format_version=format_version, pq=pq)

    rng = np.random.default_rng(seed + 1)
    ref_index, ref_emb, ref_cfg = index, emb, cfg
    for _ in range(n_deltas):
        n_slots = int(np.asarray(ref_index.cluster_docs).size)
        delta = _random_delta(rng, np.asarray(ref_index.doc_cluster),
                              n_slots, emb.shape[1], cfg.vocab)
        report = index_lib.write_index_delta(out, delta)
        assert report["bytes_rewritten"] <= report["shard_bytes_total"]
        if delta.n_upserts == 0:         # delete-only: zero-rewrite
            assert report["bytes_rewritten"] == 0
        ref_index, ref_emb, _ = index_lib.apply_delta_to_index(
            ref_cfg, ref_index, ref_emb, delta, n_shards=n_shards,
            policy_vectors=_policy_vectors(ref_index, delta))
        ref_cfg = dataclasses.replace(ref_cfg, n_docs=ref_index.n_docs)

    man_live = index_lib.compact_index(out)
    ref_out = str(tmp_root / "ref")
    man_ref = index_lib.write_index(
        ref_out, ref_cfg, ref_index, ref_emb, n_shards=n_shards,
        format_version=format_version, pq=ref_index.quantizer)
    _assert_same_artifacts(out, man_live, ref_out, man_ref)
    # the compacted index is fully valid + verifiable
    index_lib.IndexReader.open(out, verify="full")


@settings(max_examples=6, deadline=None)
@given(st.integers(1, 10_000))
def test_delta_sequence_then_compaction_equals_rebuild_v1(tmp_path_factory,
                                                          seed):
    _run_delta_sequence(tmp_path_factory.mktemp("upd_v1"), seed,
                        index_lib.FORMAT_VERSION)


@settings(max_examples=5, deadline=None)
@given(st.integers(1, 10_000))
def test_delta_sequence_then_compaction_equals_rebuild_v2(tmp_path_factory,
                                                          seed):
    _run_delta_sequence(tmp_path_factory.mktemp("upd_v2"), seed,
                        index_lib.FORMAT_VERSION_PQ)


# ---------------------------------------------------------------------------
# fixed scenarios on a real (k-means-built) index
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def live_index(tmp_path_factory):
    """A real tiny index on disk + its corpus, rebuilt per module."""
    from test_index import _tiny_cfg
    from repro.core import clusd as cl
    from repro.data import synth_corpus

    cfg = _tiny_cfg()
    corpus = synth_corpus(11, cfg.n_docs, cfg.dim, cfg.vocab)
    emb = np.asarray(corpus.embeddings, np.float32)
    index = cl.build_index(cfg, jax.random.key(0), corpus.embeddings,
                           corpus.doc_terms, corpus.doc_weights)
    src = str(tmp_path_factory.mktemp("upd_live") / "index")
    index_lib.write_index(src, cfg, index, emb, n_shards=4)
    return cfg, corpus, index, emb, src


def _fresh_copy(src, tmp_path, name="idx"):
    dst = str(tmp_path / name)
    shutil.copytree(src, dst)
    return dst


def _delta_from_corpus(cfg, corpus, *, upsert_ids, delete_ids, seed=3):
    rng = np.random.default_rng(seed)
    U = len(upsert_ids)
    emb = rng.standard_normal((U, cfg.dim)).astype(np.float32)
    emb /= np.linalg.norm(emb, axis=1, keepdims=True)
    terms = rng.integers(0, cfg.vocab, (U, 8)).astype(np.int32)
    weights = rng.lognormal(0.0, 0.5, (U, 8)).astype(np.float32)
    return index_lib.IndexDelta(
        upsert_ids=np.asarray(upsert_ids, np.int64),
        upsert_embeddings=emb, upsert_terms=terms, upsert_weights=weights,
        delete_ids=np.asarray(delete_ids, np.int64))


def test_wrong_format_delta_rejected(live_index, tmp_path):
    """Satellite acceptance: a v2 delta against a v1 index fails up front
    with IndexFormatError (and a v1 delta against a v2 index likewise)."""
    cfg, corpus, index, emb, src = live_index
    out = _fresh_copy(src, tmp_path)
    delta = _delta_from_corpus(cfg, corpus, upsert_ids=[0], delete_ids=[])
    delta.format_version = index_lib.FORMAT_VERSION_PQ
    with pytest.raises(index_lib.IndexFormatError, match="format"):
        index_lib.write_index_delta(out, delta)
    # nothing was committed: still generation 0, fully verifiable
    reader = index_lib.IndexReader.open(out, verify="full")
    assert reader.generation == 0

    pq = quant_lib.train_pq(jax.random.key(1), jnp.asarray(emb), nsub=8,
                            iters=2)
    out_v2 = str(tmp_path / "v2")
    index_lib.write_index(out_v2, cfg, index, emb, n_shards=2,
                          format_version=index_lib.FORMAT_VERSION_PQ, pq=pq)
    delta.format_version = index_lib.FORMAT_VERSION
    with pytest.raises(index_lib.IndexFormatError, match="format"):
        index_lib.write_index_delta(out_v2, delta)


def test_delete_only_delta_is_zero_rewrite_and_masks(live_index, tmp_path):
    cfg, corpus, index, emb, src = live_index
    out = _fresh_copy(src, tmp_path)
    dele = np.asarray([5, 17, 200, 201, 202], np.int64)
    victim_clusters = np.asarray(index.doc_cluster)[dele]
    delta = _delta_from_corpus(cfg, corpus, upsert_ids=[], delete_ids=dele)
    report = index_lib.write_index_delta(out, delta)
    assert report["bytes_rewritten"] == 0
    assert report["shards_rewritten"] == []

    reader = index_lib.IndexReader.open(out, verify="full")
    tomb = reader.tombstones()
    assert tomb is not None and tomb.sum() == len(dele)
    # the store masks tombstoned slots at fetch time: same bytes on disk,
    # docs reported -1/invalid
    store = reader.open_store()
    _, docs, valid = store.fetch_blocks(np.unique(victim_clusters))
    assert not np.isin(docs, dele).any()
    # deleted docs are gone from the loaded index's doc table and postings
    _, lindex = reader.load_index()
    assert not np.isin(np.asarray(lindex.cluster_docs), dele).any()
    assert not np.isin(np.asarray(lindex.sparse_index.postings_docs),
                       dele).any()
    assert np.all(np.asarray(lindex.doc_cluster)[dele] == -1)


def test_generation_archive_and_refresh(live_index, tmp_path):
    cfg, corpus, index, emb, src = live_index
    out = _fresh_copy(src, tmp_path)
    reader = index_lib.IndexReader.open(out)
    assert reader.generation == 0
    for i in range(2):
        delta = _delta_from_corpus(
            cfg, corpus, upsert_ids=[cfg.n_docs + i], delete_ids=[],
            seed=20 + i)
        index_lib.write_index_delta(out, delta)
    # stale reader sees gen 0 until refresh; refresh adopts exactly once
    assert reader.generation == 0
    assert reader.refresh() is True
    assert reader.generation == 2
    assert reader.refresh() is False
    # every older generation stays loadable AND fully verifiable
    for g in (0, 1):
        man = index_lib.load_manifest(out, generation=g)
        assert index_lib.manifest_generation(man) == g
        fmt.verify_files(out, man, level="full")
    with pytest.raises(index_lib.IndexFormatError, match="generation"):
        index_lib.load_manifest(out, generation=7)
    # compaction drops the history but keeps the lineage stamp
    man = index_lib.compact_index(out)
    assert man["generation"] == 3 and man["parent_generation"] == 2
    index_lib.IndexReader.open(out, verify="full")


def test_engine_hot_reload_serves_across_commit(live_index, tmp_path):
    from repro.data import synth_queries
    cfg, corpus, index, emb, src = live_index
    out = _fresh_copy(src, tmp_path)
    reader = index_lib.IndexReader.open(out)
    qs = synth_queries(7, corpus, 8)
    dele = np.asarray([40, 41, 42], np.int64)
    with reader.engine(max_batch=8, cache_capacity=64) as eng:
        pre_ids, _ = eng.retrieve(qs.q_dense, qs.q_terms, qs.q_weights)
        assert eng.stats()["generation"] == 0
        assert eng.stats()["cache"]["size"] > 0
        delta = _delta_from_corpus(
            cfg, corpus,
            upsert_ids=np.arange(cfg.n_docs, cfg.n_docs + 4),
            delete_ids=dele)
        index_lib.write_index_delta(out, delta)
        # old generation keeps serving until the explicit swap
        mid_ids, _ = eng.retrieve(qs.q_dense, qs.q_terms, qs.q_weights)
        np.testing.assert_array_equal(np.asarray(mid_ids),
                                      np.asarray(pre_ids))
        gen = eng.reload_index()
        assert gen == 1
        st = eng.stats()
        assert st["generation"] == 1 and st["reloads"] == 1
        assert st["cache"]["size"] == 0 and st["cache"]["clears"] >= 1
        post_ids, _ = eng.retrieve(qs.q_dense, qs.q_terms, qs.q_weights)
        assert not np.isin(np.asarray(post_ids), dele).any()
        assert eng.index.n_docs == cfg.n_docs + 4
    # engines not built from a reader refuse to reload
    from repro.engine import RetrievalEngine, InMemoryStore
    mem_eng = RetrievalEngine(cfg, index,
                              store=InMemoryStore(corpus.embeddings,
                                                  index.cluster_docs))
    with pytest.raises(ValueError, match="reader"):
        mem_eng.reload_index()


def test_overflow_triggers_local_recluster_and_keeps_parity(tmp_path):
    """Pack clusters to capacity, then upsert into them: placements
    overflow to next-nearest clusters, the shard re-clusters locally, and
    the compaction invariant still holds byte-for-byte."""
    from test_index_properties import _random_index
    cfg, index, emb = _random_index(17)
    cfg = dataclasses.replace(
        cfg, max_postings=int(np.asarray(
            index.sparse_index.postings_docs).shape[1]))
    cd = np.asarray(index.cluster_docs)
    n_clusters, cap = cd.shape
    out = str(tmp_path / "live")
    index_lib.write_index(out, cfg, index, emb, n_shards=2)

    rng = np.random.default_rng(0)
    live = np.flatnonzero(np.asarray(index.doc_cluster) >= 0)
    n_free = n_clusters * cap - len(live)
    dele = rng.choice(live, min(4, len(live) - 1), replace=False)
    n_app = min(4, n_free + len(dele))
    D = len(np.asarray(index.doc_cluster))
    delta = index_lib.IndexDelta(
        upsert_ids=np.arange(D, D + n_app),
        upsert_embeddings=rng.standard_normal(
            (n_app, emb.shape[1])).astype(np.float32),
        upsert_terms=rng.integers(0, cfg.vocab, (n_app, 4)).astype(np.int32),
        upsert_weights=rng.lognormal(0, 0.5, (n_app, 4)).astype(np.float32),
        delete_ids=dele)
    kw = dict(recluster_overflow=0.0, recluster_min_overflow=0,
              lloyd_iters=2)
    report = index_lib.write_index_delta(out, delta, **kw)
    assert report["reclustered_shards"], "recluster did not trigger"

    ref_index, ref_emb, ref_report = index_lib.apply_delta_to_index(
        cfg, index, emb, delta, n_shards=2, **kw)
    assert ref_report["reclustered_shards"] == report["reclustered_shards"]
    man_live = index_lib.compact_index(out)
    ref_out = str(tmp_path / "ref")
    man_ref = index_lib.write_index(
        ref_out, dataclasses.replace(cfg, n_docs=ref_index.n_docs),
        ref_index, ref_emb, n_shards=2)
    _assert_same_artifacts(out, man_live, ref_out, man_ref)
