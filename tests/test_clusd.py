"""CluSD system tests: stage-1 invariants (hypothesis property tests),
LSTM training improves selection, end-to-end quality, fusion exactness."""

import dataclasses

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

from repro.configs import get_config
from repro.core import bins as bins_lib
from repro.core import clusd as cl
from repro.core import fusion as fusion_lib
from repro.core import sparse as sparse_lib
from repro.core import stage1 as stage1_lib
from repro.core import train_lstm as tl
from repro.data import mrr_at, recall_at, synth_corpus, synth_queries


@pytest.fixture(scope="module")
def small_index():
    cfg = get_config("clusd-msmarco", "smoke")
    corpus = synth_corpus(0, cfg.n_docs, cfg.dim, cfg.vocab)
    index = cl.build_index(cfg, jax.random.key(0), corpus.embeddings,
                           corpus.doc_terms, corpus.doc_weights)
    return cfg, corpus, index


# ---------------------------------------------------------------------------
# stage 1 properties
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_multikey_sort_is_lexicographic(seed):
    rng = np.random.default_rng(seed)
    N, v, n = 40, 4, 10
    P = jnp.asarray(rng.integers(0, 4, (1, N, v)), jnp.float32)
    sim = jnp.asarray(rng.random((1, N)), jnp.float32)
    got = np.asarray(stage1_lib.sort_by_overlap(P, sim, n))[0]
    keys = [tuple(-np.asarray(P[0, c])) + (-float(sim[0, c]),)
            for c in range(N)]
    want = sorted(range(N), key=lambda c: keys[c])[:n]
    assert list(got) == list(want)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_overlap_counts_match_bruteforce(seed):
    rng = np.random.default_rng(seed)
    D, N, k, v = 200, 16, 50, 4
    bins = (5, 15, 30, 50)
    doc_cluster = jnp.asarray(rng.integers(0, N, D), jnp.int32)
    top = jnp.asarray(rng.choice(D, (2, k), replace=False), jnp.int32)
    scores = jnp.asarray(rng.random((2, k)), jnp.float32)
    bin_ids = bins_lib.rank_bin_ids(bins, k)
    P, Q = bins_lib.overlap_features(top, scores, doc_cluster, N, bin_ids, v)
    P, Q = np.asarray(P), np.asarray(Q)
    dc = np.asarray(doc_cluster)
    bi = np.asarray(bin_ids)
    for b in range(2):
        for c in range(N):
            for j in range(v):
                members = [i for i in range(k)
                           if dc[top[b, i]] == c and bi[i] == j]
                assert P[b, c, j] == len(members)
                if members:
                    np.testing.assert_allclose(
                        Q[b, c, j],
                        np.mean([scores[b, i] for i in members]), rtol=1e-5)


def test_sparse_retrieval_exact_when_untruncated():
    """With max_postings >= D the inverted-index score equals brute force."""
    rng = np.random.default_rng(3)
    D, V, T = 300, 64, 8
    dt = rng.integers(0, V, (D, T)).astype(np.int32)
    dw = rng.random((D, T)).astype(np.float32)
    idx = sparse_lib.SparseIndex.build(dt, dw, V, max_postings=D)
    qt = jnp.asarray(rng.integers(0, V, (4, 5)), jnp.int32)
    qw = jnp.asarray(rng.random((4, 5)), jnp.float32)
    _, _, scores = sparse_lib.sparse_retrieve(idx, qt, qw, 10)
    # brute force: dense doc-term matrix
    M = np.zeros((D, V), np.float32)
    for d in range(D):
        for t, w in zip(dt[d], dw[d]):
            M[d, t] += w
    Q = np.zeros((4, V), np.float32)
    for b in range(4):
        for t, w in zip(np.asarray(qt[b]), np.asarray(qw[b])):
            Q[b, t] += w
    np.testing.assert_allclose(np.asarray(scores), Q @ M.T, rtol=1e-4,
                               atol=1e-4)


# ---------------------------------------------------------------------------
# fusion
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_fusion_merge_equals_scatter(seed):
    rng = np.random.default_rng(seed)
    D, Ks, Kd, k = 500, 40, 60, 20
    sid = jnp.asarray(rng.choice(D, (2, Ks), replace=False), jnp.int32)
    ss = jnp.asarray(rng.random((2, Ks)), jnp.float32)
    did = jnp.asarray(rng.choice(D, (2, Kd), replace=False), jnp.int32)
    ds = jnp.asarray(rng.random((2, Kd)), jnp.float32)
    dm = jnp.asarray(rng.random((2, Kd)) > 0.2)
    a = 0.5
    i1, s1 = fusion_lib.fuse_topk(sid, ss, did, jnp.where(dm, ds, 0.0), dm,
                                  D, a, k)
    i2, s2 = fusion_lib.fuse_topk_merge(sid, ss, did, jnp.where(dm, ds, 0.0),
                                        dm, a, k, sentinel=D + 7)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-5,
                               atol=1e-6)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_fusion_merge_equals_scatter_any_multiplicity(seed):
    """Merge-path == scatter-oracle with ids drawn WITH replacement (a doc
    may repeat within a side and across sides at any multiplicity) and a
    ragged valid prefix on the sparse side — for both fusion methods."""
    rng = np.random.default_rng(seed)
    D, Ks, Kd, k = 120, 30, 40, 15
    sid = jnp.asarray(rng.integers(0, D, (3, Ks)), jnp.int32)
    ss = jnp.asarray(np.sort(rng.random((3, Ks)))[:, ::-1].copy(),
                     jnp.float32)
    sm = jnp.arange(Ks)[None, :] < jnp.asarray(
        rng.integers(0, Ks + 1, (3, 1)))             # ragged prefix
    did = jnp.asarray(rng.integers(0, D, (3, Kd)), jnp.int32)
    ds = jnp.asarray(rng.random((3, Kd)), jnp.float32)
    dm = jnp.asarray(rng.random((3, Kd)) > 0.2)
    a = 0.43                                         # != 0.5: no cross-side
    for method in fusion_lib.FUSION_METHODS:         # rank ties under rrf
        i1, s1 = fusion_lib.fuse_topk(
            sid, ss, did, ds, dm, D, a, k, sparse_mask=sm, method=method)
        i2, s2 = fusion_lib.fuse_topk_merge(
            sid, ss, did, ds, dm, a, k, sentinel=D + 7, sparse_mask=sm,
            method=method)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                                   rtol=1e-5, atol=1e-6, err_msg=method)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2),
                                      err_msg=method)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_fusion_ignores_sparse_padding(seed):
    """Regression for the padding bug: entries behind the sparse valid
    mask must not shift normalization, ranks, or the fused top-k — two
    different junk tails under the same mask fuse bitwise identically."""
    rng = np.random.default_rng(seed)
    D, Ks, Kd, k = 200, 24, 24, 10
    n_valid = int(rng.integers(1, Ks))
    sid_v = rng.choice(D, n_valid, replace=False).astype(np.int32)
    ss_v = np.sort(rng.random(n_valid).astype(np.float32))[::-1].copy()
    sm = jnp.asarray((np.arange(Ks) < n_valid)[None, :])
    did = jnp.asarray(rng.choice(D, (1, Kd), replace=False), jnp.int32)
    ds = jnp.asarray(rng.random((1, Kd)), jnp.float32)
    dm = jnp.ones((1, Kd), bool)

    def pad(junk_ids, junk_scores):
        sid = np.concatenate([sid_v, junk_ids]).astype(np.int32)
        ss = np.concatenate([ss_v, junk_scores]).astype(np.float32)
        return jnp.asarray(sid[None, :]), jnp.asarray(ss[None, :])

    pads = [pad(rng.integers(0, D, Ks - n_valid),
                rng.random(Ks - n_valid) * 10 - 5),
            pad(np.zeros(Ks - n_valid, np.int64),
                np.full(Ks - n_valid, 99.0))]
    for method in fusion_lib.FUSION_METHODS:
        outs = []
        for sid, ss in pads:
            i1, s1 = fusion_lib.fuse_topk(sid, ss, did, ds, dm, D, 0.5, k,
                                          sparse_mask=sm, method=method)
            i2, s2 = fusion_lib.fuse_topk_merge(sid, ss, did, ds, dm, 0.5,
                                                k, sentinel=D + 7,
                                                sparse_mask=sm,
                                                method=method)
            outs.append((np.asarray(i1), np.asarray(s1),
                         np.asarray(i2), np.asarray(s2)))
        for got, want in zip(outs[0], outs[1]):
            np.testing.assert_array_equal(got, want, err_msg=method)


def test_rrf_matches_rank_oracle():
    """Weighted-RRF fused scores equal the textbook sum over both lists:
    weight / (rrf_k + 1-based rank among valid entries)."""
    D, k, a, K = 50, 6, 0.4, 60.0
    sid = np.array([[3, 5, 7, 9]], np.int32)
    ss = np.array([[9.0, 5.0, 1.0, 0.5]], np.float32)
    sm = np.array([[True, True, True, False]])     # 9 is padding
    did = np.array([[5, 2, 11]], np.int32)
    ds = np.array([[8.0, 6.0, 4.0]], np.float32)
    dm = np.array([[True, True, False]])           # 11 is a dead slot
    acc = {}
    for ids, scores, mask, w in ((sid, ss, sm, a), (did, ds, dm, 1 - a)):
        order = np.argsort(-scores[0][mask[0]], kind="stable")
        for rank, j in enumerate(order, start=1):
            doc = int(ids[0][mask[0]][j])
            acc[doc] = acc.get(doc, 0.0) + w / (K + rank)
    want = sorted(acc.items(), key=lambda kv: (-kv[1], kv[0]))[:k]
    ids, scores = fusion_lib.fuse_topk(
        jnp.asarray(sid), jnp.asarray(ss), jnp.asarray(did),
        jnp.asarray(ds), jnp.asarray(dm), D, a, k,
        sparse_mask=jnp.asarray(sm), method="rrf", rrf_k=K)
    got = list(zip(np.asarray(ids)[0][:len(want)].tolist(),
                   np.asarray(scores)[0][:len(want)].tolist()))
    for (gi, gs), (wi, ws) in zip(got, want):
        assert gi == wi, (got, want)
        np.testing.assert_allclose(gs, ws, rtol=1e-6)


def test_fusion_rejects_unknown_method():
    z = jnp.zeros((1, 4))
    zi = jnp.zeros((1, 4), jnp.int32)
    m = jnp.ones((1, 4), bool)
    with pytest.raises(ValueError):
        fusion_lib.fuse_topk(zi, z, zi, z, m, 8, 0.5, 2, method="borda")


# ---------------------------------------------------------------------------
# stage-1 neighbor-graph expansion
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_expand_candidates_invariants(seed):
    rng = np.random.default_rng(seed)
    N, n, m, B, depth = 24, 5, 6, 3, 2
    S = rng.random((N, N)).astype(np.float32)
    np.fill_diagonal(S, -1.0)                      # graph excludes self
    nid = np.argsort(-S, axis=1)[:, :m].astype(np.int32)
    nsim = np.take_along_axis(S, nid, axis=1).astype(np.float32)
    qc = rng.random((B, N)).astype(np.float32)
    cand = np.stack([rng.choice(N, n, replace=False)
                     for _ in range(B)]).astype(np.int32)
    n_out = min(n * (1 + depth), N)
    out = np.asarray(stage1_lib.expand_candidates(
        jnp.asarray(cand), jnp.asarray(nid), jnp.asarray(nsim),
        jnp.asarray(qc), depth, n_out))
    assert out.shape == (B, n_out) and out.dtype == np.int32
    for b in range(B):
        assert list(out[b, :n]) == list(cand[b])   # seed prefix untouched
        assert len(set(out[b].tolist())) == n_out  # all-distinct
        assert ((0 <= out[b]) & (out[b] < N)).all()
        reach = ({int(c) for s in cand[b] for c in nid[s, :depth]}
                 - set(cand[b].tolist()))
        take = min(len(reach), n_out - n)
        # graph-reached clusters fill the extension before any IVF fill
        assert set(out[b, n:n + take].tolist()) <= reach
        if len(reach) <= n_out - n:
            assert reach <= set(out[b, n:].tolist())
    # depth 0 (or no headroom) is the identity — the current pipeline
    out0 = stage1_lib.expand_candidates(
        jnp.asarray(cand), jnp.asarray(nid), jnp.asarray(nsim),
        jnp.asarray(qc), 0, n_out)
    np.testing.assert_array_equal(np.asarray(out0), cand)
    same = stage1_lib.expand_candidates(
        jnp.asarray(cand), jnp.asarray(nid), jnp.asarray(nsim),
        jnp.asarray(qc), depth, n)
    np.testing.assert_array_equal(np.asarray(same), cand)
    with pytest.raises(ValueError):
        stage1_lib.expand_candidates(
            jnp.asarray(cand), jnp.asarray(nid), jnp.asarray(nsim),
            jnp.asarray(qc), depth, N + 1)


def test_fused_equals_full_when_all_selected(small_index):
    """If every cluster is selected, CluSD's dense side equals brute force."""
    cfg, corpus, index = small_index
    q = synth_queries(5, corpus, 8)
    big = dataclasses.replace(cfg, theta=0.0,
                              max_selected=cfg.n_candidates)
    sel_ids = jnp.tile(jnp.arange(cfg.n_clusters, dtype=jnp.int32)[None],
                       (8, 1))
    sel_mask = jnp.ones_like(sel_ids, bool)
    did, dscore, dmask = cl.score_selected(index, q.q_dense, sel_ids, sel_mask)
    full = np.asarray(q.q_dense @ index.embeddings.T)
    ds = np.asarray(jnp.where(dmask, dscore, -np.inf))
    ids = np.asarray(did)
    for b in range(8):
        valid = np.isfinite(ds[b])
        np.testing.assert_allclose(ds[b][valid], full[b][ids[b][valid]],
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# LSTM training + end-to-end
# ---------------------------------------------------------------------------

def test_lstm_training_improves_selection(small_index):
    cfg, corpus, index = small_index
    tq = synth_queries(1, corpus, 128)
    cand, feats, labels = tl.make_labels(cfg, index, tq.q_dense, tq.q_terms,
                                         tq.q_weights)
    params, hist = tl.train_selector(cfg, jax.random.key(2),
                                     np.asarray(feats), np.asarray(labels),
                                     epochs=30, batch_size=32, lr=0.01)
    assert hist[-1] < hist[0] * 0.9
    from repro.core.lstm import lstm_apply
    probs = lstm_apply(params, feats)
    # theta=0.02 is the paper's permissive serving threshold (selects ~2/3 of
    # candidates); separation is tested at the 0.5 operating point.
    q = tl.selection_quality(probs, labels, 0.5)
    assert float(q["precision"]) > float(labels.mean()) * 1.2
    assert float(q["recall"]) > 0.2


def test_end_to_end_beats_single_retrievers(small_index):
    cfg, corpus, index = small_index
    tq = synth_queries(1, corpus, 128)
    _, feats, labels = tl.make_labels(cfg, index, tq.q_dense, tq.q_terms,
                                      tq.q_weights)
    index.lstm_params, _ = tl.train_selector(
        cfg, jax.random.key(2), np.asarray(feats), np.asarray(labels),
        epochs=30, batch_size=32, lr=0.01)
    test_q = synth_queries(11, corpus, 64)
    ids, _, diag = cl.retrieve(cfg, index, test_q.q_dense, test_q.q_terms,
                               test_q.q_weights)
    clusd_mrr = mrr_at(np.asarray(ids), test_q.rel_doc)
    dense_ids, _ = cl.full_dense_topk(index.embeddings, test_q.q_dense, 64)
    dense_mrr = mrr_at(np.asarray(dense_ids), test_q.rel_doc)
    sid, _ = sparse_lib.sparse_retrieve_topk(
        index.sparse_index, test_q.q_terms, test_q.q_weights, cfg.k_sparse)
    sparse_mrr = mrr_at(np.asarray(sid), test_q.rel_doc)
    assert clusd_mrr > max(dense_mrr, sparse_mrr) * 0.95
    # partial retrieval: only a fraction of the corpus scanned
    assert float(diag["frac_docs_scanned"].mean()) < 0.5
    index.lstm_params = None


def _sparse_build_loop(doc_terms, doc_weights, vocab, max_postings):
    """The per-posting loop SparseIndex.build replaced: (weight, doc)
    tuples sorted descending per term, truncated."""
    lists = [[] for _ in range(vocab)]
    for d in range(doc_terms.shape[0]):
        for t, w in zip(doc_terms[d], doc_weights[d]):
            if t >= 0 and w > 0:
                lists[int(t)].append((float(w), d))
    pd = np.full((vocab, max_postings), -1, np.int32)
    pw = np.zeros((vocab, max_postings), np.float32)
    truncated = 0
    for t in range(vocab):
        lst = sorted(lists[t], reverse=True)
        truncated += max(0, len(lst) - max_postings)
        for i, (w, d) in enumerate(lst[:max_postings]):
            pd[t, i], pw[t, i] = d, w
    return pd, pw, truncated


@pytest.mark.parametrize("max_postings", [3, 8, 64])
def test_sparse_build_matches_loop(max_postings):
    """Vectorised SparseIndex.build is bit-identical to the loop, with
    weight ties (broken doc id descending), repeated terms within a doc,
    pads, zero weights and truncation."""
    rng = np.random.default_rng(3)
    D, T, V = 200, 9, 37
    terms = rng.integers(-1, V, (D, T)).astype(np.int32)
    weights = rng.choice(np.asarray([0.0, 0.5, 1.0, 1.25, 2.0], np.float32),
                         (D, T))
    weights[::7] = rng.lognormal(0.0, 0.5, (len(weights[::7]), T))
    sp = sparse_lib.SparseIndex.build(terms, weights, V, max_postings)
    pd, pw, truncated = _sparse_build_loop(terms, weights, V, max_postings)
    np.testing.assert_array_equal(np.asarray(sp.postings_docs), pd)
    np.testing.assert_array_equal(np.asarray(sp.postings_weights), pw)
    assert sp.truncated_postings == truncated
