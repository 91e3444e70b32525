"""CluSD system tests: stage-1 invariants (hypothesis property tests),
LSTM training improves selection, end-to-end quality, fusion exactness."""

import dataclasses
import functools

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

def _draw_ids(rng, D, shape, distinct):
    if distinct:
        return np.stack([rng.choice(D, shape[1], replace=False)
                         for _ in range(shape[0])]).astype(np.int32)
    return rng.integers(0, D, shape).astype(np.int32)


def _fusion_case(name, rng):
    """One fusion case: (n_docs, k, alpha, variants, multiplicity <= 2);
    each variant is (sid, ss, sm, did, ds, dm), all variants of a case
    meant to fuse alike (the padding case's two junk tails)."""
    B = 2
    if name == "distinct":
        D, Ks, Kd, k = 500, 40, 60, 20
        sid = _draw_ids(rng, D, (B, Ks), True)
        ss = rng.random((B, Ks)).astype(np.float32)
        sm = np.ones((B, Ks), bool)
        did = _draw_ids(rng, D, (B, Kd), True)
        dm = rng.random((B, Kd)) > 0.2
        ds = np.where(dm, rng.random((B, Kd)), 0.0).astype(np.float32)
        return D, k, 0.5, [(sid, ss, sm, did, ds, dm)], True
    if name == "any_multiplicity":
        # a doc may repeat within a side and across sides at any
        # multiplicity, behind a ragged valid prefix on the sparse side;
        # alpha != 0.5 so no cross-side rank ties under rrf
        B, D, Ks, Kd, k = 3, 120, 30, 40, 15
        sid = _draw_ids(rng, D, (B, Ks), False)
        ss = np.sort(rng.random((B, Ks)))[:, ::-1].astype(np.float32)
        sm = np.arange(Ks)[None, :] < rng.integers(0, Ks + 1, (B, 1))
        did = _draw_ids(rng, D, (B, Kd), False)
        ds = rng.random((B, Kd)).astype(np.float32)
        dm = rng.random((B, Kd)) > 0.2
        return D, k, 0.43, [(sid, ss, sm, did, ds, dm)], False
    if name == "sparse_padding":
        # entries behind the sparse valid mask must not shift the
        # normalization, the ranks or the top-k: two junk tails
        D, Ks, Kd, k = 200, 24, 24, 10
        n_valid = int(rng.integers(1, Ks))
        sid_v = rng.choice(D, n_valid, replace=False)
        ss_v = np.sort(rng.random(n_valid))[::-1]
        sm = np.broadcast_to(np.arange(Ks) < n_valid, (1, Ks))
        did = _draw_ids(rng, D, (1, Kd), True)
        ds = rng.random((1, Kd)).astype(np.float32)
        dm = np.ones((1, Kd), bool)
        variants = []
        for junk_ids, junk_scores in (
                (rng.integers(0, D, Ks - n_valid),
                 rng.random(Ks - n_valid) * 10 - 5),
                (np.zeros(Ks - n_valid), np.full(Ks - n_valid, 99.0))):
            sid = np.concatenate([sid_v, junk_ids])[None].astype(np.int32)
            ss = np.concatenate([ss_v, junk_scores])[None].astype(np.float32)
            variants.append((sid, ss, sm, did, ds, dm))
        return D, k, 0.5, variants, True
    if name == "few_positive":
        # fewer live candidates than k, some with ids < k: the fill
        # entries must give the buffer's zero-score tail, lowest ids first
        D, Ks, Kd, k = 4096, 8, 32, 50
        sid = _draw_ids(rng, 2 * k, (B, Ks), True)
        ss = rng.random((B, Ks)).astype(np.float32)
        sm = np.arange(Ks)[None, :] < rng.integers(1, 4, (B, 1))
        did = _draw_ids(rng, 2 * k, (B, Kd), True)
        ds = rng.random((B, Kd)).astype(np.float32)
        dm = rng.random((B, Kd)) > 0.85
        return D, k, 0.43, [(sid, ss, sm, did, ds, dm)], True
    if name == "dense_all_masked":
        D, Ks, Kd, k = 1000, 30, 64, 40
        sid = _draw_ids(rng, D, (B, Ks), True)
        ss = rng.random((B, Ks)).astype(np.float32)
        sm = np.ones((B, Ks), bool)
        did = _draw_ids(rng, D, (B, Kd), False)
        ds = rng.random((B, Kd)).astype(np.float32)
        dm = np.zeros((B, Kd), bool)
        return D, k, 0.5, [(sid, ss, sm, did, ds, dm)], True
    if name == "ragged_sparse":
        D, Ks, Kd, k = 1000, 50, 64, 30
        sid = _draw_ids(rng, D, (B, Ks), True)
        ss = np.sort(rng.random((B, Ks)))[:, ::-1].astype(np.float32)
        sm = np.arange(Ks)[None, :] < rng.integers(0, Ks + 1, (B, 1))
        did = _draw_ids(rng, D, (B, Kd), True)
        ds = rng.random((B, Kd)).astype(np.float32)
        dm = rng.random((B, Kd)) > 0.3
        return D, k, 0.43, [(sid, ss, sm, did, ds, dm)], True
    if name == "multiplicity_3plus":
        # ids from a pool of 24 in a corpus of 2,048: every doc is
        # several times on each side
        D, Ks, Kd, k = 2048, 40, 80, 30
        sid = _draw_ids(rng, 24, (B, Ks), False)
        ss = rng.random((B, Ks)).astype(np.float32)
        sm = np.ones((B, Ks), bool)
        did = _draw_ids(rng, 24, (B, Kd), False)
        ds = rng.random((B, Kd)).astype(np.float32)
        dm = rng.random((B, Kd)) > 0.1
        return D, k, 0.43, [(sid, ss, sm, did, ds, dm)], False
    if name == "small_corpus":
        # a candidate list longer than the corpus: the fill entries
        # repeat candidate ids, which must not count twice
        D, Ks, Kd, k = 100, 30, 60, 20
        sid = _draw_ids(rng, D, (B, Ks), True)
        ss = rng.random((B, Ks)).astype(np.float32)
        sm = np.ones((B, Ks), bool)
        did = _draw_ids(rng, D, (B, Kd), True)
        ds = rng.random((B, Kd)).astype(np.float32)
        dm = rng.random((B, Kd)) > 0.2
        return D, k, 0.5, [(sid, ss, sm, did, ds, dm)], True
    if name == "k_equals_n_docs":
        D, Ks, Kd, k = 64, 20, 40, 64
        sid = _draw_ids(rng, D, (B, Ks), True)
        ss = rng.random((B, Ks)).astype(np.float32)
        sm = np.arange(Ks)[None, :] < rng.integers(0, Ks + 1, (B, 1))
        did = _draw_ids(rng, D, (B, Kd), True)
        ds = rng.random((B, Kd)).astype(np.float32)
        dm = rng.random((B, Kd)) > 0.3
        return D, k, 0.43, [(sid, ss, sm, did, ds, dm)], True
    if name == "tied_scores":
        # scores on a grid of 4 (interp) and single-side docs at equal
        # ranks (rrf) give groups of equal fused scores across the k-th
        # place: they must rank lowest id first, as over the buffer
        D, Ks, Kd, k = 300, 40, 60, 50
        sid = _draw_ids(rng, D, (B, Ks), True)
        ss = (rng.integers(0, 4, (B, Ks)) / 4).astype(np.float32)
        sm = np.ones((B, Ks), bool)
        did = _draw_ids(rng, D, (B, Kd), True)
        ds = (rng.integers(0, 4, (B, Kd)) / 4).astype(np.float32)
        dm = rng.random((B, Kd)) > 0.2
        return D, k, 0.5, [(sid, ss, sm, did, ds, dm)], True
    raise KeyError(name)


_SCATTER = jax.jit(fusion_lib.fuse_topk_scatter, static_argnums=(5, 6, 7),
                   static_argnames="method")
_FUSE = jax.jit(fusion_lib.fuse_topk, static_argnums=(5, 6, 7),
                static_argnames="method")
_MERGE = jax.jit(fusion_lib.fuse_topk_merge, static_argnums=(5, 6, 7),
                 static_argnames="method")
FUSION_CASES = ("distinct", "any_multiplicity", "sparse_padding",
                "few_positive", "dense_all_masked", "ragged_sparse",
                "multiplicity_3plus", "small_corpus", "k_equals_n_docs",
                "tied_scores")


@pytest.mark.parametrize("case", FUSION_CASES)
@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_fusion_merge_equals_scatter(case, seed):
    """`fuse_topk` and `fuse_topk_merge` (both sort-merge) against the
    scatter buffer as the oracle, for both methods: ids equal; scores
    bitwise up to two entries a doc, within 1e-6 beyond (XLA leaves the
    order of a scatter's duplicate updates open). `fuse_topk_merge` has
    no fill entries, so it answers for the oracle's positive scores."""
    rng = np.random.default_rng(seed)
    D, k, a, variants, pairs_only = _fusion_case(case, rng)
    Ks, Kd = variants[0][0].shape[1], variants[0][3].shape[1]
    for method in fusion_lib.FUSION_METHODS:
        outs = []
        for sid, ss, sm, did, ds, dm in variants:
            args = (jnp.asarray(sid), jnp.asarray(ss), jnp.asarray(did),
                    jnp.asarray(ds), jnp.asarray(dm))
            kw = dict(sparse_mask=jnp.asarray(sm), method=method)
            oi, os_ = (np.asarray(x) for x in _SCATTER(*args, D, a, k, **kw))
            fi, fs = (np.asarray(x) for x in _FUSE(*args, D, a, k, **kw))
            km = min(k, Ks + Kd)        # the merge ranks live entries only
            mi, ms = (np.asarray(x) for x in _MERGE(*args, a, km, D + 7,
                                                    **kw))
            pos = os_[:, :km] > 0
            if pairs_only:
                same = np.testing.assert_array_equal
            else:
                same = functools.partial(np.testing.assert_allclose, rtol=0,
                                         atol=1e-6)
            np.testing.assert_array_equal(fi, oi, err_msg=method)
            same(fs, os_, err_msg=method)
            np.testing.assert_array_equal(mi[pos], oi[:, :km][pos],
                                          err_msg=method)
            same(ms[pos], os_[:, :km][pos], err_msg=method)
            outs.append((fi, fs, mi, ms))
        for got in outs[1:]:            # junk tails fuse bitwise alike
            for g, w in zip(got, outs[0]):
                np.testing.assert_array_equal(g, w, err_msg=method)


def test_fuse_topk_rejects_k_above_corpus():
    """k fill entries stand for the corpus's zero-score tail, so k may
    not exceed n_docs, as the buffer's top-k over n_docs cannot; nor may
    it exceed the entries of `fuse_topk_merge`, which has no fill."""
    sid = jnp.zeros((1, 4), jnp.int32)
    did = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="exceeds the corpus"):
        fusion_lib.fuse_topk(sid, jnp.ones((1, 4)), did, jnp.ones((1, 8)),
                             jnp.ones((1, 8), bool), 10, 0.5, 11)
    # without fill entries the merge ranks at most its 12 entries
    with pytest.raises(ValueError, match="entries to rank"):
        fusion_lib.fuse_topk_merge(sid, jnp.ones((1, 4)), did,
                                   jnp.ones((1, 8)), jnp.ones((1, 8), bool),
                                   0.5, 13, 10)


def _jaxpr_shapes(jaxpr):
    """(primitive names, every array shape) of a jaxpr and its sub-jaxprs."""
    prims, shapes = set(), set()
    for eqn in jaxpr.eqns:
        prims.add(eqn.primitive.name)
        for v in list(eqn.invars) + list(eqn.outvars):
            shapes.add(tuple(getattr(v.aval, "shape", ())))
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    sp, ss = _jaxpr_shapes(inner)
                    prims |= sp
                    shapes |= ss
    return prims, shapes


def test_run_sums_pass_count_ignores_dead_entries():
    """The run-sum loop makes (longest live run - 1) passes: the masked
    entries, which share the sentinel id and are most of the dense slots
    at serving, form no run however many there are."""
    sentinel = 1000
    ids = np.array([[3, 3, 7, 9, 9, 9] + [sentinel] * 40,
                    [1, 2, 4, 4, 5, 6] + [sentinel] * 40], np.int32)
    c = np.arange(ids.size, dtype=np.float32).reshape(ids.shape) + 1.0
    total, passes = jax.jit(fusion_lib._run_sums, static_argnums=2)(
        jnp.asarray(ids), jnp.asarray(c), sentinel)
    assert int(passes) == 2
    total = np.asarray(total)
    np.testing.assert_array_equal(total[0, :6], [1, 3, 3, 4, 9, 15])
    np.testing.assert_array_equal(total[1, :6], [47, 48, 49, 99, 51, 52])
    np.testing.assert_array_equal(total[:, 6:], c[:, 6:])


def test_fuse_topk_serving_shapes_have_no_corpus_buffer():
    """At the serving shapes (16 queries, 1,000 sparse + 32 x 2,048 dense
    entries, 2^21 docs, top 1,000) fuse_topk traces no scatter and no
    array with an n_docs or n_docs + 1 dimension. Abstract inputs only."""
    B, Ks, Kd, D, k = 16, 1000, 65536, 2 ** 21, 1000
    spec = jax.ShapeDtypeStruct
    for method in fusion_lib.FUSION_METHODS:
        jaxpr = jax.make_jaxpr(
            lambda sid, ss, did, ds, dm: fusion_lib.fuse_topk(
                sid, ss, did, ds, dm, D, 0.5, k, method=method))(
            spec((B, Ks), jnp.int32), spec((B, Ks), jnp.float32),
            spec((B, Kd), jnp.int32), spec((B, Kd), jnp.float32),
            spec((B, Kd), jnp.bool_))
        prims, shapes = _jaxpr_shapes(jaxpr.jaxpr)
        assert not any("scatter" in p for p in prims), (method, prims)
        assert not any(d in (D, D + 1) for s in shapes for d in s), method
        # ties rank by the sort's id key, not by a top-k's order
        assert "sort" in prims and "top_k" not in prims, prims
    # the oracle at the same shapes does build the buffer: the walk sees it
    jaxpr = jax.make_jaxpr(
        lambda sid, ss, did, ds, dm: fusion_lib.fuse_topk_scatter(
            sid, ss, did, ds, dm, D, 0.5, k))(
        spec((B, Ks), jnp.int32), spec((B, Ks), jnp.float32),
        spec((B, Kd), jnp.int32), spec((B, Kd), jnp.float32),
        spec((B, Kd), jnp.bool_))
    prims, shapes = _jaxpr_shapes(jaxpr.jaxpr)
    assert any("scatter" in p for p in prims)
    assert any(D + 1 in s for s in shapes)


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
