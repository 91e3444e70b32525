"""The device generator against the program's own (`repro.data.synthetic`)
at a small size on the CPU: the same shapes and dtypes, and summary
statistics within sampling error of each other."""

import numpy as np

import gen
from repro.data import synthetic

N_DOCS, DIM, VOCAB, N_Q = 4096, 64, 2048, 512


def _stats(emb, terms, weights, topic, qd, qt, qw, src):
    emb, terms, weights = map(np.asarray, (emb, terms, weights))
    topic, qd, qt, qw, src = map(np.asarray, (topic, qd, qt, qw, src))
    same = topic[:, None] == topic[None, :256]
    cos = emb @ emb[:256].T
    in_doc = (qt[:, :, None] == terms[src][:, None, :]).any(-1)
    return {
        "emb_norm": np.linalg.norm(emb, axis=1).mean(),
        "cos_same_topic": cos[same & ~np.eye(len(emb), 256, dtype=bool)].mean(),
        "cos_other_topic": cos[~same].mean(),
        "log_doc_weight_mean": np.log(weights).mean(),
        "log_doc_weight_std": np.log(weights).std(),
        "distinct_terms_per_doc": np.mean([len(set(r)) for r in terms]),
        "q_src_cos": (qd * emb[src]).sum(1).mean(),
        "q_terms_in_src": in_doc.sum(1).mean(),
        "log_q_weight_std": np.log(qw).std(),
    }


def test_device_generator_matches_program_generator():
    c = synthetic.synth_corpus(3, N_DOCS, DIM, VOCAB)
    q = synthetic.synth_queries(4, c, N_Q)
    ref = _stats(c.embeddings, c.doc_terms, c.doc_weights, c.topic_of,
                 q.q_dense, q.q_terms, q.q_weights, q.rel_doc)
    emb, terms, weights, topic = gen.corpus(3, N_DOCS, DIM, VOCAB)
    qd, qt, qw, src = gen.queries(4, emb, terms, N_Q, VOCAB)
    assert emb.shape == c.embeddings.shape and emb.dtype == np.float32
    assert terms.shape == c.doc_terms.shape and terms.dtype == np.int32
    assert weights.shape == c.doc_weights.shape
    assert weights.dtype == np.float32
    assert qd.shape == q.q_dense.shape and qt.shape == q.q_terms.shape
    assert qt.dtype == np.int32 and qw.dtype == np.float32
    got = _stats(emb, terms, weights, topic, qd, qt, qw, src)
    for name, want in ref.items():
        assert abs(got[name] - want) <= 0.03 * abs(want) + 0.01, (
            name, got[name], want)


def test_same_seed_same_data_and_large_seeds():
    big = 2**31 + 12345
    a = gen.corpus(big, 256, 16, 128)
    b = gen.corpus(big, 256, 16, 128)
    c = gen.corpus(big + 1, 256, 16, 128)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    qa = gen.queries(big, a[0], a[1], 32, 128)
    qb = gen.queries(big, a[0], a[1], 32, 128)
    assert all(np.array_equal(x, y) for x, y in zip(qa, qb))
