"""Sparse lexical retrieval (SPLADE/BM25-style) as a TPU-native inverted
index: per-term posting lists are impact-ordered, truncated to a static
budget, and scoring is gather + scatter-add (`jnp.take` + `segment_sum`) —
the same primitive family as EmbeddingBag (DESIGN.md §2).

Documents/queries are bags of (term_id, weight); the exact rank score is
L(q) . L(d) = sum over shared terms of qw * dw.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["postings_docs", "postings_weights"],
                   meta_fields=["n_docs"])
@dataclasses.dataclass
class SparseIndex:
    """A pytree: the posting arrays are leaves (jit arguments, never
    compiled-in constants); `n_docs` is static aux data."""
    postings_docs: jnp.ndarray     # (V, P) int32, -1 padded, impact-ordered
    postings_weights: jnp.ndarray  # (V, P) f32
    n_docs: int

    @staticmethod
    def build(doc_terms, doc_weights, vocab, max_postings):
        """doc_terms: (D, T) int32 term ids (-1 pad); doc_weights: (D, T) f32.

        Each term's postings are its (weight, doc) pairs with weight > 0 in
        descending tuple order (weight desc, ties doc id desc), truncated
        to `max_postings`."""
        doc_terms = np.asarray(doc_terms)
        doc_weights = np.asarray(doc_weights)
        D, T = doc_terms.shape
        keep = (doc_terms >= 0) & (doc_weights > 0)
        t = doc_terms[keep].astype(np.int64)
        w = doc_weights[keep]
        d = np.broadcast_to(np.arange(D, dtype=np.int64)[:, None],
                            (D, T))[keep]
        order = np.lexsort((-d, -w, t))     # term asc, weight desc, doc desc
        t, w, d = t[order], w[order], d[order]
        counts = np.bincount(t, minlength=vocab)
        starts = np.cumsum(counts) - counts
        rank = np.arange(len(t)) - starts[t]
        fit = rank < max_postings
        pd = np.full((vocab, max_postings), -1, np.int32)
        pw = np.zeros((vocab, max_postings), np.float32)
        pd[t[fit], rank[fit]] = d[fit]
        pw[t[fit], rank[fit]] = w[fit]
        idx = SparseIndex(jnp.asarray(pd), jnp.asarray(pw), D)
        idx.truncated_postings = int(
            np.maximum(counts - max_postings, 0).sum())
        return idx


def sparse_retrieve(index: SparseIndex, q_terms, q_weights, k):
    """q_terms: (B, Tq) int32 (-1 pad); q_weights: (B, Tq).

    Returns (top-k doc ids (B, k), top-k scores (B, k), full scores (B, D)).
    """
    B = q_terms.shape[0]
    D = index.n_docs
    qt = jnp.maximum(q_terms, 0)
    qmask = (q_terms >= 0) & (q_weights > 0)

    docs = jnp.take(index.postings_docs, qt, axis=0)       # (B, Tq, P)
    ws = jnp.take(index.postings_weights, qt, axis=0)      # (B, Tq, P)
    contrib = ws * q_weights[..., None]
    contrib = jnp.where(qmask[..., None], contrib, 0.0)
    dmask = docs >= 0
    flat_docs = jnp.where(dmask, docs, D).reshape(B, -1)   # overflow row D
    flat_contrib = jnp.where(dmask, contrib, 0.0).reshape(B, -1)

    def one(fd, fc):
        return jax.ops.segment_sum(fc, fd, num_segments=D + 1)[:D]

    scores = jax.vmap(one)(flat_docs, flat_contrib)        # (B, D)
    top_scores, top_ids = jax.lax.top_k(scores, k)
    return top_ids.astype(jnp.int32), top_scores, scores


@jax.named_scope("sparse_topk")
def sparse_retrieve_topk(index: SparseIndex, q_terms, q_weights, k):
    ids, scores, _ = sparse_retrieve(index, q_terms, q_weights, k)
    return ids, scores
