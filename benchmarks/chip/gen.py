"""Synthetic MS MARCO-like corpus and queries, generated on the device.

A vectorised copy of the distribution in `repro.data.synthetic` (the
program's generator loops over documents in Python: 66 s at 2^20 docs).
Documents live in latent topics; each topic owns `terms_per_topic` term
ids, and a document takes `doc_terms - n_bg` distinct positions of its
topic's list plus `n_bg` background terms, with lognormal(0, 0.5)
weights. Its embedding is the topic centre plus Gaussian noise,
L2-normalised. A query comes from a source document: its embedding plus
noise, `q_terms - n_noise` distinct terms of the document plus `n_noise`
random ones, lognormal(0, 0.4) weights. The source id is the relevance
label (MS MARCO's one relevant passage per query).

Every array is made in one jitted call from a seed, so a run pays no host
loop and no transfer for its data.
"""

import functools

import jax
import jax.numpy as jnp


def key_for(seed):
    """A PRNG key for any whole-number seed (seeds may exceed 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


@functools.partial(jax.jit, static_argnames=(
    "n_docs", "dim", "vocab", "n_topics", "doc_terms", "terms_per_topic",
    "topic_noise", "bg_frac"))
def _corpus(key, *, n_docs, dim, vocab, n_topics, doc_terms,
            terms_per_topic, topic_noise, bg_frac):
    k = jax.random.split(key, 7)
    centers = jax.random.normal(k[0], (n_topics, dim), jnp.float32)
    centers /= jnp.linalg.norm(centers, axis=1, keepdims=True)
    topic = jax.random.randint(k[1], (n_docs,), 0, n_topics, jnp.int32)
    emb = centers[topic] + topic_noise * jax.random.normal(
        k[2], (n_docs, dim), jnp.float32)
    emb /= jnp.linalg.norm(emb, axis=1, keepdims=True)
    topic_terms = jax.random.randint(k[3], (n_topics, terms_per_topic), 0,
                                     vocab, jnp.int32)
    n_bg = max(1, int(doc_terms * bg_frac))
    n_tp = doc_terms - n_bg
    # n_tp distinct positions of the topic's term list: the positions of
    # the n_tp largest of uniform keys are a uniform random subset
    _, pos = jax.lax.top_k(
        jax.random.uniform(k[4], (n_docs, terms_per_topic)), n_tp)
    tt = jnp.take_along_axis(topic_terms[topic], pos, axis=1)
    bg = jax.random.randint(k[5], (n_docs, n_bg), 0, vocab, jnp.int32)
    terms = jnp.concatenate([tt, bg], axis=1)
    weights = jnp.exp(0.5 * jax.random.normal(k[6], (n_docs, doc_terms),
                                              jnp.float32))
    return emb, terms, weights, topic


def corpus(seed, n_docs, dim, vocab, *, n_topics=None, doc_terms=16,
           terms_per_topic=64, topic_noise=0.55, bg_frac=0.25):
    """-> (embeddings (D, dim) f32, doc_terms (D, T) int32, doc_weights
    (D, T) f32, topic_of (D,) int32), all on the device."""
    return _corpus(key_for(seed), n_docs=n_docs, dim=dim, vocab=vocab,
                   n_topics=n_topics or max(8, n_docs // 64),
                   doc_terms=doc_terms, terms_per_topic=terms_per_topic,
                   topic_noise=topic_noise, bg_frac=bg_frac)


@functools.partial(jax.jit, static_argnames=(
    "n_queries", "vocab", "q_terms", "dense_noise", "term_noise_frac"))
def _queries(key, emb, doc_terms, *, n_queries, vocab, q_terms,
             dense_noise, term_noise_frac):
    k = jax.random.split(key, 6)
    n_docs, dim = emb.shape
    src = jax.random.randint(k[0], (n_queries,), 0, n_docs, jnp.int32)
    qd = emb[src] + dense_noise * jax.random.normal(
        k[1], (n_queries, dim), jnp.float32)
    qd /= jnp.linalg.norm(qd, axis=1, keepdims=True)
    n_noise = max(0, int(q_terms * term_noise_frac))
    n_doc = q_terms - n_noise
    dterms = doc_terms[src]
    # n_doc distinct positions among the document's (all valid) terms
    _, pos = jax.lax.top_k(
        jax.random.uniform(k[2], dterms.shape), n_doc)
    pick = jnp.take_along_axis(dterms, pos, axis=1)
    noise = jax.random.randint(k[3], (n_queries, n_noise), 0, vocab,
                               jnp.int32)
    qt = jnp.concatenate([pick, noise], axis=1)
    qw = jnp.exp(0.4 * jax.random.normal(k[4], (n_queries, q_terms),
                                         jnp.float32))
    return qd, qt, qw, src


def queries(seed, emb, doc_terms, n_queries, vocab, *, stream=0, q_terms=8,
            dense_noise=0.35, term_noise_frac=0.25):
    """-> (q_dense (B, dim) f32, q_terms (B, Tq) int32, q_weights (B, Tq)
    f32, rel_doc (B,) int32), on the device. Every document has all its
    `doc_terms` slots filled, as `corpus` makes them. Query sets of one
    seed in different `stream`s are independent."""
    key = jax.random.fold_in(key_for(seed), stream)
    return _queries(key, emb, doc_terms, n_queries=n_queries,
                    vocab=vocab, q_terms=q_terms, dense_noise=dense_noise,
                    term_noise_frac=term_noise_frac)
