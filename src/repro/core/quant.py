"""Vector quantization for the embedding store (paper Tables 1, 6, 7):

  - PQ: product quantization, nsub subspaces x 256 codes, ADC scoring via
    per-query lookup tables (gather + sum — TPU-friendly).
  - OPQ-lite: PCA rotation before PQ (the eigen-allocation variant of OPQ;
    full OPQ alternates rotation/codebook — PCA-init is its standard seed).
  - DistillVQ/JPQ stand-ins (Table 7) are PQ retrained with different
    objectives; here they map to PQ with different nsub/rotation settings.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kmeans as km


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["codebooks", "codes", "rotation"],
                   meta_fields=["nsub"])
@dataclasses.dataclass
class PQ:
    codebooks: jnp.ndarray   # (nsub, 256, dsub)
    codes: jnp.ndarray       # (D, nsub) uint8 — int32 on CPU backends
    rotation: jnp.ndarray    # (dim, dim) or None
    nsub: int

    def space_bytes(self):
        return int(self.codes.shape[0]) * self.nsub


def train_pq(rng, X, nsub, n_codes=256, iters=10, rotate=False):
    """X: (D, dim). dim % nsub == 0."""
    D, dim = X.shape
    assert dim % nsub == 0, (dim, nsub)
    R = None
    if rotate:
        Xc = X - jnp.mean(X, axis=0, keepdims=True)
        cov = Xc.T @ Xc / D
        _, vecs = jnp.linalg.eigh(cov)
        R = vecs[:, ::-1]                       # descending eigenvalue order
        X = X @ R
    dsub = dim // nsub
    Xs = X.reshape(D, nsub, dsub)
    books, codes = [], []
    for s in range(nsub):
        rng, sub = jax.random.split(rng)
        n_k = min(n_codes, D)
        c, a = km.kmeans(sub, Xs[:, s], n_k, iters=iters)
        if n_k < n_codes:
            c = jnp.pad(c, ((0, n_codes - n_k), (0, 0)))
        books.append(c)
        codes.append(a)
    return PQ(jnp.stack(books), jnp.stack(codes, axis=1).astype(jnp.int32),
              R, nsub)


def pq_encode(codebooks, X, rotation=None):
    """Assign each row of X (C, dim) to its nearest codebook entry per
    subspace: (C, nsub) int32 codes. Chunk-friendly: call per bounded row
    chunk — nothing here depends on seeing the whole corpus."""
    X = jnp.asarray(X, jnp.float32)
    if rotation is not None:
        X = X @ rotation
    nsub, n_codes, dsub = codebooks.shape
    Xs = X.reshape(X.shape[0], nsub, dsub)
    # argmin_k ||x_s - c_sk||^2 = argmin_k ||c_sk||^2 - 2 x_s . c_sk
    c2 = jnp.sum(codebooks * codebooks, axis=-1)             # (nsub, K)
    dots = jnp.einsum("csd,skd->csk", Xs, codebooks)         # (C, nsub, K)
    return jnp.argmin(c2[None] - 2.0 * dots, axis=-1).astype(jnp.int32)


def train_pq_stream(rng, embeddings, nsub, *, n_codes=256, iters=10,
                    rotate=False, sample_docs=1 << 16, chunk_docs=1 << 14):
    """PQ for corpora larger than RAM: codebooks are trained on a bounded
    random sample gathered in `chunk_docs`-row reads, then every document is
    encoded chunk-by-chunk. `embeddings` only needs row indexing (np.memmap
    is fine); no read ever touches more than max(chunk_docs, sample rows
    per chunk) rows, and the full float matrix is never materialized.

    Returns a PQ whose `codes` covers all D docs.
    """
    D = int(embeddings.shape[0])
    n_sample = min(D, sample_docs)
    rng, sub = jax.random.split(rng)
    idx = np.sort(np.asarray(
        jax.random.choice(sub, D, (n_sample,), replace=False)))
    sample = np.empty((n_sample, int(embeddings.shape[1])), np.float32)
    for lo in range(0, n_sample, chunk_docs):
        sel = idx[lo:lo + chunk_docs]
        sample[lo:lo + len(sel)] = np.asarray(embeddings[sel], np.float32)
    pq = train_pq(rng, jnp.asarray(sample), nsub, n_codes=n_codes,
                  iters=iters, rotate=rotate)
    codes = np.empty((D, nsub), np.int32)
    for lo in range(0, D, chunk_docs):
        chunk = np.asarray(embeddings[lo:lo + chunk_docs], np.float32)
        codes[lo:lo + len(chunk)] = np.asarray(
            pq_encode(pq.codebooks, chunk, pq.rotation))
    return PQ(pq.codebooks, jnp.asarray(codes), pq.rotation, nsub)


def decode_code_blocks(codebooks, codes, rotation=None):
    """Host-side ADC reconstruction of packed code blocks: codes
    (..., nsub) uint8/int -> float32 (..., dim). Used by the sharded PQ
    store; dot(q, decode(codes)) equals the ADC LUT score exactly (same
    per-subspace terms, summed in the same order)."""
    books = np.asarray(codebooks, np.float32)        # (nsub, K, dsub)
    nsub = books.shape[0]
    vecs = books[np.arange(nsub), np.asarray(codes, np.int64)]
    flat = vecs.reshape(codes.shape[:-1] + (-1,))
    if rotation is not None:
        flat = flat @ np.asarray(rotation, np.float32).T
    return flat


def adc_tables(pq: PQ, q):
    """q: (B, dim) -> LUT (B, nsub, 256), in float32 (HIGHEST precision:
    on TPU the default would round the operands to bf16, and the ADC
    kernels build their LUT in float32)."""
    hi = jax.lax.Precision.HIGHEST
    if pq.rotation is not None:
        q = jnp.dot(q, pq.rotation, precision=hi)
    B = q.shape[0]
    dsub = pq.codebooks.shape[-1]
    qs = q.reshape(B, pq.nsub, dsub)
    return jnp.einsum("bsd,skd->bsk", qs, pq.codebooks, precision=hi)


def adc_score(pq: PQ, lut, doc_ids):
    """lut: (B, nsub, 256); doc_ids: (B, K) -> approx scores (B, K).

    score[b, k] = sum_s lut[b, s, codes[doc_ids[b, k], s]]
    """
    codes = jnp.take(pq.codes, jnp.maximum(doc_ids, 0), axis=0)  # (B, K, S)
    B, K, S = codes.shape
    s_idx = jnp.arange(S)[None, None, :]
    scores = lut[jnp.arange(B)[:, None, None], s_idx, codes]
    return jnp.sum(scores, axis=-1)


def reconstruct(pq: PQ, doc_ids):
    """Decode quantized embeddings for given ids: (K, dim)."""
    codes = jnp.take(pq.codes, doc_ids, axis=0)                  # (K, nsub)
    vecs = pq.codebooks[jnp.arange(pq.nsub)[None, :], codes]     # (K, nsub, dsub)
    flat = vecs.reshape(doc_ids.shape[0], -1)
    if pq.rotation is not None:
        flat = flat @ pq.rotation.T
    return flat


def score_selected_pq(index, q_dense, sel_ids, sel_mask):
    """Quantized Step-3 scoring — thin wrapper over the engine pipeline
    with a PQStore backend (ADC scoring via `score_docs`)."""
    from repro.engine import pipeline as pipe_lib
    from repro.engine import stores as stores_lib
    store = stores_lib.PQStore(index.quantizer, index.cluster_docs)
    return pipe_lib.score_selected(store, q_dense, sel_ids, sel_mask)


def identity_pq(embeddings, nsub=1):
    """Exact (lossless) PQ for corpora with <= 256 docs: doc d's code in
    every subspace is d, and codebook entries are the docs' own sub-vectors.
    ADC then reproduces the exact dot product — used by backend-parity
    tests and debugging, not by real indexes."""
    X = jnp.asarray(embeddings)
    D, dim = X.shape
    assert D <= 256, f"identity PQ needs <= 256 docs, got {D}"
    assert dim % nsub == 0, (dim, nsub)
    dsub = dim // nsub
    books = X.reshape(D, nsub, dsub).transpose(1, 0, 2)      # (nsub, D, dsub)
    books = jnp.pad(books, ((0, 0), (0, 256 - D), (0, 0)))
    codes = jnp.tile(jnp.arange(D, dtype=jnp.int32)[:, None], (1, nsub))
    return PQ(books, codes, None, nsub)
