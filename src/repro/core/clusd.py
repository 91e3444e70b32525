"""CluSD end-to-end: index build + online inference (paper §2.1 steps 1-3).

Index artifacts (all static-shape, device-resident or disk-backed):
  centroids (N, dim) · cluster_docs (N, cap) · doc_cluster (D,)
  neighbor_ids/sims (N, m) · sparse inverted index · LSTM params

Online retrieve (batched over queries, jit-able end to end):
  1. sparse retrieval -> top-k ids/scores
  2. Stage I: P/Q overlap features -> multikey sort -> top-n candidates
     Stage II: LSTM over candidate sequence -> f(C_i) >= theta -> selected
     clusters (static budget max_selected, mask-padded)
  3. gather selected cluster blocks -> dense dot scores -> min-max fusion
"""

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import bins as bins_lib
from repro.core import features as feat_lib
from repro.core import fusion as fusion_lib
from repro.core import kmeans as km
from repro.core import sparse as sparse_lib
from repro.core import stage1 as stage1_lib
from repro.core.lstm import SELECTORS


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CluSDIndex:
    """A pytree of index arrays: serving programs take it as a jit
    argument (placed on the device once), so no index array is ever
    compiled into a program as a constant. Unset fields are None."""
    centroids: Any          # (N, dim)
    cluster_docs: Any       # (N, cap) int32, -1 pad
    doc_cluster: Any        # (D,) int32
    neighbor_ids: Any       # (N, m)
    neighbor_sims: Any      # (N, m)
    embeddings: Any         # (D, dim) float  (or None when on disk / quantized)
    sparse_index: Any       # SparseIndex
    lstm_params: Any = None
    quantizer: Any = None   # optional PQ/OPQ (core/quant.py)
    bin_ids: Any = None     # (k_sparse,) rank -> bin id

    @property
    def n_docs(self):
        return int(self.doc_cluster.shape[0])

    @property
    def n_clusters(self):
        return int(self.centroids.shape[0])


def build_index(cfg, rng, embeddings, doc_terms, doc_weights,
                kmeans_iters=15) -> CluSDIndex:
    centroids, assign = km.kmeans(rng, embeddings, cfg.n_clusters,
                                  iters=kmeans_iters)
    cluster_docs, doc_cluster = km.build_cluster_table(
        assign, cfg.n_clusters, cfg.cluster_cap, embeddings, centroids)
    m = min(cfg.n_neighbors, cfg.n_clusters - 1)
    nb_ids, nb_sims = km.neighbor_graph(centroids, m)
    sp = sparse_lib.SparseIndex.build(doc_terms, doc_weights, cfg.vocab,
                                      cfg.max_postings)
    return CluSDIndex(
        centroids=centroids, cluster_docs=cluster_docs,
        doc_cluster=doc_cluster, neighbor_ids=nb_ids, neighbor_sims=nb_sims,
        embeddings=embeddings, sparse_index=sp,
        bin_ids=bins_lib.rank_bin_ids(cfg.bins, cfg.k_sparse))


def full_dense_topk(embeddings, q_dense, k):
    scores = q_dense @ embeddings.T
    s, i = jax.lax.top_k(scores, k)
    return i.astype(jnp.int32), s


@jax.named_scope("stage1")
def stage1_candidates(cfg, index, q_dense, sparse_ids, sparse_scores, *,
                      stage1="overlap"):
    """Step 1: sparse-overlap features -> ordered candidate clusters.

    Split out from stage 2 so a serving layer can kick off block prefetch
    for the candidates while the LSTM selection runs (engine/server.py).
    """
    qc_sim = q_dense @ index.centroids.T                     # (B, N)
    P, Q = bins_lib.overlap_features(
        sparse_ids, fusion_lib.minmax_norm(sparse_scores), index.doc_cluster,
        index.n_clusters, index.bin_ids, cfg.v_bins)
    if stage1 == "overlap":
        cand = stage1_lib.sort_by_overlap(P, qc_sim, cfg.n_candidates)
    else:
        cand = stage1_lib.sort_by_dist(qc_sim, cfg.n_candidates)
    if cfg.expand_depth > 0 and cfg.n_candidates_total > cfg.n_candidates:
        # hybrid mode: deepen the seed list through the neighbor graph
        # (LADR-style); depth 0 is bitwise the unexpanded pipeline
        cand = stage1_lib.expand_candidates(
            cand, index.neighbor_ids, index.neighbor_sims, qc_sim,
            cfg.expand_depth, cfg.n_candidates_total)
    feats = feat_lib.candidate_features(
        cand, qc_sim, P, Q, index.neighbor_ids, index.neighbor_sims,
        cfg.u_bins)
    return {"cand": cand, "feats": feats, "qc_sim": qc_sim, "P": P, "Q": Q}


@jax.named_scope("selector")
def stage2_select(cfg, index, cand, feats, *, selector="lstm", theta=None,
                  use_kernel=False, selector_params=None):
    """Step 2: selector probabilities -> thresholded, budgeted selection."""
    theta = cfg.theta if theta is None else theta
    params = selector_params if selector_params is not None else index.lstm_params
    if params is None:
        # untrained fallback: stage-1 order only — take first max_selected
        B, n = cand.shape
        probs = jnp.linspace(1.0, 0.5, n)[None, :].repeat(B, 0)
    else:
        _, apply = SELECTORS[selector]
        if selector == "lstm":
            probs = apply(params, feats, use_kernel=use_kernel)
        else:
            probs = apply(params, feats)

    picked = probs >= theta                                  # (B, n)
    # static budget: top max_selected by prob among picked. Unpicked entries
    # sort last via -inf; the mask is the picked bit carried through the
    # permutation (NOT a sentinel comparison, which broke for theta <= 0 /
    # selectors emitting scores outside [0, 1]).
    masked = jnp.where(picked, probs, -jnp.inf)
    _, top_i = jax.lax.top_k(masked, min(cfg.max_selected, cand.shape[1]))
    sel_mask = jnp.take_along_axis(picked, top_i, axis=1)
    sel_ids = jnp.take_along_axis(cand, top_i, axis=1)
    return {"probs": probs, "sel_ids": sel_ids, "sel_mask": sel_mask}


def select_clusters(cfg, index, q_dense, sparse_ids, sparse_scores, *,
                    selector="lstm", stage1="overlap", theta=None,
                    use_kernel=False, selector_params=None):
    """Steps 1-2. Returns dict with candidates, probs, selected ids + mask."""
    s1 = stage1_candidates(cfg, index, q_dense, sparse_ids, sparse_scores,
                           stage1=stage1)
    s2 = stage2_select(cfg, index, s1["cand"], s1["feats"], selector=selector,
                       theta=theta, use_kernel=use_kernel,
                       selector_params=selector_params)
    return {**s1, **s2}


def score_selected(index, q_dense, sel_ids, sel_mask, embeddings=None):
    """Step 3 dense scoring. Returns (doc_ids (B, S*cap), scores, mask).

    Thin wrapper over the engine pipeline with an in-memory backend (kept
    for baselines/benches that score explicit selections).
    """
    from repro.engine import pipeline as pipe_lib
    from repro.engine import stores as stores_lib
    emb = embeddings if embeddings is not None else index.embeddings
    store = stores_lib.InMemoryStore(emb, index.cluster_docs)
    return pipe_lib.score_selected(store, q_dense, sel_ids, sel_mask)


def retrieve(cfg, index, q_dense, q_terms, q_weights, *, selector="lstm",
             stage1="overlap", theta=None, use_kernel=False,
             selector_params=None, k=None):
    """Full CluSD pipeline (in-memory or PQ backend, chosen from the index).

    Thin wrapper over engine/pipeline.py — the select/score/fuse logic
    lives there, parameterized by a ClusterStore. Jit-able end to end.
    """
    from repro.engine import pipeline as pipe_lib
    from repro.engine import stores as stores_lib
    return pipe_lib.retrieve(
        cfg, index, stores_lib.store_for_index(index), q_dense, q_terms,
        q_weights, selector=selector, stage1=stage1, theta=theta,
        use_kernel=use_kernel, selector_params=selector_params, k=k)
