"""Score fusion (paper Step 3): combine the per-query top results of the
sparse and dense retrievers into one ranked list.

Two fusion methods share both formulations below:

  method="interp"  (paper / CC default): min-max normalize each side's
      VALID entries, then linear interpolation alpha*sparse +
      (1-alpha)*dense. Docs reached by only one retriever contribute 0 on
      the missing side after normalization.
  method="rrf"     weighted reciprocal-rank fusion (the hybrid-retrieval
      standard): fused(d) = alpha / (rrf_k + r_s(d)) +
      (1-alpha) / (rrf_k + r_d(d)), with 1-based ranks r among each
      side's VALID entries ordered (score desc, position asc — exactly
      lax.top_k's tie rule). Rank-based, so it needs no score
      normalization and is robust to incomparable score scales.

Both sides carry an explicit validity mask: `dense_mask` (required — the
dense candidate list is mask-padded by construction) and `sparse_mask`
(optional; None = every entry valid, the full-`k_sparse` serving case).
Masked entries contribute 0 AND are excluded from the min-max range /
rank assignment — a padded or ragged sparse list must not skew the
normalization of the real entries.

Two formulations, same semantics (property-tested against each other at
arbitrary id multiplicity in tests/test_clusd.py):

  fuse_topk        O(n_docs) scatter-add oracle (exact, jit-able).
  fuse_topk_merge  sort-merge without the O(n_docs) buffer — duplicates
      are folded by a segment-sum over the id-sorted entries, so a doc id
      may appear ANY number of times across (and within) the two lists;
      the distributed serving path and graph-expanded candidate lists
      both produce multiplicity > 2.
"""

import jax
import jax.numpy as jnp

FUSION_METHODS = ("interp", "rrf")


def minmax_norm(scores, mask=None):
    """Per-row min-max over valid entries. scores: (B, K)."""
    if mask is None:
        mask = jnp.ones_like(scores, bool)
    big = jnp.where(mask, scores, -jnp.inf)
    small = jnp.where(mask, scores, jnp.inf)
    mx = jnp.max(big, axis=-1, keepdims=True)
    mn = jnp.min(small, axis=-1, keepdims=True)
    rng = jnp.maximum(mx - mn, 1e-9)
    out = (scores - mn) / rng
    return jnp.where(mask, jnp.clip(out, 0.0, 1.0), 0.0)


def rank_desc(scores, mask):
    """1-based rank of every entry among its row's VALID entries, ordered
    (score desc, position asc) = lax.top_k's tie rule. Invalid entries
    rank after every valid one. scores/mask: (B, K) -> (B, K) int32."""
    keyed = jnp.where(mask, scores, -jnp.inf)
    order = jnp.argsort(-keyed, axis=-1, stable=True)
    inv = jnp.argsort(order, axis=-1, stable=True)     # inverse permutation
    return (inv + 1).astype(jnp.int32)


def side_contrib(scores, mask, weight, method, rrf_k):
    """Per-entry fused-score contribution of one retriever side.

    interp: weight * minmax_norm over valid entries; rrf: weight /
    (rrf_k + rank). Masked entries contribute exactly 0 either way."""
    if method == "interp":
        return weight * minmax_norm(scores, mask)
    if method == "rrf":
        r = rank_desc(scores, mask).astype(scores.dtype)
        return jnp.where(mask, weight / (rrf_k + r), 0.0)
    raise ValueError(f"unknown fusion method {method!r}; "
                     f"expected one of {FUSION_METHODS}")


@jax.named_scope("fuse_topk")
def fuse_topk(sparse_ids, sparse_scores, dense_ids, dense_scores, dense_mask,
              n_docs, alpha, k, *, sparse_mask=None, method="interp",
              rrf_k=60.0):
    """Union-merge + fuse + global top-k (exact scatter formulation).

    sparse_ids/scores: (B, Ks) with optional sparse_mask for padding;
    dense_ids/scores: (B, Kd) with dense_mask for padding. Returns
    (ids (B, k), fused scores (B, k)).
    """
    if sparse_mask is None:
        sparse_mask = jnp.ones_like(sparse_ids, bool)
    s_c = side_contrib(sparse_scores, sparse_mask, alpha, method, rrf_k)
    d_c = side_contrib(dense_scores, dense_mask, 1.0 - alpha, method, rrf_k)

    def one(sid, sc, sm, did, dc, dm):
        fused = jnp.zeros((n_docs + 1,), jnp.float32)
        # masked entries carry contribution 0 and are routed to the dump
        # row n_docs, so a padded id can never touch a real doc's score
        fused = fused.at[jnp.where(dm, did, n_docs)].add(dc)
        fused = fused.at[jnp.where(sm, sid, n_docs)].add(sc)
        scores, ids = jax.lax.top_k(fused[:n_docs], k)
        return ids.astype(jnp.int32), scores

    return jax.vmap(one)(sparse_ids, s_c, sparse_mask,
                         dense_ids, d_c, dense_mask)


def fuse_topk_merge(sparse_ids, sparse_scores, dense_ids, dense_scores,
                    dense_mask, alpha, k, sentinel, *, sparse_mask=None,
                    method="interp", rrf_k=60.0):
    """Sort-merge fusion WITHOUT an O(n_docs) scatter buffer — the serving
    path for corpus-scale retrieval.

    Duplicate ids are folded by a segment-sum over the id-sorted entry
    list, so a doc may appear any number of times across the two sides
    (multi-shard gathers and graph-expanded candidate lists legitimately
    surface a doc 3+ times; the old pairwise `roll` merge silently
    dropped the third occurrence).

    sentinel: id strictly greater than any real doc id (pads sort last).
    """
    if sparse_mask is None:
        sparse_mask = jnp.ones_like(sparse_ids, bool)
    s_c = side_contrib(sparse_scores, sparse_mask, alpha, method, rrf_k)
    d_c = side_contrib(dense_scores, dense_mask, 1.0 - alpha, method, rrf_k)

    def one(sid, sc, sm, did, dc, dm):
        ids = jnp.concatenate([jnp.where(sm, sid, sentinel),
                               jnp.where(dm, did, sentinel)])
        contrib = jnp.concatenate([sc, dc])       # masked entries already 0
        order = jnp.argsort(ids)
        ids_s = jnp.take(ids, order)
        c_s = jnp.take(contrib, order)
        L = ids_s.shape[0]
        # contiguous segment index per distinct id run
        first = jnp.concatenate([jnp.ones((1,), bool),
                                 ids_s[1:] != ids_s[:-1]])
        seg = jnp.cumsum(first) - 1                              # (L,)
        totals = jax.ops.segment_sum(c_s, seg, num_segments=L)
        seg_ids = jax.ops.segment_max(ids_s, seg, num_segments=L)
        live = (jnp.arange(L) < seg[-1] + 1) & (seg_ids < sentinel)
        seg_ids = jnp.where(live, seg_ids, sentinel)
        final = jnp.where(live, totals, -jnp.inf)
        top_s, top_i = jax.lax.top_k(final, k)
        return jnp.take(seg_ids, top_i).astype(jnp.int32), top_s

    return jax.vmap(one)(sparse_ids, s_c, sparse_mask,
                         dense_ids, d_c, dense_mask)
