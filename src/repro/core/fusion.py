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

Two formulations, same answers (tests/test_clusd.py holds them to each
other at arbitrary id multiplicity):

  fuse_topk          serves. No scatter and no n_docs-wide array: one
      stable sort of the candidate entries by id, a loop that sums each
      run of equal ids left to right (one pass per entry beyond a run's
      first), and a sort of the run ends by (score desc, id asc), of
      which it keeps the first k. A doc id may appear ANY number of times
      across (and within) the two lists.
  fuse_topk_scatter  the tests' oracle: an (n_docs + 1) buffer per query,
      scatter-add every entry's contribution, then top-k over the whole
      corpus.

Every document outside the candidates scores exactly 0 (both methods'
contributions are >= 0), so `fuse_topk` adds k fill entries, ids 0..k-1
with contribution 0: they stand for the buffer's zero-score tail, of
which the lowest ids rank first. It needs k <= n_docs, as the buffer's
top-k does. Ties go to the lower id by the second sort key, not by the
order in which a top-k happens to leave equal scores (a TPU v5e's
`lax.top_k` over a 2^21-long row was seen to leave them out of index
order).

Sum order: the sort is stable and the dense entries precede the sparse
ones, so a doc on both sides sums dc + sc, the same float adds as the
buffer's 0 + dc + sc, and the scores are bitwise equal. A doc repeated
within one side is summed in list order; XLA leaves the order of
duplicate updates inside one scatter open, so there the two may differ
by a few ulp. `fuse_topk_merge` is the same merge with a caller-given
sentinel and no fill entries (the distributed path): sentinel ids and
-inf past the live entries.
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


def _contribs(sparse_ids, sparse_scores, dense_scores, dense_mask, alpha,
              method, rrf_k, sparse_mask):
    if sparse_mask is None:
        sparse_mask = jnp.ones_like(sparse_ids, bool)
    s_c = side_contrib(sparse_scores, sparse_mask, alpha, method, rrf_k)
    d_c = side_contrib(dense_scores, dense_mask, 1.0 - alpha, method, rrf_k)
    return sparse_mask, s_c, d_c


def _shift(x, fill):
    """x shifted one entry right along axis 1, `fill` entering at 0."""
    return jnp.concatenate([jnp.full_like(x[:, :1], fill), x[:, :-1]],
                           axis=1)


def _run_sums(ids_s, c_s, sentinel):
    """At every live entry of the id-sorted (B, L) list, the sum of its
    run of equal ids up to and including it, added left to right as the
    buffer's scatter adds them. Each pass extends every sum by one earlier
    entry of its run, so the loop runs (longest live run - 1) passes: one
    at serving, where a doc is at most once on each side. The dead entries
    (ids >= sentinel, tens of thousands of masked slots) form no run.
    Returns (sums (B, L), passes made)."""
    # entry i continues i - 1's run
    same = (ids_s == _shift(ids_s, -1)) & (ids_s < sentinel)

    def step(state):
        total, open_, passes = state   # open_: the sum at i lacks an entry
        return (jnp.where(same, _shift(total, 0.0) + c_s, c_s),
                same & _shift(open_, False), passes + 1)

    total, _, passes = jax.lax.while_loop(lambda st: jnp.any(st[1]), step,
                                          (c_s, same, jnp.int32(0)))
    return total, passes


def _entries(dense_ids, dense_mask, d_c, sparse_ids, sparse_mask, s_c,
             sentinel, n_fill):
    """The (B, L) merge list: (ids, contributions) of the dense entries,
    then the sparse ones, then n_fill zero entries with ids 0..n_fill-1;
    a masked entry takes the sentinel id."""
    B = dense_ids.shape[0]
    fill = jnp.arange(n_fill, dtype=jnp.int32)
    ids = jnp.concatenate([
        jnp.where(dense_mask, dense_ids, sentinel).astype(jnp.int32),
        jnp.where(sparse_mask, sparse_ids, sentinel).astype(jnp.int32),
        jnp.broadcast_to(fill, (B, n_fill))], axis=1)
    contrib = jnp.concatenate([d_c.astype(jnp.float32),
                               s_c.astype(jnp.float32),
                               jnp.zeros((B, n_fill), jnp.float32)], axis=1)
    return ids, contrib


def _merge_topk(ids, contrib, k, sentinel):
    """Top-k by (score desc, id asc) over the distinct ids of (B, L)
    entries, each id's score the sum of its entries' contributions; ids
    >= sentinel are dead. Returns (ids (B, k) int32 — sentinel past the
    live entries, scores (B, k))."""
    if k > ids.shape[1]:
        raise ValueError(f"k={k} exceeds the {ids.shape[1]} entries to rank")
    ids_s, c_s = jax.lax.sort((ids, contrib), dimension=1, num_keys=1,
                              is_stable=True)
    total, _ = _run_sums(ids_s, c_s, sentinel)
    last = jnp.concatenate([ids_s[:, 1:] != ids_s[:, :-1],
                            jnp.ones_like(ids_s[:, :1], bool)], axis=1)
    keep = last & (ids_s < sentinel)     # one entry per live doc: its run's end
    neg, top_ids = jax.lax.sort(
        (jnp.where(keep, -total, jnp.inf), jnp.where(keep, ids_s, sentinel)),
        dimension=1, num_keys=2)
    return top_ids[:, :k].astype(jnp.int32), -neg[:, :k]


@jax.named_scope("fuse_topk")
def fuse_topk(sparse_ids, sparse_scores, dense_ids, dense_scores, dense_mask,
              n_docs, alpha, k, *, sparse_mask=None, method="interp",
              rrf_k=60.0):
    """Union-merge + fuse + global top-k over a corpus of n_docs.

    sparse_ids/scores: (B, Ks) with optional sparse_mask for padding;
    dense_ids/scores: (B, Kd) with dense_mask for padding. Returns
    (ids (B, k), fused scores (B, k)), as `fuse_topk_scatter` does, by
    sort-merge over the candidates (module docstring). k <= n_docs.
    """
    if k > n_docs:
        raise ValueError(f"k={k} exceeds the corpus of {n_docs} documents")
    sparse_mask, s_c, d_c = _contribs(sparse_ids, sparse_scores,
                                      dense_scores, dense_mask, alpha,
                                      method, rrf_k, sparse_mask)
    return _merge_topk(*_entries(dense_ids, dense_mask, d_c, sparse_ids,
                                 sparse_mask, s_c, n_docs, k), k, n_docs)


def fuse_topk_scatter(sparse_ids, sparse_scores, dense_ids, dense_scores,
                      dense_mask, n_docs, alpha, k, *, sparse_mask=None,
                      method="interp", rrf_k=60.0):
    """`fuse_topk` through an (n_docs + 1) scatter-add buffer per query:
    the oracle the sort-merge is tested against."""
    sparse_mask, s_c, d_c = _contribs(sparse_ids, sparse_scores,
                                      dense_scores, dense_mask, alpha,
                                      method, rrf_k, sparse_mask)

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


@jax.named_scope("fuse_topk")
def fuse_topk_merge(sparse_ids, sparse_scores, dense_ids, dense_scores,
                    dense_mask, alpha, k, sentinel, *, sparse_mask=None,
                    method="interp", rrf_k=60.0):
    """Sort-merge fusion over the candidates alone, for callers that know
    no corpus size (the distributed path): no fill entries, so fewer
    than k live ids leave sentinel ids with score -inf at the tail.

    sentinel: id strictly greater than any real doc id (pads sort last).
    """
    sparse_mask, s_c, d_c = _contribs(sparse_ids, sparse_scores,
                                      dense_scores, dense_mask, alpha,
                                      method, rrf_k, sparse_mask)
    return _merge_topk(*_entries(dense_ids, dense_mask, d_c, sparse_ids,
                                 sparse_mask, s_c, sentinel, 0), k, sentinel)
