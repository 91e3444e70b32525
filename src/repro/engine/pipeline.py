"""The single CluSD select/score/fuse pipeline, parameterized by a
ClusterStore backend (engine/stores.py).

Pre-engine, the repo had three copies of this logic — in-memory
(core/clusd.py), on-disk with a per-query Python loop (core/disk.py), and
PQ (core/quant.py). They now all route here:

  retrieve(cfg, index, store, ...) =
      sparse retrieval
      -> Stage I/II cluster selection (core/clusd.py, batched over queries)
      -> dense scoring of the selected cluster blocks via `store`
      -> min-max fusion + global top-k

Scoring has two shapes:
  * device stores (InMemoryStore, PQStore): a jit-traceable gather/ADC over
    (B, S) selected clusters — identical numerics to the pre-engine code.
  * host stores (DiskStore): selection still runs batched on device; block
    I/O is ONE deduplicated fetch for the whole query batch (optionally
    through a BlockCache), replacing the old per-query read loop.

The serving engine (engine/server.py) drives host stores through the
FUSED path instead of eager `score_and_fuse`: `dedup_selected` +
`fetch_unique_blocks`/`fetch_unique_code_blocks` stay on the host, and
`build_fused_scorer` compiles score -> mask -> fuse -> top-k into ONE
jitted pass per request bucket (unique-block count padded to power-of-two
so compilations stay bounded). For code-backed stores (`is_coded`) the
fused pass scores raw PQ codes via ADC lookup tables
(repro.kernels.adc) — floats are never decoded on the host.
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import clusd as clusd_lib
from repro.core import fusion as fusion_lib
from repro.core import sparse as sparse_lib
from repro.kernels import adc as adc_ops
from repro.obs import NOOP_TRACE


# ---------------------------------------------------------------------------
# compiled stage builders (shared by RetrievalEngine and ShardRouter)
# ---------------------------------------------------------------------------
# Each returns a fresh jitted fn that closes over `cfg` (static Python
# values only) and takes every index array as a jit ARGUMENT: the caller
# places the index pytree (core.clusd.CluSDIndex, the device stores, the
# PQ codebooks) on the device once and passes it on every call, so no
# program carries an index array as a compiled-in constant. Arguments a
# stage does not read are pruned from its executable by jit. Callers key
# the fns per request bucket and drop them when `cfg` moves (selector
# publishes for stage2 and the fused tails).
#
# Each program is named (`jit_clusd_<stage>` on a profile) and the core
# functions it calls wrap their bodies in `jax.named_scope`s (sparse_topk,
# stage1, selector, dense_score, fuse_topk), so a device trace can split
# any program's time by stage; scopes are metadata and compile to nothing.

def build_stage1_fn(cfg):
    """Sparse retrieval + Stage-I candidate generation.
    fn(index, qd, qt, qw) -> (sparse_ids, sparse_scores, cand, feats)."""
    def clusd_stage1(index, qd, qt, qw):
        sid, ss = sparse_lib.sparse_retrieve_topk(
            index.sparse_index, qt, qw, cfg.k_sparse)
        s1 = clusd_lib.stage1_candidates(cfg, index, qd, sid, ss)
        return sid, ss, s1["cand"], s1["feats"]
    return jax.jit(clusd_stage1)


def build_stage2_fn(cfg):
    """Stage-II LSTM cluster selection.
    fn(index, cand, feats) -> (sel_ids, sel_mask, probs) — probs are the
    raw per-candidate selector probabilities (explain telemetry compares
    them against theta/budget; they are computed anyway, so returning them
    is free). Only `index.lstm_params` reaches the executable."""
    def clusd_stage2(index, cand, feats):
        s2 = clusd_lib.stage2_select(cfg, index, cand, feats)
        return s2["sel_ids"], s2["sel_mask"], s2["probs"]
    return jax.jit(clusd_stage2)


def build_lut_fn():
    """Per-query ADC LUT build (OPQ rotation folded in).
    fn(codebooks, rotation, qd) -> (B, nsub, 256) float32."""
    def clusd_lut(codebooks, rotation, qd):
        with jax.named_scope("dense_score"):
            return adc_ops.adc_tables(qd, codebooks, rotation)
    return jax.jit(clusd_lut)


def build_device_fn(cfg, *, k):
    """The whole pipeline in one program for device stores (InMemoryStore,
    PQStore). fn(index, store, qd, qt, qw) -> (ids, scores, n_selected)."""
    def clusd_device_pipeline(index, store, qd, qt, qw):
        ids, scores, diag = retrieve(cfg, index, store, qd, qt, qw, k=k)
        return ids, scores, diag["n_selected"]
    return jax.jit(clusd_device_pipeline)


# ---------------------------------------------------------------------------
# dense scoring of selected clusters
# ---------------------------------------------------------------------------

@jax.named_scope("dense_score")
def score_selected(store, q_dense, sel_ids, sel_mask):
    """Device-store scoring (jit-traceable).

    q_dense (B, dim); sel_ids/sel_mask (B, S).
    Returns (doc_ids (B, S*cap) int32, scores with -inf at invalid, valid).
    """
    docs = jnp.take(store.cluster_docs, sel_ids, axis=0)     # (B, S, cap)
    B, S, cap = docs.shape
    valid = (docs >= 0) & sel_mask[:, :, None]
    docs_flat = jnp.where(valid, docs, 0).reshape(B, S * cap)
    scorer = getattr(store, "score_docs", None)
    if scorer is not None:
        scores = scorer(q_dense, docs_flat)
    else:
        vecs, _, _ = store.fetch_blocks(sel_ids)             # (B, S, cap, dim)
        scores = jnp.einsum("bd,bscd->bsc", q_dense, vecs).reshape(B, S * cap)
    scores = jnp.where(valid.reshape(B, S * cap), scores, -jnp.inf)
    return docs_flat.astype(jnp.int32), scores, valid.reshape(B, S * cap)


def fetch_unique_blocks(store, uniq, cache=None, trace=None):
    """Fetch blocks for sorted unique cluster ids, through the LRU cache
    when given. Only cache misses hit the store (and count as I/O ops).
    Returns (U, cap, dim) float32. `trace` (a repro.obs Trace) wraps the
    store reads in nested `disk_fetch` spans — cache hits emit none."""
    tr = trace if trace is not None else NOOP_TRACE

    def fill(cids):
        with tr.span("disk_fetch", n_blocks=len(cids)):
            return np.asarray(store.fetch_blocks(np.asarray(cids))[0])

    if cache is None:
        return fill(uniq)
    got = cache.get_or_fetch_many(uniq, fill)
    return np.stack([got[int(c)] for c in uniq])


def fetch_unique_code_blocks(store, uniq, cache=None, trace=None):
    """Raw-code sibling of `fetch_unique_blocks` for code-backed stores:
    returns (U, cap, nsub) uint8 — no decode happens anywhere on this
    path, and the cache holds CODE blocks (4*dim/nsub more clusters per
    cache byte than float blocks under a byte budget)."""
    tr = trace if trace is not None else NOOP_TRACE

    def fill(cids):
        with tr.span("disk_fetch", n_blocks=len(cids)):
            return np.asarray(store.fetch_code_blocks(np.asarray(cids))[0])

    if cache is None:
        return fill(uniq)
    got = cache.get_or_fetch_many(uniq, fill)
    return np.stack([got[int(c)] for c in uniq])


def dedup_selected(sel_ids, sel_mask):
    """Host-side dedup of the batch's selected clusters.

    -> (uniq (U,) int64 sorted unique cluster ids — never empty: an
    all-masked selection yields a single placeholder id 0 so downstream
    shapes stay static — and pos (B, S) positions into uniq; masked slots
    point at uniq[0] and are dropped by the validity mask later)."""
    sel = np.asarray(sel_ids)
    mask = np.asarray(sel_mask)
    if mask.any():
        uniq = np.unique(sel[mask])
    else:
        uniq = np.zeros((1,), np.int64)
    pos = np.searchsorted(uniq, np.where(mask, sel, uniq[0]))
    return uniq, pos.astype(np.int32)


def build_fused_scorer(cfg, n_docs, *, k, mode):
    """Compile score -> mask -> fuse -> top-k into one jitted pass.

    mode "adc":  blocks are (U, cap, nsub) uint8 PQ codes and q_or_lut is
                 the (B, nsub, 256) ADC lookup table (adc_tables, built
                 once per batch — the OPQ rotation is already folded in).
    mode "dot":  blocks are (U, cap, dim) float and q_or_lut is (B, dim).

    The returned fn(cluster_docs, q_or_lut, sid, ss, sel_ids, sel_mask,
    blocks, pos) -> (ids, scores) closes over cfg (including the fusion
    method/rrf_k) and the corpus size, so the engine must drop it on index
    and selector reloads (cfg is re-read)."""
    alpha, method, rrf_k = cfg.alpha, cfg.fusion, cfg.rrf_k

    def run(cluster_docs, q_or_lut, sid, ss, sel_ids, sel_mask, blocks, pos):
        with jax.named_scope("dense_score"):
            docs = jnp.take(cluster_docs, sel_ids, axis=0)     # (B, S, cap)
            B, S, cap = docs.shape
            valid = (docs >= 0) & sel_mask[:, :, None]
            if mode == "adc":
                scores3 = adc_ops.adc_score_blocks(q_or_lut, blocks, pos)
            else:
                vecs = jnp.take(blocks, pos, axis=0)           # (B,S,cap,dim)
                scores3 = jnp.einsum("bd,bscd->bsc", q_or_lut, vecs)
            vf = valid.reshape(B, S * cap)
            dscore = jnp.where(vf, scores3.reshape(B, S * cap), 0.0)
            did = jnp.where(valid, docs, 0).reshape(B, S * cap) \
                .astype(jnp.int32)
        return fusion_lib.fuse_topk(sid, ss, did, dscore, vf,
                                    n_docs, alpha, k,
                                    method=method, rrf_k=rrf_k)

    run.__name__ = run.__qualname__ = f"clusd_fused_{mode}"
    return jax.jit(run)


def score_selected_host(store, q_dense, sel_ids, sel_mask, cache=None,
                        use_kernel=False):
    """Host-store scoring: dedup selected cluster ids across the whole query
    batch, fetch each block at most once, then score on device. Mirrors
    `score_selected`'s contract exactly.

    use_kernel routes the block dot products through the cluster_score
    Pallas kernel over the (U, cap, dim) unique-block tensor — the per-slot
    block gather happens in the kernel's DMA index_map instead of a
    materialized (B, S, cap, dim) jnp.take."""
    sel = np.asarray(sel_ids)
    mask = np.asarray(sel_mask)
    B, S = sel.shape
    docs = store.cluster_docs_np[sel]                        # (B, S, cap)
    cap = docs.shape[-1]
    valid = (docs >= 0) & mask[:, :, None]
    if mask.any():
        uniq = np.unique(sel[mask])
        blocks = fetch_unique_blocks(store, uniq, cache)     # (U, cap, dim)
        pos = np.searchsorted(uniq, np.where(mask, sel, uniq[0]))
        if use_kernel:
            from repro.kernels.cluster_score import cluster_score
            scores = cluster_score(
                jnp.asarray(q_dense), jnp.asarray(blocks),
                jnp.asarray(pos, jnp.int32)).reshape(B, S * cap)
        else:
            # ship only the U unique blocks to device; expand by gather there
            vecs = jnp.take(jnp.asarray(blocks), jnp.asarray(pos), axis=0)
            scores = jnp.einsum("bd,bscd->bsc", q_dense,
                                vecs).reshape(B, S * cap)
    else:
        scores = jnp.zeros((B, S * cap), jnp.float32)
    valid_flat = jnp.asarray(valid.reshape(B, S * cap))
    scores = jnp.where(valid_flat, scores, -jnp.inf)
    docs_flat = jnp.asarray(np.where(valid, docs, 0).reshape(B, S * cap))
    return docs_flat.astype(jnp.int32), scores, valid_flat


# ---------------------------------------------------------------------------
# fusion + full pipeline
# ---------------------------------------------------------------------------

def score_and_fuse(cfg, index, store, q_dense, sparse_ids, sparse_scores,
                   sel_ids, sel_mask, *, k=None, cache=None,
                   use_kernel=False):
    """Step 3: dense-score the selected clusters via `store`, fuse with the
    sparse results. Returns (ids, scores, dmask)."""
    k = k or cfg.k_final
    if getattr(store, "is_host", False):
        did, dscore, dmask = score_selected_host(store, q_dense, sel_ids,
                                                 sel_mask, cache=cache,
                                                 use_kernel=use_kernel)
    else:
        did, dscore, dmask = score_selected(store, q_dense, sel_ids, sel_mask)
    ids, scores = fusion_lib.fuse_topk(
        sparse_ids, sparse_scores, did, jnp.where(dmask, dscore, 0.0), dmask,
        index.n_docs, cfg.alpha, k, method=cfg.fusion, rrf_k=cfg.rrf_k)
    return ids, scores, dmask


def retrieve(cfg, index, store, q_dense, q_terms, q_weights, *,
             selector="lstm", stage1="overlap", theta=None, use_kernel=False,
             selector_params=None, k=None, cache=None):
    """Full CluSD pipeline against any backend. Returns (ids, scores, diag).

    Jit-able end to end for device stores; for host stores selection runs
    on device and block fetch/score runs eagerly (call outside jit).
    """
    k = k or cfg.k_final
    sparse_ids, sparse_scores = sparse_lib.sparse_retrieve_topk(
        index.sparse_index, q_terms, q_weights, cfg.k_sparse)
    sel = clusd_lib.select_clusters(cfg, index, q_dense, sparse_ids,
                                    sparse_scores, selector=selector,
                                    stage1=stage1, theta=theta,
                                    use_kernel=use_kernel,
                                    selector_params=selector_params)
    ids, scores, dmask = score_and_fuse(
        cfg, index, store, q_dense, sparse_ids, sparse_scores,
        sel["sel_ids"], sel["sel_mask"], k=k, cache=cache,
        use_kernel=use_kernel)
    diag = {
        "n_selected": jnp.sum(sel["sel_mask"], axis=1),
        "frac_docs_scanned": jnp.mean(dmask.astype(jnp.float32), axis=1)
        * dmask.shape[1] / index.n_docs,
        "sparse_ids": sparse_ids, "sparse_scores": sparse_scores,
        **{k_: sel[k_] for k_ in ("cand", "probs", "sel_ids", "sel_mask")},
    }
    return ids, scores, diag
