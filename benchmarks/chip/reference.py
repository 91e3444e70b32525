"""Plain reference for a CluSD answer (paper §2.1, steps 1-3), computed
from the deployment's data alone: the seeded corpus and queries, and
the clustering and selector weights that the benchmark made
(clusters.py, selector.py). It imports nothing of the program and takes
none of its tables.

For each query it computes, in float64 on the host or at the highest
precision on the device:

  1. the exact lexical score of every document and its top k_sparse;
  2. Stage I: per cluster and rank bin the count P and mean normalised
     sparse score Q of the top-k documents it holds; the clusters in
     descending lexicographic order of P, ties by query-centroid
     similarity, then by id; the first n are the candidates, each with
     the features (similarity, u averages of its similarity to the
     candidates of each position bin where they are among its m nearest
     centroids, P, Q);
  3. Stage II: the selector LSTM's logit per candidate; the candidates
     with probability >= theta (the most probable max_selected of them)
     are selected, and C is every document of the selected clusters;
  4. the dense score of every document in C (the configuration's
     reference file), its min-max normalisation over C, the sparse
     min-max normalisation over the top k_sparse, and the fused score
     alpha * sparse + (1 - alpha) * dense of every document in either.

Where the reference's own numbers leave a decision to rounding, it keeps
every outcome: a selector logit within LOGIT_MARGIN of theta's may fall
either way (each combination, up to MAX_FLIPS of them, is a selection
the answer may match). A query whose Stage I turns on a near-tie is
counted as unresolved, by its reason, and not compared: two sparse
scores at a bin edge, two candidates with the same P and similarities,
or a candidate at the edge of another's m nearest centroids, each closer
than TIE times the largest magnitude the two numbers could have (the top
sparse score; the product of the vectors' norms).

The gap of a served answer to a selection's fused scores is the largest
of: the distance of a served score from the reference's score of that
document; how far the list is out of order; how far the best document
left out scores above the list's last. An answer's gap is its smallest
over the selections the reference allows. A malformed list (wrong
length, an id out of range or twice, a score not finite) is counted
apart.
"""

import dataclasses
import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
# two scores or similarities closer than this, relative to the largest
# magnitude they could have, may order either way in float32
TIE = 1e-6
# a selector logit this close to theta's may land on either side (the
# program's logits lie within 1.2e-4 of the reference's on the chip)
LOGIT_MARGIN = 1e-3
# why a query was left to rounding
SPARSE_EDGE, STAGE1_ORDER, NEIGHBOUR_EDGE, SELECTION = (
    "sparse_edge", "stage1_order", "neighbour_edge", "selection")
# near-threshold candidates enumerated per query; more is unresolved
MAX_FLIPS = 4
QUERY_BLOCK = 16


@functools.partial(jax.jit, static_argnames=("k",))
def _sparse_block(doc_terms, doc_weights, q_terms, q_weights, *, k):
    dmask = (doc_terms >= 0) & (doc_weights > 0)

    def one(q):
        qt, qw = q
        w = jnp.where((qt >= 0) & (qw > 0), qw, 0.0)
        hit = (doc_terms[:, :, None] == qt[None, None, :]) & dmask[:, :, None]
        s = jnp.einsum("dkt,dk,t->d", hit.astype(jnp.float32), doc_weights,
                       w, precision=HI)
        return jax.lax.top_k(s, k)

    return jax.lax.map(one, (q_terms, q_weights))


def sparse_top(data, q_terms, q_weights, k):
    """Exact lexical (sum over shared terms of qw * dw) top-k of each
    query: (ids (B, k) int64, scores (B, k) float64), ties to the lower
    id."""
    out = [_sparse_block(data["doc_terms"], data["doc_weights"],
                         jnp.asarray(q_terms[i:i + QUERY_BLOCK]),
                         jnp.asarray(q_weights[i:i + QUERY_BLOCK]), k=k)
           for i in range(0, len(q_terms), QUERY_BLOCK)]
    return (np.concatenate([np.asarray(o[1], np.int64) for o in out]),
            np.concatenate([np.asarray(o[0], np.float64) for o in out]))


@jax.jit
def _sims(a, b):
    return jnp.dot(a, b.T, precision=HI)


def neighbours(centroids, m):
    """Centroid similarities (N, N) with -inf on the diagonal, each
    cluster's m-th and (m + 1)-th largest, and the centroids' norms."""
    c = np.asarray(centroids, np.float64)
    s = np.asarray(_sims(centroids, centroids), np.float64)
    np.fill_diagonal(s, -np.inf)
    m = min(m, len(s) - 1)
    part = -np.partition(-s, [m - 1, m], axis=1)
    return s, part[:, m - 1], part[:, m], np.linalg.norm(c, axis=1)


def max_doc_freq(doc_terms):
    """The longest posting list the corpus needs."""
    t = np.asarray(doc_terms).reshape(-1)
    return int(np.bincount(t[t >= 0]).max())


def _norm(s):
    return np.clip((s - s.min()) / max(s.max() - s.min(), 1e-9), 0.0, 1.0)


def stage_one(conf, top_ids, top_s, doc_cluster, qc, q_norm, nbr):
    """Candidates (n,), their features (n, 1 + u + 2v), and the reason a
    near-tie leaves them to rounding (None if none), for one query.
    top_ids/top_s hold k_sparse + 1 entries (the one past the edge tells
    a tie there)."""
    k, n, u = conf["k_sparse"], conf["n_candidates"], conf["u_bins"]
    edges = np.asarray(conf["bins"])
    v, n_clusters = len(edges), len(qc)
    sims, mth, nxt, c_norm = nbr
    tie = None
    if any(abs(top_s[e - 1] - top_s[e]) < TIE * abs(top_s[0])
           for e in edges if e < len(top_s)):
        tie = SPARSE_EDGE
    ids, s = top_ids[:k], top_s[:k]
    slot = doc_cluster[ids] * v + np.searchsorted(edges, np.arange(k),
                                                  side="right")
    P = np.bincount(slot, minlength=n_clusters * v).reshape(n_clusters, v)
    Q = (np.bincount(slot, _norm(s), minlength=n_clusters * v)
         .reshape(n_clusters, v) / np.maximum(P, 1))
    order = np.lexsort([-qc] + [-P[:, j] for j in range(v - 1, -1, -1)])
    a, b = order[:n], order[1:n + 1]
    scale = TIE * q_norm * np.maximum(c_norm[a], c_norm[b])
    if np.any((P[a] == P[b]).all(1) & (np.abs(qc[a] - qc[b]) < scale)):
        tie = tie or STAGE1_ORDER
    cand = order[:n]
    S = sims[np.ix_(cand, cand)]
    scale = TIE * c_norm[cand] * c_norm.max()
    edge = (mth - nxt)[cand] < scale
    if np.any(edge[:, None] & (np.abs(S - mth[cand][:, None])
                               < scale[:, None])):
        tie = tie or NEIGHBOUR_EDGE
    within = np.where(S >= mth[cand][:, None], S, 0.0)
    us = n // u
    f_avg = within[:, :us * u].reshape(n, u, us).mean(-1)
    feats = np.concatenate([qc[cand][:, None], f_avg, P[cand], Q[cand]], 1)
    return cand, feats, tie


def stage_one_batch(conf, data, q_dense, q_terms, q_weights, nbr=None):
    """stage_one over a query set -> (sparse ids, sparse scores, cand
    (B, n), feats (B, n, F) float64, tie reasons (B,), None where
    none)."""
    top_ids, top_s = sparse_top(data, q_terms, q_weights,
                                conf["k_sparse"] + 1)
    cents = data["centroids"]
    if nbr is None:
        nbr = neighbours(cents, conf["n_neighbors"])
    qc = np.concatenate([
        np.asarray(_sims(jnp.asarray(q_dense[i:i + 256]), cents),
                   np.float64) for i in range(0, len(q_dense), 256)])
    dc = np.asarray(data["doc_cluster"])
    q_norm = np.linalg.norm(np.asarray(q_dense, np.float64), axis=1)
    out = [stage_one(conf, top_ids[i], top_s[i], dc, qc[i], q_norm[i], nbr)
           for i in range(len(q_dense))]
    return (top_ids, top_s, np.stack([o[0] for o in out]),
            np.stack([o[1] for o in out]), [o[2] for o in out])


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def lstm_logits(p, feats):
    """The selector's logits (B, n) for features (B, n, F), float64."""
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    B, n, _ = feats.shape
    H = p["wh"].shape[0]
    h, c = np.zeros((B, H)), np.zeros((B, H))
    out = np.empty((B, n))
    for t in range(n):
        g = feats[:, t] @ p["wx"] + h @ p["wh"] + p["b"]
        i, f, gg, o = np.split(g, 4, axis=-1)
        c = _sigmoid(f) * c + _sigmoid(i) * np.tanh(gg)
        h = _sigmoid(o) * np.tanh(c)
        out[:, t] = h @ p["head_w"][:, 0] + p["head_b"][0]
    return out


def selections(conf, logit):
    """The selections (bool (n,)) the reference allows for one query's
    logits, or None where too many decisions are left to rounding."""
    theta = conf["theta"]
    z = np.log(theta / (1 - theta))
    near = np.flatnonzero(np.abs(logit - z) < LOGIT_MARGIN)
    if len(near) > MAX_FLIPS:
        return None
    out = []
    for flips in itertools.product((False, True), repeat=len(near)):
        pick = logit >= z
        pick[near[list(flips)]] ^= True
        ranked = np.lexsort((np.arange(len(logit)), -logit))
        ranked = ranked[pick[ranked]]
        keep = conf["max_selected"]
        if len(ranked) > keep:
            if abs(logit[ranked[keep - 1]] - logit[ranked[keep]]) \
                    < LOGIT_MARGIN:
                return None
            pick = np.zeros_like(pick)
            pick[ranked[:keep]] = True
        if not any((pick == o).all() for o in out):
            out.append(pick)
    return out


def fused(conf, top_ids, top_s, cand_docs, cand_dense, sel):
    """(sorted ids, fused scores) of every document with a part, for one
    query under one selection. cand_docs/cand_dense: (n, cap), -1 pad."""
    k, alpha = conf["k_sparse"], conf["alpha"]
    docs, d = cand_docs[sel].ravel(), cand_dense[sel].ravel()
    d = d[docs >= 0]
    docs = docs[docs >= 0]
    nd = _norm(d) if len(d) else d
    ids = np.concatenate([top_ids[:k], docs])
    part = np.concatenate([alpha * _norm(top_s[:k]), (1 - alpha) * nd])
    uid, inv = np.unique(ids, return_inverse=True)
    return uid, np.bincount(inv, part, minlength=len(uid))


def top_list(uid, score, k):
    """The k best (id, score), ties to the lower id, filled with the
    lowest ids scoring 0 as a scatter over the corpus would."""
    pos = score > 0
    order = np.lexsort((uid[pos], -score[pos]))[:k]
    ids, sc = uid[pos][order], score[pos][order]
    if len(ids) < k:
        fill = np.setdiff1d(np.arange(k + len(ids)), ids)[:k - len(ids)]
        ids = np.concatenate([ids, fill])
        sc = np.concatenate([sc, np.zeros(len(fill))])
    return ids, sc


def list_gap(ids, scores, uid, score):
    """Gap of a served list to reference scores (see the module
    docstring)."""
    j = np.clip(np.searchsorted(uid, ids), 0, len(uid) - 1)
    ref = np.where(uid[j] == ids, score[j], 0.0)
    off = float(np.abs(scores - ref).max())
    order = max(0.0, float(np.max(scores[1:] - scores[:-1], initial=0.0)))
    out = score[~np.isin(uid, ids)]
    left = max(0.0, float(np.max(out - scores[-1], initial=0.0)))
    return max(off, order, left)


def malformed(ids, scores, n_docs, k_final):
    return (len(ids) != k_final or ids.min() < 0 or ids.max() >= n_docs
            or len(np.unique(ids)) != len(ids)
            or not np.isfinite(scores).all())


@dataclasses.dataclass
class Report:
    gaps: list                  # one per compared answer
    malformed: int = 0
    # queries left to rounding, not compared: reason -> count
    left: dict = dataclasses.field(default_factory=dict)
    selected: list = dataclasses.field(default_factory=list)
    rows: list = dataclasses.field(default_factory=list)
    # what the reference computed, for diagnostics: candidates (B, n),
    # features (B, n, F), selector logits (B, n), Stage-I tie reasons
    # (B,), sparse top ids and scores (B, k + 1), and the scale of a
    # query-centroid similarity (|q| * the largest centroid norm)
    cand: np.ndarray = None
    feats: np.ndarray = None
    logits: np.ndarray = None
    ties: list = None
    top: tuple = None
    sim_scale: np.ndarray = None

    @property
    def gap(self):
        return float(max(self.gaps)) if self.gaps else float("inf")

    @property
    def unresolved(self):
        return sum(self.left.values())


def check(answers, queries, data, dense_fn, conf, *, control_fn=None):
    """Compare served answers [(ids, scores)] to the queries (q_dense,
    q_terms, q_weights) with the reference. `dense_fn(data, q_dense, ids)`
    is the configuration's dense reference. With `control_fn`, each
    answer is replaced by the reference's own list with the dense scores
    of `control_fn` (the control run). -> Report.

    Raises where a term has more postings than the program's budget: the
    program then drops postings that this reference keeps."""
    df = max_doc_freq(data["doc_terms"])
    if df > conf["max_postings"]:
        raise ValueError(f"a term is in {df} documents, over the posting "
                         f"budget {conf['max_postings']}: the exact lexical "
                         f"score is not what the program serves")
    qd = np.asarray(queries[0])
    top_ids, top_s, cand, feats, ties = stage_one_batch(
        conf, data, qd, queries[1], queries[2])
    logits = lstm_logits(data["selector"], feats)
    B, n = cand.shape
    docs = np.asarray(data["members"])[cand].reshape(B, -1)
    dense = dense_fn(data, qd, np.maximum(docs, 0))
    other = control_fn(data, qd, np.maximum(docs, 0)) if control_fn else None
    rep = Report(gaps=[], cand=cand, feats=feats, logits=logits, ties=ties,
                 top=(top_ids, top_s), sim_scale=np.linalg.norm(
                     qd.astype(np.float64), axis=1) * np.linalg.norm(
                     np.asarray(data["centroids"], np.float64), axis=1).max())
    for i, (ids, scores) in enumerate(answers):
        ids, scores = np.asarray(ids), np.asarray(scores, np.float64)
        sels = None if ties[i] else selections(conf, logits[i])
        if sels is None:
            why = ties[i] or SELECTION
            rep.left[why] = rep.left.get(why, 0) + 1
            continue
        cd = docs[i].reshape(n, -1)
        refs = [fused(conf, top_ids[i], top_s[i], cd,
                      dense[i].reshape(n, -1), s) for s in sels]
        if other is not None:
            ids, scores = top_list(*fused(conf, top_ids[i], top_s[i], cd,
                                          other[i].reshape(n, -1), sels[0]),
                                   conf["k_final"])
        if malformed(ids, scores, conf["n_docs"], conf["k_final"]):
            rep.malformed += 1
            continue
        g = [list_gap(ids, scores, *r) for r in refs]
        best = int(np.argmin(g))
        rep.gaps.append(g[best])
        rep.selected.append(int(sels[best].sum()))
        rep.rows.append(int((cd[sels[best]] >= 0).sum()))
    return rep
