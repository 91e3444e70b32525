"""What both deployments share: the corpus made on the device from the
configuration's data seed, the benchmark's clustering and selector
weights (clusters.py, selector.py), and the index built over them by
the program's own builders (cluster table, neighbour graph,
SparseIndex).

The corpus, clustering and selector are the deployment, fixed by
`data_seed`: every run of a cell serves the same index, and `--seed`
draws the traffic.
"""

import dataclasses
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import clusters
import gen
import reference
import selector

# query streams: the window's queries come from --seed in stream 0; the
# fixed sets (selector training, MRR evaluation, warm-up) from the data
# seed in streams of their own, so no seed can draw them again
WINDOW, TRAIN, EVAL, WARM = 0, 1, 2, 3


def log_time(what, t0):
    """Log a set-up step's seconds to standard error; returns now."""
    now = time.perf_counter()
    print(f"set-up: {what} {now - t0:.3f} s", file=sys.stderr, flush=True)
    return now


def clusd_config(conf):
    """The program's CluSDConfig for a configuration file."""
    from repro.configs.base import CluSDConfig
    fields = {f.name for f in dataclasses.fields(CluSDConfig)}
    kw = {k: v for k, v in conf.items() if k in fields}
    kw["bins"] = tuple(kw["bins"])
    return CluSDConfig(**kw)


def make_corpus(conf):
    t = time.perf_counter()
    out = gen.corpus(conf["data_seed"], conf["n_docs"], conf["dim"],
                     conf["vocab"], doc_terms=conf["doc_terms"])
    jax.block_until_ready(out)
    log_time("corpus", t)
    return out


def make_queries(conf, emb, terms, seed, n, stream):
    """Host arrays (q_dense, q_terms, q_weights, rel_doc) of n queries."""
    out = gen.queries(seed, emb, terms, n, conf["vocab"], stream=stream,
                      q_terms=conf["q_terms"])
    return tuple(np.asarray(x) for x in out)


def build_index_and_selector(cfg, conf, emb, terms, weights):
    """The program's index over the corpus, and the data the reference
    reads: the clustering (clusters.py) and the selector weights
    (selector.py) are the benchmark's, made from the data seed; the
    cluster table, neighbour graph and SparseIndex come from the
    program's own builders over them. -> (index, data)."""
    from repro.core import bins as bins_lib
    from repro.core import kmeans as km
    from repro.core.clusd import CluSDIndex
    from repro.core.sparse import SparseIndex
    t = time.perf_counter()
    cents, assign, members, moved = clusters.make(
        gen.key_for(conf["data_seed"] + 20), emb, cfg.n_clusters,
        cfg.cluster_cap, conf["kmeans_iters"])
    t = log_time(f"clustering ({moved} documents moved for capacity)", t)
    table, doc_cluster = km.build_cluster_table(assign, cfg.n_clusters,
                                                cfg.cluster_cap)
    if not np.array_equal(np.asarray(doc_cluster), assign):
        raise RuntimeError("the program's cluster table moved documents "
                           "of the benchmark's assignment")
    nb_ids, nb_sims = km.neighbor_graph(
        cents, min(cfg.n_neighbors, cfg.n_clusters - 1))
    sp = SparseIndex.build(np.asarray(terms), np.asarray(weights),
                           cfg.vocab, cfg.max_postings)
    jax.block_until_ready((table, nb_ids, sp))
    t = log_time("cluster table, neighbour graph, SparseIndex", t)
    data = {"doc_terms": terms, "doc_weights": weights,
            "doc_cluster": assign, "members": members, "centroids": cents}
    qd, qt, qw, _ = make_queries(conf, emb, terms, conf["data_seed"],
                                 cfg.train_queries, TRAIN)
    _, _, cand, feats, _ = reference.stage_one_batch(conf, data, qd, qt, qw)
    labels = selector.labels(emb, qd, cand, assign)
    t = log_time(f"selector labels (positive rate {labels.mean():.4f})", t)
    data["selector"] = selector.train(
        gen.key_for(conf["data_seed"] + 30), feats, labels,
        hidden=cfg.lstm_hidden, epochs=cfg.epochs, lr=cfg.lr,
        pos_weight=cfg.pos_weight)
    log_time("selector training", t)
    index = CluSDIndex(
        centroids=cents, cluster_docs=table, doc_cluster=doc_cluster,
        neighbor_ids=nb_ids, neighbor_sims=nb_sims, embeddings=emb,
        sparse_index=sp,
        lstm_params={k: jnp.asarray(v) for k, v in data["selector"].items()},
        bin_ids=bins_lib.rank_bin_ids(cfg.bins, cfg.k_sparse))
    return index, data


class Deployment:
    """A served deployment: the engine under test, the query sets drawn
    for this run (host arrays), and the data its reference needs."""

    def __init__(self, cfg, engine, pools, data, cleanup=None):
        self.cfg = cfg
        self.engine = engine
        self.pools = pools          # name -> (q_dense, q_terms, q_weights, rel)
        self.data = data            # what the plain reference reads
        self._cleanup = cleanup
        self._stages = None

    def serve(self, qd, qt, qw):
        """One request through the engine; the answer on the host."""
        ids, scores = self.engine.retrieve(qd, qt, qw)
        return np.asarray(ids), np.asarray(scores)

    def stages(self, qd, qt, qw):
        """The program's own Stage-I and Stage-II programs over its index
        (warm-up and diagnostics only): {sparse_ids, sparse_scores, cand,
        feats, sel_ids, sel_mask, probs}, host arrays."""
        from repro.engine import pipeline as pipe_lib
        eng = self.engine
        if self._stages is None:
            self._stages = (pipe_lib.build_stage1_fn(eng.cfg),
                            pipe_lib.build_stage2_fn(eng.cfg))
        stage1, stage2 = self._stages
        sid, ss, cand, feats = stage1(eng.index, qd, qt, qw)
        sel_ids, sel_mask, probs = stage2(eng.index, cand, feats)
        names = ("sparse_ids", "sparse_scores", "cand", "feats", "sel_ids",
                 "sel_mask", "probs")
        return {k: np.asarray(v) for k, v in zip(
            names, (sid, ss, cand, feats, sel_ids, sel_mask, probs))}

    def cache_counts(self):
        """(hits, misses) of the engine's block cache, or None."""
        c = self.engine.cache
        return None if c is None else (c.hits, c.misses)

    def close(self):
        self.engine.close()
        self.engine = None
        if self._cleanup:
            self._cleanup()


def make_pools(conf, emb, terms, pools):
    """{name: (seed, n, stream)} -> {name: host query arrays}."""
    t = time.perf_counter()
    out = {name: make_queries(conf, emb, terms, *spec)
           for name, spec in pools.items()}
    log_time("query sets", t)
    return out
