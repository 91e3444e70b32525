"""The float32 corpus in HBM, served by RetrievalEngine's one-jit device
path (InMemoryStore, pipeline.build_device_fn): sparse retrieval,
Stage I, the LSTM selection, the row gather and dot, fusion and top-k in
one program per batch bucket."""

import deploy


def build(conf, *, tracer, pools):
    from repro.engine import RetrievalEngine
    cfg = deploy.clusd_config(conf)
    emb, terms, weights, _ = deploy.make_corpus(conf)
    index, data = deploy.build_index_and_selector(cfg, conf, emb, terms,
                                                  weights)
    engine = RetrievalEngine(cfg, index, max_batch=conf["max_batch"],
                             tracer=tracer)
    return deploy.Deployment(
        cfg, engine, deploy.make_pools(conf, emb, terms, pools),
        {**data, "embeddings": emb})


def warm_batches(dep, pool, batch):
    """Extra warm batches: none, one batch bucket is one program.
    -> (batches, buckets missed)."""
    return [], []
