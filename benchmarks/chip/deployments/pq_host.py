"""The same corpus as 8-bit PQ codes (format v2) behind the host block
cache, served as the README serves a built index: `write_index`
(format_version 2) into a directory of the run, `IndexReader` ->
`ShardedPQStore` behind a byte-budgeted `BlockCache` with prefetch, and
RetrievalEngine's staged host path (Stage I, LUT build, Stage II, host
dedup and block fetch, one fused ADC score -> fuse -> top-k program).

The PQ codebooks and codes are data of the deployment, made here from
the seed by a plain k-means per subspace on a sample of the corpus, so
the reference can score the same codes without taking anything the
program made."""

import functools
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

import deploy
import gen

HI = jax.lax.Precision.HIGHEST


@functools.partial(jax.jit, static_argnames=("nsub", "n_codes", "iters",
                                             "sample"))
def _codebooks(key, emb, *, nsub, n_codes, iters, sample):
    n, dim = emb.shape
    idx = jax.random.choice(key, n, (sample,), replace=False)
    x = emb[idx].reshape(sample, nsub, dim // nsub).transpose(1, 0, 2)

    def step(c, _):
        d = (c * c).sum(-1)[:, None, :] - 2 * jnp.einsum(
            "snd,skd->snk", x, c, precision=HI)
        hot = jax.nn.one_hot(jnp.argmin(d, -1), n_codes, dtype=jnp.float32)
        sums = jnp.einsum("snk,snd->skd", hot, x, precision=HI)
        cnt = hot.sum(1)[..., None]
        return jnp.where(cnt > 0, sums / jnp.maximum(cnt, 1), c), None

    return jax.lax.scan(step, x[:, :n_codes], None, length=iters)[0]


@functools.partial(jax.jit, static_argnames=("chunk",))
def _encode(emb, books, *, chunk):
    nsub, _, dsub = books.shape
    c2 = (books * books).sum(-1)

    def one(x):
        xs = x.reshape(chunk, nsub, dsub)
        d = c2[None] - 2 * jnp.einsum("nsd,skd->nsk", xs, books,
                                      precision=HI)
        return jnp.argmin(d, -1).astype(jnp.uint8)

    return jax.lax.map(one, emb.reshape(-1, chunk, emb.shape[1])) \
        .reshape(emb.shape[0], nsub)


def make_pq(conf, emb):
    """(codebooks (nsub, 256, dsub) f32, codes (D, nsub) uint8) on the
    host, from the data seed."""
    books = _codebooks(gen.key_for(conf["data_seed"] + 10), emb,
                       nsub=conf["pq_nsub"], n_codes=256,
                       iters=conf["pq_iters"], sample=conf["pq_sample"])
    codes = _encode(emb, books, chunk=min(1 << 16, emb.shape[0]))
    return np.asarray(books), np.asarray(codes)


def build(conf, *, tracer, pools):
    from repro.core.quant import PQ
    from repro.index import IndexReader
    from repro.index.builder import write_index
    cfg = deploy.clusd_config(conf)
    emb, terms, weights, _ = deploy.make_corpus(conf)
    index, data = deploy.build_index_and_selector(cfg, conf, emb, terms,
                                                  weights)
    qsets = deploy.make_pools(conf, emb, terms, pools)
    t = time.perf_counter()
    books, codes = make_pq(conf, emb)
    t = deploy.log_time("PQ codebooks and codes", t)
    work = tempfile.mkdtemp(prefix="chipbench_pq_")
    try:
        out = f"{work}/index"
        write_index(out, cfg, index, emb, format_version=2,
                    pq=PQ(books, codes, None, conf["pq_nsub"]))
        del index, emb           # the served index lives on disk now
        t = deploy.log_time("write_index (format v2)", t)
        reader = IndexReader.open(out, verify="size")
        engine = reader.engine(max_batch=conf["max_batch"],
                               cache_capacity=conf["cache_blocks"],
                               prefetch=True, tracer=tracer)
        deploy.log_time("IndexReader and engine", t)
    except BaseException:
        shutil.rmtree(work, ignore_errors=True)
        raise
    return deploy.Deployment(
        cfg, engine, qsets,
        {**data, "codes": codes, "codebooks": books},
        cleanup=lambda: shutil.rmtree(work, ignore_errors=True))


def _pow2(n):
    return 1 << max(0, int(n) - 1).bit_length()


def warm_batches(dep, pool, batch):
    """Batches (rows of `pool`) that between them make the engine compile
    its fused tail for every unique-block bucket a batch of this size can
    reach: the union of a batch's selected clusters, padded to a power of
    two. A bucket is reached by adding warm queries, smallest selections
    first, while the union stays within it; one the warm queries cannot
    reach is reported and left out."""
    qd, qt, qw, _ = pool
    n = len(qd) // batch * batch
    sel = []
    for i in range(0, n, batch):
        st = dep.stages(qd[i:i + batch], qt[i:i + batch], qw[i:i + batch])
        ids, mask = st["sel_ids"], st["sel_mask"]
        sel += [set(ids[j][mask[j]].tolist()) for j in range(batch)]
    up = sorted(range(n), key=lambda j: len(sel[j]))
    top = min(batch * dep.cfg.max_selected, dep.cfg.n_clusters)
    out, missed = [], []
    b = 1
    while b <= _pow2(top):
        rows = _compose(sel, up, b, batch) or _compose(sel, up[::-1], b,
                                                        batch)
        if rows:
            out.append(np.array(rows + [rows[-1]] * (batch - len(rows))))
        else:
            missed.append(b)
        b *= 2
    return out, missed


def _compose(sel, order, b, batch):
    """Rows whose selections' union lands in bucket b, or None."""
    union, rows = set(), []
    for j in order:
        if len(rows) == batch or len(union) > b // 2:
            break
        if len(union | sel[j]) <= b:
            union |= sel[j]
            rows.append(j)
    return rows if rows and _pow2(max(1, len(union))) == b else None
