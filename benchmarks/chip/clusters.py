"""The deployment's clustering, made by the benchmark from the data seed:
k-means centroids and a capacity-respecting assignment of every document
to one cluster. The program's index is built over this assignment with
its own builders (cluster table, neighbour graph), and the plain
reference reads the same assignment, so neither side takes the other's
tables.

A cluster holds at most `cap` documents (the program's padded block).
Documents past a cluster's capacity, farthest from its centroid first,
move to their next-nearest cluster with room, as a balanced IVF does.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _chunk(n):
    c = min(n, 1 << 15)
    while n % c:
        c //= 2
    return c


@functools.partial(jax.jit, static_argnames=("chunk",))
def _nearest(emb, cents, *, chunk):
    """(nearest centroid, squared distance to it) of every row."""
    c2 = (cents * cents).sum(-1)

    def one(x):
        d = c2[None] - 2 * jnp.einsum("nd,kd->nk", x, cents, precision=HI)
        return (jnp.argmin(d, -1).astype(jnp.int32),
                d.min(-1) + (x * x).sum(-1))

    a, d = jax.lax.map(one, emb.reshape(-1, chunk, emb.shape[1]))
    return a.reshape(-1), d.reshape(-1)


@functools.partial(jax.jit, static_argnames=("n_clusters", "iters",
                                             "chunk"))
def _kmeans(key, emb, *, n_clusters, iters, chunk):
    n = emb.shape[0]
    init = emb[jax.random.choice(key, n, (n_clusters,), replace=False)]

    def step(c, _):
        a, _ = _nearest(emb, c, chunk=chunk)
        sums = jax.ops.segment_sum(emb, a, num_segments=n_clusters)
        cnt = jax.ops.segment_sum(jnp.ones((n,), jnp.float32), a,
                                  num_segments=n_clusters)[:, None]
        return jnp.where(cnt > 0, sums / jnp.maximum(cnt, 1.0), c), None

    return jax.lax.scan(step, init, None, length=iters)[0]


def balance(assign, dist, emb, cents, cap):
    """Move the documents past each cluster's capacity (farthest first)
    to their next-nearest cluster with room. Host arrays in, host
    assignment out."""
    assign = np.asarray(assign).copy()
    n_clusters = cents.shape[0]
    counts = np.bincount(assign, minlength=n_clusters)
    if counts.max() <= cap:
        return assign, 0
    order = np.lexsort((-np.asarray(dist), assign))   # by cluster, near first
    start = np.cumsum(counts) - counts
    rank = np.empty(len(assign), np.int64)
    rank[order] = np.arange(len(assign)) - start[assign[order]]
    over = np.flatnonzero(rank >= cap)
    counts = np.minimum(counts, cap)
    x = np.asarray(emb[jnp.asarray(over)], np.float64)
    c = np.asarray(cents, np.float64)
    pref = np.argsort((c * c).sum(1)[None] - 2 * x @ c.T, axis=1)
    for i, d in enumerate(over):
        for k in pref[i]:
            if counts[k] < cap:
                counts[k] += 1
                assign[d] = k
                break
    return assign, len(over)


def members_table(assign, n_clusters, cap):
    """(n_clusters, cap) document ids of each cluster in id order, -1
    padded."""
    order = np.argsort(assign, kind="stable")
    counts = np.bincount(assign, minlength=n_clusters)
    start = np.cumsum(counts) - counts
    slot = np.arange(len(assign)) - start[assign[order]]
    table = np.full((n_clusters, cap), -1, np.int64)
    table[assign[order], slot] = order
    return table


def make(key, emb, n_clusters, cap, iters):
    """-> (centroids (N, dim) f32 on the device, assignment (D,) int32,
    members (N, cap) int64 host, documents moved by balancing)."""
    chunk = _chunk(emb.shape[0])
    cents = _kmeans(key, emb, n_clusters=n_clusters, iters=iters,
                    chunk=chunk)
    a, d = _nearest(emb, cents, chunk=chunk)
    assign, moved = balance(np.asarray(a), np.asarray(d), emb, cents, cap)
    return (cents, assign.astype(np.int32),
            members_table(assign, n_clusters, cap), moved)
