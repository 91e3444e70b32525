"""The deployment's Stage-II selector weights, trained by the benchmark
(paper §2.3): an LSTM over the n Stage-I candidates of a query, one
logit per candidate, trained with class-weighted binary cross-entropy
against labels "the cluster holds one of the query's top-10 full dense
results" by Adam. The features and labels come from the plain reference
(reference.py), so the weights are data of the deployment that the
program serves and the reference reads alike.

The weights are laid out as the program's index holds a selector:
wx (F, 4H), wh (H, 4H), b (4H,), head_w (H, 1), head_b (1,), with the
gates in the order input, forget, cell, output.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
BATCH = 256
TOP_DENSE = 10


def init(key, feat_dim, hidden):
    k = jax.random.split(key, 3)

    def glorot(key, shape):
        return jax.random.normal(key, shape, jnp.float32) * np.sqrt(
            2.0 / sum(shape))

    return {"wx": glorot(k[0], (feat_dim, 4 * hidden)),
            "wh": glorot(k[1], (hidden, 4 * hidden)),
            "b": jnp.zeros((4 * hidden,), jnp.float32),
            "head_w": glorot(k[2], (hidden, 1)),
            "head_b": jnp.zeros((1,), jnp.float32)}


def logits(p, feats):
    """feats (B, n, F) -> (B, n) logits."""
    B = feats.shape[0]
    H = p["wh"].shape[0]

    def step(carry, x):
        h, c = carry
        g = (jnp.dot(x, p["wx"], precision=HI)
             + jnp.dot(h, p["wh"], precision=HI) + p["b"])
        i, f, gg, o = jnp.split(g, 4, axis=-1)
        c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(gg)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), h

    zero = jnp.zeros((B, H), jnp.float32)
    _, hs = jax.lax.scan(step, (zero, zero), jnp.moveaxis(feats, 1, 0))
    return (jnp.dot(jnp.moveaxis(hs, 0, 1), p["head_w"], precision=HI)
            + p["head_b"])[..., 0]


@functools.partial(jax.jit, static_argnames=("epochs",))
def _train(key, p, feats, labels, *, epochs, lr, pos_weight):
    n = feats.shape[0]
    size = min(BATCH, n)
    steps = n // size

    def loss(p, f, y):
        z = logits(p, f)
        # -(w y log s(z) + (1 - y) log(1 - s(z))), stably
        return jnp.mean(pos_weight * y * jax.nn.softplus(-z)
                        + (1 - y) * jax.nn.softplus(z))

    def adam(carry, batch):
        p, m, v, t = carry
        f, y = batch
        g = jax.grad(loss)(p, f, y)
        t = t + 1
        m = jax.tree.map(lambda m, g: 0.9 * m + 0.1 * g, m, g)
        v = jax.tree.map(lambda v, g: 0.999 * v + 0.001 * g * g, v, g)
        corr = jnp.sqrt(1 - 0.999 ** t) / (1 - 0.9 ** t)
        p = jax.tree.map(lambda p, m, v: p - lr * corr * m
                         / (jnp.sqrt(v) + 1e-8), p, m, v)
        return (p, m, v, t), None

    def epoch(carry, k):
        perm = jax.random.permutation(k, n)[:steps * size].reshape(
            steps, size)
        return jax.lax.scan(adam, carry, (feats[perm], labels[perm]))[0], \
            None

    zeros = jax.tree.map(jnp.zeros_like, p)
    carry = (p, zeros, zeros, jnp.zeros((), jnp.float32))
    return jax.lax.scan(epoch, carry, jax.random.split(key, epochs))[0][0]


def train(key, feats, labels, *, hidden, epochs, lr, pos_weight):
    """Weights (host float32 arrays) from (B, n, F) features and (B, n)
    labels; batches of 256 (or all of a smaller set), `epochs` passes."""
    k0, k1 = jax.random.split(key)
    p = init(k0, feats.shape[-1], hidden)
    p = _train(k1, p, jnp.asarray(feats, jnp.float32),
               jnp.asarray(labels, jnp.float32), epochs=epochs,
               lr=jnp.float32(lr), pos_weight=jnp.float32(pos_weight))
    return {k: np.asarray(v) for k, v in p.items()}


@functools.partial(jax.jit, static_argnames=("k",))
def _dense_top(emb, qd, *, k):
    return jax.lax.top_k(jnp.dot(qd, emb.T, precision=HI), k)[1]


def labels(emb, q_dense, cand, doc_cluster, block=256):
    """1 where a candidate cluster holds one of the query's top-10 full
    dense results. cand (B, n) host; -> (B, n) float32."""
    top = np.concatenate([
        np.asarray(_dense_top(emb, jnp.asarray(q_dense[i:i + block]),
                              k=TOP_DENSE))
        for i in range(0, len(q_dense), block)])
    pos = np.asarray(doc_cluster)[top]                      # (B, 10)
    return (cand[:, :, None] == pos[:, None, :]).any(-1).astype(np.float32)
