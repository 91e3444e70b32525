"""Plain dense reference of msmarco-hbm: a document's dense score is the
dot product of the float32 query and document embeddings, computed in
float32 at the highest matmul precision. The control is the same dot in
bfloat16 (both sides rounded, float32 accumulation), the precision below
the float32 that the configuration states.
"""

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 16
HI = jax.lax.Precision.HIGHEST


@jax.jit
def _dot(emb, qd, ids):
    return jnp.take_along_axis(jnp.dot(qd, emb.T, precision=HI), ids,
                               axis=1)


@jax.jit
def _dot_bf16(emb, qd, ids):
    s = jnp.dot(qd.astype(jnp.bfloat16), emb.astype(jnp.bfloat16).T,
                preferred_element_type=jnp.float32)
    return jnp.take_along_axis(s, ids, axis=1)


def _blocks(fn, data, q_dense, ids):
    return np.concatenate([
        np.asarray(fn(data["embeddings"], jnp.asarray(q_dense[i:i + BLOCK]),
                      jnp.asarray(ids[i:i + BLOCK])), np.float64)
        for i in range(0, len(ids), BLOCK)])


def dense(data, q_dense, ids):
    return _blocks(_dot, data, q_dense, ids)


def control(data, q_dense, ids):
    return _blocks(_dot_bf16, data, q_dense, ids)
