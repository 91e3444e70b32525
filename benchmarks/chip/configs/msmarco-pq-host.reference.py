"""Plain dense reference of msmarco-pq-host: a document's dense score is
the asymmetric distance (ADC) score of its 8-bit PQ code, the sum over
subspaces of the query's sub-vector dotted with the code's centroid,
computed in float64 from the codes and codebooks that the benchmark made
from the seed. The control builds the lookup tables in bfloat16 (query
sub-vectors and codebooks rounded, float32 accumulation), the precision
below the float32 tables the configuration states.
"""

import jax.numpy as jnp
import numpy as np

BLOCK = 16


def _bf16(x):
    return np.asarray(x, np.float32).astype(jnp.bfloat16).astype(np.float64)


def _adc(books, q_dense, codes, round_to=None):
    nsub, _, dsub = books.shape
    qs = np.asarray(q_dense, np.float64).reshape(len(q_dense), nsub, dsub)
    books = np.asarray(books, np.float64)
    if round_to is not None:
        qs, books = round_to(qs), round_to(books)
    lut = np.einsum("bsd,skd->bsk", qs, books)
    if round_to is not None:
        lut = lut.astype(np.float32).astype(np.float64)
    s = np.arange(nsub)
    return np.stack([lut[b][s, codes[b]].sum(-1) for b in range(len(codes))])


def _blocks(data, q_dense, ids, round_to=None):
    return np.concatenate([
        _adc(data["codebooks"], q_dense[i:i + BLOCK],
             data["codes"][ids[i:i + BLOCK]], round_to)
        for i in range(0, len(ids), BLOCK)])


def dense(data, q_dense, ids):
    return _blocks(data, q_dense, ids)


def control(data, q_dense, ids):
    return _blocks(data, q_dense, ids, _bf16)
