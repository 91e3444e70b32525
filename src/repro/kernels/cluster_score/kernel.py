"""Selected-cluster scoring kernel (paper Step 3, the partial-dense-retrieval
hot spot).

For each (query b, selection slot s): load the embedding block of cluster
sel_ids[b, s] from HBM into VMEM via a scalar-prefetch-driven BlockSpec
index_map (the gather happens in the DMA engine — no materialized
(B, S*cap, dim) gather in HBM, unlike the jnp reference), then one
(1, dim) x (dim, cap) MXU matmul per slot.

This is the TPU-native form of the paper's "cluster-based block I/O": the
HBM->VMEM DMA of a contiguous cluster block plays the role of the paper's
SSD block read (DESIGN.md §2).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _score_kernel(sel_ref, q_ref, blocks_ref, out_ref):
    # q_ref: (1, 1, dim); blocks_ref: (1, cap, dim); out_ref: (1, 1, 1, cap)
    out_ref[0, 0] = jax.lax.dot_general(
        q_ref[0], blocks_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)               # (1, cap)


@functools.partial(jax.jit, static_argnames=("interpret",))
def cluster_score_pallas(q, blocks, sel_ids, *, interpret=True):
    """q: (B, dim); blocks: (N, cap, dim); sel_ids: (B, S) int32.

    Returns scores (B, S, cap) float32. q is passed as (B, 1, dim) and the
    output produced as (B, S, 1, cap), so every block spans its array's
    last two dims (TPU tiling holds at any B and S).
    """
    B, dim = q.shape
    N, cap, _ = blocks.shape
    S = sel_ids.shape[1]

    # scalar-prefetch grid spec: sel_ids drives the blocks index_map
    from jax.experimental.pallas import tpu as pltpu
    kernel = pl.pallas_call(
        _score_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, S),
            in_specs=[
                pl.BlockSpec((1, 1, dim), lambda b, s, sel: (b, 0, 0)),
                pl.BlockSpec((1, cap, dim), lambda b, s, sel: (sel[b, s], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, 1, cap),
                                   lambda b, s, sel: (b, s, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, S, 1, cap), jnp.float32),
        interpret=interpret,
    )
    return kernel(sel_ids, q.reshape(B, 1, dim), blocks).reshape(B, S, cap)
