"""ADC (asymmetric distance computation) Pallas kernels — the PQ serving
fast path: build per-query lookup tables once, then accumulate scores
directly over uint8 codes, never reconstructing float embeddings.

Two kernels:

  * adc_tables_pallas — LUT build. Grid (nsub,); each cell is one
    (B, dsub) x (dsub, K) MXU matmul: lut[:, j] = q_sub[j] @ books[j].T.
    The OPQ rotation is folded in BEFORE the kernel (ops.py rotates q
    once), so the kernel sees only the rotated query.

  * adc_score_blocks_pallas — code scoring. Like cluster_score, sel_ids
    is scalar-prefetched and drives the code-block BlockSpec index_map:
    the (nsub, cap) uint8 block of cluster sel_ids[b, s] is DMA'd into
    VMEM (16x fewer bytes than the float block), then scores accumulate
    in-register in ascending subspace order (ref.py contract): per
    subspace the (1, K) LUT row hits a (K, cap) one-hot of the code row
    on the MXU — a gather-free formulation that lowers on TPU.

Every BlockSpec's last two dims span whole array axes (or are (8, 128)
multiples), as Mosaic requires; operands are laid out for that outside
the kernels, which changes no arithmetic.

Output is float32 and matches dot(q, decode(codes)) up to the documented
reassociation of the dim-length sum into nsub partial dots.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# f32 MXU passes: a one-hot row times a LUT row must return the LUT entry
# exactly, and the LUT build must match the oracle's f32 dot
_HIGHEST = jax.lax.Precision.HIGHEST


def _tables_kernel(q_ref, books_ref, out_ref):
    # q_ref: (1, B, dsub); books_ref: (1, dsub, K); out_ref: (1, B, K)
    out_ref[0] = jnp.dot(q_ref[0], books_ref[0], precision=_HIGHEST,
                         preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def adc_tables_pallas(q, codebooks, *, interpret=True):
    """q: (B, dim) float32 (already rotated); codebooks: (nsub, K, dsub).

    Returns LUT (B, nsub, K) float32. Grid (nsub,): subspace j is one
    (B, dsub) x (dsub, K) MXU matmul. Both operands are laid out
    subspace-major outside the kernel, so every block spans its array's
    last two dims (TPU tiling holds at any dsub, e.g. 768 / 8 = 96).
    """
    B, dim = q.shape
    nsub, K, dsub = codebooks.shape
    q_sub = q.astype(jnp.float32).reshape(B, nsub, dsub).transpose(1, 0, 2)
    books_t = jnp.asarray(codebooks, jnp.float32).transpose(0, 2, 1)
    lut = pl.pallas_call(
        _tables_kernel,
        grid=(nsub,),
        in_specs=[
            pl.BlockSpec((1, B, dsub), lambda j: (j, 0, 0)),
            pl.BlockSpec((1, dsub, K), lambda j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, B, K), lambda j: (j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nsub, B, K), jnp.float32),
        interpret=interpret,
    )(q_sub, books_t)
    return lut.transpose(1, 0, 2)


def _score_kernel(sel_ref, lut_ref, codes_ref, out_ref, *, nsub, K):
    # lut_ref: (1, nsub, K); codes_ref: (1, nsub, cap) subspace-major;
    # out_ref: (1, 1, 1, cap)
    codes = codes_ref[0].astype(jnp.int32)                 # (nsub, cap)
    cap = codes.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (K, cap), 0)
    acc = jnp.zeros((1, cap), jnp.float32)
    for j in range(nsub):
        # LUT row @ one-hot(codes[j]) on the MXU: a gather-free lookup;
        # ascending j is the documented accumulation order
        onehot = (codes[j:j + 1, :] == rows).astype(jnp.float32)  # (K, cap)
        acc = acc + jnp.dot(lut_ref[0, j:j + 1, :], onehot,
                            precision=_HIGHEST,
                            preferred_element_type=jnp.float32)   # (1, cap)
    out_ref[0, 0] = acc


@functools.partial(jax.jit, static_argnames=("interpret",))
def adc_score_blocks_pallas(lut, code_blocks, sel_ids, *, interpret=True):
    """lut: (B, nsub, K); code_blocks: (N, cap, nsub) uint8;
    sel_ids: (B, S) int32. Returns scores (B, S, cap) float32.

    The code blocks are laid out subspace-major, (N, nsub, cap), before
    the kernel: cap then fills the 128-wide lanes instead of nsub. The
    output is produced as (B, S, 1, cap) so that each grid cell's
    (1, cap) block spans the array's last two dims, then reshaped (free).
    """
    B, nsub, K = lut.shape
    N, cap, _ = code_blocks.shape
    S = sel_ids.shape[1]

    from jax.experimental.pallas import tpu as pltpu
    kernel = pl.pallas_call(
        functools.partial(_score_kernel, nsub=nsub, K=K),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, S),
            in_specs=[
                pl.BlockSpec((1, nsub, K), lambda b, s, sel: (b, 0, 0)),
                pl.BlockSpec((1, nsub, cap),
                             lambda b, s, sel: (sel[b, s], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, 1, cap),
                                   lambda b, s, sel: (b, s, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, S, 1, cap), jnp.float32),
        interpret=interpret,
    )
    return kernel(sel_ids, lut.astype(jnp.float32),
                  code_blocks.transpose(0, 2, 1)).reshape(B, S, cap)
