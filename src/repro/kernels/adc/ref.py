"""Pure-jnp oracles for the ADC kernel family.

Accumulation-order contract (shared by ref, Pallas kernel, and the
quant-lib device path): a block score is

    score[b, s, c] = sum_{j=0}^{nsub-1} lut[b, j, codes[sel[b, s], c, j]]

accumulated in ascending subspace order j with a single float32
accumulator, where each LUT entry is itself the float32 dot product
lut[b, j, k] = q_rot[b, j*dsub:(j+1)*dsub] . codebooks[j, k]. This is
dot(q, decode(codes)) with the dim-length sum reassociated into nsub
partial dots — identical math, reordered — so ADC scoring is
rank-equivalent to decode-then-score and agrees to float rounding.
"""

import jax
import jax.numpy as jnp


def adc_tables_ref(q, codebooks, rotation=None):
    """Per-query ADC lookup tables.

    q: (B, dim); codebooks: (nsub, K, dsub); rotation: (dim, dim) or None
    (the OPQ rotation is folded into the LUT build: q is rotated once,
    then never touched again — code scoring is rotation-free).
    Returns (B, nsub, K) float32.
    """
    hi = jax.lax.Precision.HIGHEST      # float32 on TPU too, as the kernel
    q = jnp.asarray(q, jnp.float32)
    if rotation is not None:
        q = jnp.dot(q, jnp.asarray(rotation, jnp.float32), precision=hi)
    nsub, K, dsub = codebooks.shape
    qs = q.reshape(q.shape[0], nsub, dsub)
    return jnp.einsum("bsd,skd->bsk", qs,
                      jnp.asarray(codebooks, jnp.float32), precision=hi)


def adc_score_blocks_ref(lut, code_blocks, sel_ids):
    """Score selected code blocks against per-query LUTs.

    lut: (B, nsub, K) float32; code_blocks: (N, cap, nsub) uint8/int;
    sel_ids: (B, S) int32. Returns (B, S, cap) float32 under the
    module-docstring accumulation order.
    """
    codes = jnp.take(code_blocks, sel_ids, axis=0).astype(jnp.int32)
    B = codes.shape[0]
    nsub = codes.shape[-1]
    b_idx = jnp.arange(B)[:, None, None, None]
    j_idx = jnp.arange(nsub)[None, None, None, :]
    vals = lut[b_idx, j_idx, codes]                  # (B, S, cap, nsub)
    return jnp.sum(vals, axis=-1).astype(jnp.float32)
