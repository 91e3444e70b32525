"""Public ops: ADC LUT build + code-block scoring.

Dispatch policy differs from the other kernel packages on purpose: the
ADC ops sit on the SERVING hot path, where interpret-mode Pallas (a
Python-level emulator) would be orders of magnitude slower than XLA.
`use_kernel=None` (the default) therefore compiles the Pallas kernel on
TPU and falls back to the jnp oracle — same math, same accumulation
order (ref.py) — everywhere else. Tests pin `use_kernel=True` to
exercise the kernel bodies in interpret mode on CPU.
"""

import jax
import jax.numpy as jnp

from repro.kernels.adc.kernel import adc_score_blocks_pallas, adc_tables_pallas
from repro.kernels.adc.ref import adc_score_blocks_ref, adc_tables_ref


def _resolve(use_kernel):
    """-> (run_kernel, interpret)."""
    on_tpu = jax.default_backend() == "tpu"
    if use_kernel is None:
        use_kernel = on_tpu
    return bool(use_kernel), not on_tpu


def adc_tables(q, codebooks, rotation=None, *, use_kernel=None):
    """q: (B, dim) -> LUT (B, nsub, K) float32. The OPQ rotation is folded
    in here (q is rotated once; codes are scored rotation-free)."""
    run_kernel, interpret = _resolve(use_kernel)
    if not run_kernel:
        return adc_tables_ref(q, codebooks, rotation)
    q = jnp.asarray(q, jnp.float32)
    if rotation is not None:
        q = jnp.dot(q, jnp.asarray(rotation, jnp.float32),
                    precision=jax.lax.Precision.HIGHEST)
    return adc_tables_pallas(q, codebooks, interpret=interpret)


def adc_score_blocks(lut, code_blocks, sel_ids, *, use_kernel=None):
    """lut: (B, nsub, K); code_blocks: (N, cap, nsub) uint8;
    sel_ids: (B, S) -> (B, S, cap) float32 ADC scores."""
    run_kernel, interpret = _resolve(use_kernel)
    B, S = sel_ids.shape[0], sel_ids.shape[1]
    cap = code_blocks.shape[1]
    if S == 0 or cap == 0 or code_blocks.shape[0] == 0:
        # empty fetch/selection: nothing to score (a zero-size grid has no
        # kernel instances; keep the contract shape)
        return jnp.zeros((B, S, cap), jnp.float32)
    if not run_kernel:
        return adc_score_blocks_ref(lut, code_blocks, sel_ids)
    return adc_score_blocks_pallas(lut, jnp.asarray(code_blocks),
                                   jnp.asarray(sel_ids, jnp.int32),
                                   interpret=interpret)
