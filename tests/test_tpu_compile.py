"""The serving path compiled for a TPU v5e that is described, not attached.

Each test compiles with the TPU compiler at the smoke deployment's widths
(`chip_smoke.py`: clusd-msmarco full widths, 2^20 docs, 1,024 clusters,
2,048-row blocks, PQ nsub 8): what Mosaic or XLA would refuse on the chip
fails here, at no chip time. Nothing runs, so nothing here says anything
about results or speed.

The topology is described inside a fixture (never while a module is
imported), and everything built from it is built in fixtures or tests,
so every pytest-xdist worker collects the same tests and only the worker
given this file loads the TPU library.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core.clusd import CluSDIndex
from repro.core.features import feature_dim
from repro.core.sparse import SparseIndex
from repro.engine import pipeline as pipe_lib
from repro.engine.stores import InMemoryStore
from repro.kernels.adc import ops as adc_ops
from repro.kernels.adc.kernel import adc_score_blocks_pallas, adc_tables_pallas
from repro.kernels.cluster_score.kernel import cluster_score_pallas
from repro.kernels.lstm.kernel import lstm_sequence_pallas

GiB = 2 ** 30
HBM_BYTES = 16 * GiB            # one v5e chip
NSUB, K = 8, 256
SERVE_BATCH = 16                # chip_smoke.SERVE_BATCH


@pytest.fixture(scope="module")
def topo():
    # without the TPU library there is no compiler to ask; with it, any
    # failure to describe the chip is a failure, not a skip
    pytest.importorskip("libtpu")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """Compiles for a described chip are written to the persistent cache
    but cannot be read back without one: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.fixture(scope="module")
def cfg():
    return dataclasses.replace(get_config("clusd-msmarco", "full"),
                               n_docs=1 << 20, n_clusters=1024)


@pytest.fixture
def sds(one_chip):
    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


@pytest.fixture
def tpu_dispatch(monkeypatch):
    """The ADC ops choose the kernel from jax.default_backend(), which is
    the CPU here: steer them to the compiled (non-interpret) kernel."""
    monkeypatch.setattr(adc_ops, "_resolve", lambda use_kernel: (True, False))


def _smoke_index(cfg, sds):
    D, N, cap, dim = cfg.n_docs, cfg.n_clusters, cfg.cluster_cap, cfg.dim
    F, H, i32 = feature_dim(cfg), cfg.lstm_hidden, jnp.int32
    lstm = {"wx": sds((F, 4 * H)), "wh": sds((H, 4 * H)), "b": sds((4 * H,)),
            "head_w": sds((H, 1)), "head_b": sds((1,))}
    return CluSDIndex(
        centroids=sds((N, dim)), cluster_docs=sds((N, cap), i32),
        doc_cluster=sds((D,), i32),
        neighbor_ids=sds((N, cfg.n_neighbors), i32),
        neighbor_sims=sds((N, cfg.n_neighbors)),
        embeddings=sds((D, dim)),
        sparse_index=SparseIndex(sds((cfg.vocab, cfg.max_postings), i32),
                                 sds((cfg.vocab, cfg.max_postings)), D),
        lstm_params=lstm, bin_ids=sds((cfg.k_sparse,), i32))


def _footprint(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _has_kernel(compiled):
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("batch", [1, SERVE_BATCH])
def test_adc_tables_compiles(cfg, sds, batch):
    compiled = jax.jit(
        lambda q, cb: adc_tables_pallas(q, cb, interpret=False)).lower(
        sds((batch, cfg.dim)), sds((NSUB, K, cfg.dim // NSUB))).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("batch", [1, SERVE_BATCH])
def test_adc_score_blocks_compiles(cfg, sds, batch):
    S = cfg.max_selected
    compiled = jax.jit(
        lambda lut, codes, sel: adc_score_blocks_pallas(
            lut, codes, sel, interpret=False)).lower(
        sds((batch, NSUB, K)),
        sds((batch * S, cfg.cluster_cap, NSUB), jnp.uint8),
        sds((batch, S), jnp.int32)).compile()
    assert _has_kernel(compiled)
    # the (U, cap, nsub) code blocks stay unpadded: nsub never fills lanes
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


def test_lstm_sequence_compiles(cfg, sds):
    F, H, n = feature_dim(cfg), cfg.lstm_hidden, cfg.n_candidates
    compiled = jax.jit(
        lambda x, wx, wh, b: lstm_sequence_pallas(
            x, wx, wh, b, interpret=False)).lower(
        sds((256, n, F)), sds((F, 4 * H)), sds((H, 4 * H)),
        sds((4 * H,))).compile()
    assert _has_kernel(compiled)


def test_cluster_score_compiles(cfg, sds):
    S = cfg.max_selected
    compiled = jax.jit(
        lambda q, blocks, sel: cluster_score_pallas(
            q, blocks, sel, interpret=False)).lower(
        sds((SERVE_BATCH, cfg.dim)),
        sds((SERVE_BATCH * S // 4, cfg.cluster_cap, cfg.dim)),
        sds((SERVE_BATCH, S), jnp.int32)).compile()
    assert _has_kernel(compiled)


def test_stage1_and_fused_tail_fit_one_chip(cfg, sds, tpu_dispatch):
    """Sparse retrieval + Stage I, then the ADC fused score -> fuse ->
    top-k tail, as one program over the smoke index: it fits one chip's
    HBM and takes the postings as arguments."""
    index = _smoke_index(cfg, sds)
    stage1 = pipe_lib.build_stage1_fn(cfg)
    tail = pipe_lib.build_fused_scorer(cfg, cfg.n_docs, k=cfg.k_final,
                                       mode="adc")

    def serve(index, qd, qt, qw, lut, sel_ids, sel_mask, blocks, pos):
        sid, ss, _, _ = stage1(index, qd, qt, qw)
        return tail(index.cluster_docs, lut, sid, ss, sel_ids, sel_mask,
                    blocks, pos)

    B, S, i32 = SERVE_BATCH, cfg.max_selected, jnp.int32
    compiled = jax.jit(serve).lower(
        index, sds((B, cfg.dim)), sds((B, 8), i32), sds((B, 8)),
        sds((B, NSUB, K)), sds((B, S), i32), sds((B, S), jnp.bool_),
        sds((B * S, cfg.cluster_cap, NSUB), jnp.uint8),
        sds((B, S), i32)).compile()
    assert _has_kernel(compiled)
    postings = 2 * cfg.vocab * cfg.max_postings * 4
    assert compiled.memory_analysis().argument_size_in_bytes >= postings
    assert _footprint(compiled) < HBM_BYTES


def test_device_program_fits_one_chip_at_serving_batch(cfg, sds):
    """The in-memory one-jit program at chip_smoke's serving batch: the
    corpus embeddings and postings are arguments, and the (B, S*cap, dim)
    gather still leaves it inside one chip's HBM."""
    index = _smoke_index(cfg, sds)
    store = InMemoryStore(index.embeddings, index.cluster_docs)
    B, i32 = SERVE_BATCH, jnp.int32
    compiled = pipe_lib.build_device_fn(cfg, k=cfg.k_final).lower(
        index, store, sds((B, cfg.dim)), sds((B, 8), i32),
        sds((B, 8))).compile()
    arrays = (cfg.n_docs * cfg.dim + 2 * cfg.vocab * cfg.max_postings) * 4
    assert compiled.memory_analysis().argument_size_in_bytes >= arrays
    assert _footprint(compiled) < HBM_BYTES
