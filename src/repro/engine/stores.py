"""Pluggable cluster-block storage backends behind one protocol.

Every backend answers the same question — "give me the embedding blocks for
these clusters" — so the select/score/fuse pipeline (engine/pipeline.py) is
written once and parameterized by the store:

  fetch_blocks(cluster_ids) -> (vecs, docs, valid)
    cluster_ids : int array; device stores accept any leading batch shape
                  (jit-traceable), host stores take a 1-D host sequence.
    vecs  : (..., cap, dim) float32 block embeddings
    docs  : (..., cap)      int32 doc ids, -1 pad
    valid : (..., cap)      bool  (docs >= 0)

Backends additionally expose:
  cluster_docs : (N, cap) doc-id table (device array)
  is_host      : True when fetch_blocks does host I/O (not jit-traceable);
                 the pipeline then batches selection on device and fetches
                 deduplicated blocks on the host.
  is_coded     : True when the backend's native records are PQ codes; it
                 then also exposes `fetch_code_blocks(cluster_ids) ->
                 (codes, docs, valid)` returning RAW (..., cap, nsub)
                 uint8 code blocks plus `codebooks`/`rotation`/`nsub`, so
                 the pipeline can score codes directly via ADC lookup
                 tables (repro.kernels.adc) without ever decoding floats.
  score_docs(q_dense, doc_ids) [optional] : backend-native scoring kernel
                 (dense gather+dot, PQ ADC); the pipeline prefers it on the
                 device path so numerics match the pre-engine code exactly.

Five backends speak the protocol: InMemoryStore and PQStore (device),
DiskStore, and — re-exported from repro.index.sharded — ShardedDiskStore
(format-v1 float block shards) and ShardedPQStore (format-v2 PQ code
shards, decode-on-fetch ADC). The sharded stores additionally accept an
incrementally-updated index's tombstone bitmap and mask deleted slots at
fetch time (docs=-1/valid=False; the shard bytes are never rewritten for
a delete). The full contract — fetch semantics, IOStats run-counting,
thread safety — is documented in engine/README.md.
"""

from typing import Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant as quant_lib
from repro.core.disk import DiskClusterStore, IOStats
from repro.index.sharded import ShardedDiskStore, ShardedPQStore  # noqa: F401


@runtime_checkable
class ClusterStore(Protocol):
    is_host: bool

    def fetch_blocks(self, cluster_ids):
        """-> (vecs, docs, valid); see module docstring."""
        ...


@jax.tree_util.register_pytree_node_class
class InMemoryStore:
    """Device-resident embeddings; fetch is a jit-friendly gather. A
    pytree, so serving programs take its arrays as jit arguments."""

    is_host = False
    is_coded = False

    def __init__(self, embeddings, cluster_docs):
        self.embeddings = embeddings          # (D, dim)
        self.cluster_docs = cluster_docs      # (N, cap)

    def tree_flatten(self):
        return (self.embeddings, self.cluster_docs), None

    @classmethod
    def tree_unflatten(cls, _, children):
        return cls(*children)

    def fetch_blocks(self, cluster_ids):
        docs = jnp.take(self.cluster_docs, cluster_ids, axis=0)
        valid = docs >= 0
        vecs = jnp.take(self.embeddings, jnp.where(valid, docs, 0), axis=0)
        vecs = jnp.where(valid[..., None], vecs, 0.0)
        return vecs, docs, valid

    def score_docs(self, q_dense, doc_ids):
        """(B, dim) x (B, K) -> (B, K) exact dot scores."""
        vecs = jnp.take(self.embeddings, doc_ids, axis=0)
        return jnp.einsum("bd,bkd->bk", q_dense, vecs)


@jax.tree_util.register_pytree_node_class
class PQStore:
    """Product-quantized embeddings; scoring via ADC lookup tables,
    block fetch via codebook reconstruction (identical scores up to fp).

    Code-backed (`is_coded`): `fetch_code_blocks` gathers raw per-cluster
    code blocks so the jit'd pipeline can score codes in-kernel, never
    reconstructing float embeddings on the scoring path. A pytree, like
    InMemoryStore."""

    is_host = False
    is_coded = True

    def __init__(self, pq, cluster_docs):
        self.pq = pq
        self.cluster_docs = cluster_docs

    def tree_flatten(self):
        return (self.pq, self.cluster_docs), None

    @classmethod
    def tree_unflatten(cls, _, children):
        return cls(*children)

    @property
    def codebooks(self):
        return self.pq.codebooks

    @property
    def rotation(self):
        return self.pq.rotation

    @property
    def nsub(self):
        return self.pq.nsub

    def fetch_code_blocks(self, cluster_ids):
        """-> (codes, docs, valid): (..., cap, nsub) code blocks, padded
        slots coded as doc 0 but masked by valid. Jit-traceable."""
        docs = jnp.take(self.cluster_docs, cluster_ids, axis=0)
        valid = docs >= 0
        codes = jnp.take(self.pq.codes, jnp.where(valid, docs, 0), axis=0)
        return codes, docs, valid

    def fetch_blocks(self, cluster_ids):
        docs = jnp.take(self.cluster_docs, cluster_ids, axis=0)
        valid = docs >= 0
        flat = jnp.where(valid, docs, 0).reshape(-1)
        vecs = quant_lib.reconstruct(self.pq, flat)
        vecs = vecs.reshape(docs.shape + (vecs.shape[-1],))
        vecs = jnp.where(valid[..., None], vecs, 0.0)
        return vecs, docs, valid

    def score_docs(self, q_dense, doc_ids):
        lut = quant_lib.adc_tables(self.pq, q_dense)
        return quant_lib.adc_score(self.pq, lut, doc_ids)


class DiskStore:
    """On-disk cluster blocks (wraps core.disk.DiskClusterStore).

    fetch_blocks takes a 1-D host sequence of cluster ids and reads one
    block per id, counting I/O ops/bytes into `stats` (thread-safe, so a
    background prefetcher can share the store with the serving thread).
    """

    is_host = True
    is_coded = False

    def __init__(self, block_store: DiskClusterStore, cluster_docs,
                 stats: IOStats = None):
        import threading
        self.blocks = block_store
        self.cluster_docs = cluster_docs
        self.cluster_docs_np = np.asarray(cluster_docs)
        self.stats = stats if stats is not None else IOStats()
        self._lock = threading.Lock()

    @classmethod
    def create(cls, path, embeddings, cluster_docs, **kw):
        return cls(DiskClusterStore(path, embeddings, cluster_docs),
                   cluster_docs, **kw)

    @property
    def block_bytes(self):
        return self.blocks.block_bytes

    @property
    def cap(self):
        return self.blocks.cap

    @property
    def dim(self):
        return self.blocks.dim

    def fetch_blocks(self, cluster_ids):
        cluster_ids = np.asarray(cluster_ids, np.int64).reshape(-1)
        docs = self.cluster_docs_np[cluster_ids]
        if len(cluster_ids) == 0:
            return (np.zeros((0, self.blocks.cap, self.blocks.dim), np.float32),
                    docs, docs >= 0)
        local = IOStats()
        vecs = np.asarray(self.blocks.fetch_clusters(cluster_ids, local))
        with self._lock:
            self.stats.add(local.n_ops, local.bytes, local.wall_ms)
        return vecs, docs, docs >= 0


def place_codebooks(source):
    """The (codebooks, rotation) of a code-backed store or a PQ
    IndexReader, placed on the device once: the ADC LUT-build programs of
    the engine and the router take them as jit arguments."""
    return jax.device_put((source.codebooks, source.rotation))


def store_for_index(index):
    """Default device store for a CluSDIndex: PQ if quantized, else dense."""
    if getattr(index, "quantizer", None) is not None:
        return PQStore(index.quantizer, index.cluster_docs)
    return InMemoryStore(index.embeddings, index.cluster_docs)
