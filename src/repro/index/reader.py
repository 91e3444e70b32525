"""IndexReader: open a built index directory and serve from it.

Opening is cheap: the manifest is validated (format version always; file
sizes by default; sha256 with verify="full"), per-index arrays are
np.load-ed with mmap_mode="r", and cluster blocks stay in their per-shard
files behind a sharded store. The document embedding matrix is never
materialized — `load_index()` returns a CluSDIndex with `embeddings=None`,
and Step-3 dense scoring reads only selected cluster blocks.

Both on-disk formats are served through the same API:

  format_version 1 — float block shards -> ShardedDiskStore
  format_version 2 — PQ code shards -> ShardedPQStore (codes decoded
    through the manifest's codebooks at fetch time; asymmetric-distance
    scoring), CSR postings re-padded at load (lossless)

    reader = IndexReader.open("/path/to/index", verify="full")
    cfg, index = reader.load_index()
    engine = reader.engine(max_batch=32)        # RetrievalEngine, sharded I/O
    ids, scores = engine.retrieve(q_dense, q_terms, q_weights)
"""

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import restore_checkpoint
from repro.configs.base import CluSDConfig
from repro.core.clusd import CluSDIndex
from repro.core.disk import IOStats
from repro.core.lstm import lstm_init
from repro.core.sparse import SparseIndex
from repro.index import format as fmt
from repro.index.sharded import ShardedDiskStore, ShardedPQStore


class IndexReader:
    def __init__(self, index_dir, manifest):
        self.index_dir = os.path.abspath(index_dir)
        self.manifest = manifest
        self.geometry = manifest["geometry"]

    @classmethod
    def open(cls, index_dir, verify="size",
             supported=fmt.SUPPORTED_VERSIONS):
        """Validate and open. verify: "none" | "size" (default) | "full".
        `supported` narrows the format versions this reader accepts — a
        PR-2-era (v1-only) reader is `supported=(1,)`."""
        manifest = fmt.load_manifest(index_dir, supported=supported)
        fmt.verify_files(index_dir, manifest, level=verify)
        return cls(index_dir, manifest)

    @property
    def format_version(self):
        return self.manifest["format_version"]

    @property
    def is_pq(self):
        return self.format_version == fmt.FORMAT_VERSION_PQ

    @property
    def generation(self):
        """Index generation: 0 for a fresh build, +1 per committed delta
        (repro.index.update). Missing key (pre-generation manifests) = 0."""
        return fmt.manifest_generation(self.manifest)

    def refresh(self, verify="none"):
        """Re-read manifest.json and adopt a newer generation if one was
        committed since open. Returns True when the generation changed
        (callers should then rebuild stores/engines — see
        `RetrievalEngine.reload_index`), False when nothing moved.
        Delta commits replace the manifest atomically, so this never
        observes a torn state."""
        manifest = fmt.load_manifest(self.index_dir)
        if fmt.manifest_generation(manifest) == self.generation:
            return False
        fmt.verify_files(self.index_dir, manifest, level=verify)
        self.manifest = manifest
        self.geometry = manifest["geometry"]
        return True

    # -- raw artifacts ------------------------------------------------------

    def array(self, name):
        """Mmap a per-index array by logical name (no copy)."""
        rel = self.manifest["arrays"][name]
        return np.load(os.path.join(self.index_dir, rel), mmap_mode="r")

    def tombstones(self):
        """(n_clusters, cap) uint8 delete bitmap, or None when this
        generation has no deletes (fresh builds, compacted indexes)."""
        if "tombstones" not in self.manifest["arrays"]:
            return None
        return np.asarray(self.array("tombstones"))

    def masked_cluster_docs(self):
        """cluster_docs with tombstoned slots already masked to -1 — the
        doc-id table every serving path should see (deleted docs score as
        invalid without any shard bytes having been rewritten)."""
        cd = np.asarray(self.array("cluster_docs"))
        tomb = self.tombstones()
        if tomb is None:
            return cd
        return np.where(tomb > 0, -1, cd)

    def config(self) -> CluSDConfig:
        d = dict(self.manifest["config"])
        d["bins"] = tuple(d["bins"])
        return CluSDConfig(**d)

    def selector_meta(self):
        """Selector-publish metadata (repro.train.publish_selector): the
        calibrated operating point {theta, budget}, the full calibration
        table, label config, and training stats — or None for indexes
        whose selector came from the offline build (no publish yet)."""
        return self.manifest.get("selector")

    def lstm_params(self):
        meta = self.manifest["lstm"]
        if meta is None:
            return None
        target = lstm_init(jax.random.key(0), meta["feat_dim"],
                           meta["hidden"])
        params, _ = restore_checkpoint(
            os.path.join(self.index_dir, meta["dir"]), meta["step"], target)
        return params

    def _pq_array(self, name):
        rel = self.manifest["pq"]["arrays"].get(name)
        if rel is None:
            return None
        return np.load(os.path.join(self.index_dir, rel))

    @property
    def codebooks(self):
        """PQ codebooks (nsub, 256, dsub), or None without PQ artifacts."""
        return self._pq_array("codebooks") if self.manifest["pq"] else None

    @property
    def rotation(self):
        """The OPQ rotation (dim, dim), or None."""
        return self._pq_array("rotation") if self.manifest["pq"] else None

    def _doc_codes(self):
        """Rebuild per-doc (D, nsub) codes from the v2 code shards (cheap:
        nsub bytes per doc) — lets device-side ADC (PQStore) serve a v2
        index for parity checks and small corpora."""
        g = self.geometry
        codes = np.zeros((g["n_docs"], g["nsub"]), np.uint8)
        cd = self.masked_cluster_docs()   # a replaced doc's stale slot is
        for s in self.manifest["block_shards"]:   # tombstoned — skip it
            lo, hi = s["cluster_lo"], s["cluster_hi"]
            mm = np.memmap(os.path.join(self.index_dir, s["file"]),
                           dtype=np.uint8, mode="r",
                           shape=(hi - lo, g["cap"], g["nsub"]))
            local_cd = cd[lo:hi]
            mask = local_cd >= 0
            codes[local_cd[mask]] = mm[mask]
        return codes

    def quantizer(self):
        meta = self.manifest["pq"]
        if meta is None:
            return None
        from repro.core.quant import PQ
        rot = self._pq_array("rotation")
        if self.is_pq:
            return PQ(codebooks=jnp.asarray(self._pq_array("codebooks")),
                      codes=jnp.asarray(self._doc_codes().astype(np.int32)),
                      rotation=None if rot is None else jnp.asarray(rot),
                      nsub=meta["nsub"])
        return PQ(codebooks=jnp.asarray(self._pq_array("codebooks")),
                  codes=jnp.asarray(self._pq_array("codes")),
                  rotation=None if rot is None else jnp.asarray(rot),
                  nsub=meta["nsub"])

    # -- engine-level objects ----------------------------------------------

    def _sparse_index(self):
        if not self.is_pq:
            return SparseIndex(
                postings_docs=jnp.asarray(self.array("sparse_postings_docs")),
                postings_weights=jnp.asarray(
                    self.array("sparse_postings_weights")),
                n_docs=self.geometry["n_docs"])
        # v2: re-pad the CSR postings (lossless — sparse scoring is a
        # scatter-add over valid entries; pad width never changes scores)
        from repro.index.builder import postings_from_csr
        pd, pw = postings_from_csr(self.array("sparse_postings_data"),
                                   self.array("sparse_postings_wdata"),
                                   self.array("sparse_postings_indptr"))
        return SparseIndex(postings_docs=jnp.asarray(pd),
                           postings_weights=jnp.asarray(pw),
                           n_docs=self.geometry["n_docs"])

    def load_index(self, load_quantizer=None):
        """(cfg, CluSDIndex) with embeddings=None; small arrays go to device,
        blocks stay on disk (serve via `open_store()` / `engine()`).

        load_quantizer: by default PQ artifacts load for v1 (cheap — they
        sit in pq/*.npy) but NOT for v2, where rebuilding the per-doc code
        view would read every code shard at open time; v2 serving decodes
        straight from the shards (`open_store()`), so cold open stays
        manifest + mmap only. Pass True to force (device-side ADC over a
        v2 index), or call `reader.quantizer()` directly."""
        if load_quantizer is None:
            load_quantizer = not self.is_pq
        cfg = self.config()
        index = CluSDIndex(
            centroids=jnp.asarray(self.array("centroids")),
            cluster_docs=jnp.asarray(self.masked_cluster_docs()),
            doc_cluster=jnp.asarray(self.array("doc_cluster")),
            neighbor_ids=jnp.asarray(self.array("neighbor_ids")),
            neighbor_sims=jnp.asarray(self.array("neighbor_sims")),
            embeddings=None, sparse_index=self._sparse_index(),
            lstm_params=self.lstm_params(),
            quantizer=self.quantizer() if load_quantizer else None,
            bin_ids=jnp.asarray(self.array("bin_ids")))
        return cfg, index

    def n_block_shards(self):
        return len(self.manifest["block_shards"])

    def open_store(self, cluster_docs=None, stats: IOStats = None,
                   shards=None):
        """Sharded store over the block shard files (mmap, read-only):
        ShardedDiskStore for v1 float blocks, ShardedPQStore for v2 code
        shards (decode-on-fetch ADC). The generation's tombstone bitmap is
        handed to the store, which masks deleted slots at fetch time.

        `shards`: optional iterable of shard indices (into the manifest's
        block_shards list) to open a SUBSET store over — the multi-host
        serving tier gives each host a store over only the shards it
        owns. Fetching a cluster outside the subset raises; cluster_docs
        and tombstones stay full-size (they are global tables)."""
        g = self.geometry
        all_shards = self.manifest["block_shards"]
        if shards is None:
            shards = all_shards
        else:
            idx = sorted(set(int(s) for s in shards))
            if not idx or idx[0] < 0 or idx[-1] >= len(all_shards):
                raise ValueError(f"shard subset {idx} out of range for "
                                 f"{len(all_shards)} block shards")
            shards = [all_shards[i] for i in idx]
        paths = [os.path.join(self.index_dir, s["file"]) for s in shards]
        ranges = [(s["cluster_lo"], s["cluster_hi"]) for s in shards]
        tomb = self.tombstones()
        if cluster_docs is None:
            cluster_docs = self.array("cluster_docs")
        if self.is_pq:
            return ShardedPQStore(
                paths, ranges, g["cap"], self.codebooks,
                cluster_docs, rotation=self.rotation,
                out_dtype=np.dtype(g["block_dtype"]), tombstones=tomb,
                stats=stats)
        return ShardedDiskStore(
            paths, ranges, g["cap"], g["dim"], cluster_docs,
            dtype=fmt.resolve_block_dtype(g["block_dtype"]),
            block_scale=g.get("block_scale"), tombstones=tomb, stats=stats)

    def engine(self, cfg=None, index=None, **engine_kw):
        """RetrievalEngine serving this index through the sharded store.
        The engine keeps a handle on this reader, so
        `engine.reload_index()` hot-swaps to a newer committed generation
        (repro.index.update) with no restart."""
        from repro.engine.server import RetrievalEngine
        if index is None:
            loaded_cfg, index = self.load_index()
            cfg = cfg or loaded_cfg
        cfg = cfg if cfg is not None else self.config()
        store = self.open_store(cluster_docs=index.cluster_docs)
        return RetrievalEngine(cfg, index, store=store, reader=self,
                               **engine_kw)
