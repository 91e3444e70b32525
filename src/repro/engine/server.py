"""RetrievalEngine: the serving front-end over the unified pipeline.

Serving optimizations on top of engine/pipeline.py:

  * bucketed batching — incoming query batches are padded to power-of-two
    sizes (capped at `max_batch`), so `jax.jit` compiles once per bucket
    instead of once per ragged tail size. Oversize batches are chunked.
  * LRU block cache — for host (disk) stores, fetched cluster blocks land
    in a byte-budgeted BlockCache keyed by cluster id; hot clusters are
    served from memory. The budget is sized in float32-block equivalents
    (`cache_capacity * cap * dim * 4` bytes), so a float store caches
    exactly `cache_capacity` blocks while a code-backed store fits
    ~4*dim/nsub times more clusters in the same budget.
  * async prefetch — a background thread pulls Stage-I candidate cluster
    blocks from disk into the cache while the Stage-II LSTM selection is
    still running, so by the time the selection lands, most selected
    blocks are already cache hits.
  * fused tail — for host stores the whole score -> fuse -> top-k tail
    runs as ONE jitted pass per (batch bucket, unique-block bucket)
    (pipeline.build_fused_scorer) instead of eager per-stage dispatch.
  * ADC serving (`use_adc`, auto-on for code-backed stores): raw PQ codes
    flow disk -> cache -> device and are scored against per-query ADC
    lookup tables (repro.kernels.adc) inside the fused pass — the host
    never decodes a float block; the LUT program is dispatched right
    after Stage I and runs behind the Stage-II selection, with no sync of
    its own. stats() reports the fused pass's host wall time as `adc_ms`
    (and `decode_ms` stays 0 on this path).

Plus zero-downtime index swaps: `reload_index()` hops a serving engine to
a newer committed index generation (repro.index.update) between batches —
the store/arrays are rebuilt from the reader, compiled buckets and the
block cache are invalidated (geometry may have changed), and the prefetch
worker is quiesced across the swap so no stale block can repopulate the
fresh cache. In-flight batches finish on the old generation; no request
ever fails. When only the Stage-II selector moved (repro.train publishes
weights + calibrated thresholds as a generation that rewrites zero corpus
bytes), `reload_selector()` swaps just the LSTM params and theta/budget —
Stage-I compilations, the block cache, and the prefetch worker survive.

Usage:
    engine = RetrievalEngine(cfg, index)                  # in-memory / PQ
    engine = RetrievalEngine(cfg, index, store=DiskStore(...))
    ids, scores = engine.retrieve(q_dense, q_terms, q_weights)
    engine.stats()   # latency percentiles, cache hit rate, I/O counters
    engine.reload_index()   # adopt a newer generation (reader-backed)
    engine.close()
"""

import collections
import dataclasses
import queue
import threading
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine import pipeline as pipe_lib
from repro.engine import stores as stores_lib
from repro.engine.cache import BlockCache
from repro.obs import NOOP_TRACE, MetricsRegistry, Tracer


def bucket_size(n, max_batch):
    """Smallest power of two >= n, capped at max_batch."""
    if n < 1:
        raise ValueError(f"batch size must be >= 1, got {n}")
    b = 1
    while b < n:
        b *= 2
    return min(b, max_batch)


def _pad_rows(x, n_pad):
    """Pad axis 0 by repeating the last row (keeps ids/terms in range)."""
    if n_pad == 0:
        return x
    return np.concatenate([x, np.repeat(np.asarray(x)[-1:], n_pad, axis=0)])


def _h2d_nbytes(*arrays):
    """Bytes `jnp.asarray` copies host->device for `arrays`: those not
    already device arrays."""
    return sum(int(a.nbytes) for a in arrays if not isinstance(a, jax.Array))


def use_profiler_annotations(tracer):
    """Put the tracer's sampled spans on the JAX profiler's timeline
    (repro.obs.trace), unless the caller set its own hook."""
    if tracer.annotate is None:
        tracer.annotate = jax.profiler.TraceAnnotation


def build_explain_records(cfg, *, qid_base, generation, n, cand, probs,
                          sel_ids, sel_mask, final_ids, sparse_ids,
                          doc_cluster):
    """Explain records for one served batch (schema in
    docs/OBSERVABILITY.md). Shared by RetrievalEngine and ShardRouter —
    the router appends its own `host_contrib` field afterwards.

    All array args are batch-major; only the first `n` rows (real
    queries, not bucket padding) produce records. `doc_cluster` maps doc
    id -> cluster id and decides dense-side membership for the fusion
    contribution split."""
    cand = np.asarray(cand)[:n]
    probs = np.asarray(probs)[:n]
    sel_np = np.asarray(sel_ids)[:n]
    mask_np = np.asarray(sel_mask)[:n].astype(bool)
    final = np.asarray(final_ids)[:n]
    sid = np.asarray(sparse_ids)[:n]
    dc = np.asarray(doc_cluster)
    n_seed = int(cfg.n_candidates)
    theta = float(cfg.theta)
    records = []
    for i in range(n):
        p = probs[i]
        selected = [int(x) for x in sel_np[i][mask_np[i]]]
        sel_set = set(selected)
        over = int((p >= theta).sum())
        sparse_set = {int(d) for d in sid[i] if int(d) >= 0}
        contrib = {"sparse_only": 0, "dense_only": 0, "both": 0}
        for d in (int(x) for x in final[i] if int(x) >= 0):
            in_sparse = d in sparse_set
            in_dense = d < len(dc) and int(dc[d]) in sel_set
            if in_sparse and in_dense:
                contrib["both"] += 1
            elif in_sparse:
                contrib["sparse_only"] += 1
            elif in_dense:
                contrib["dense_only"] += 1
        records.append({
            "qid": int(qid_base + i),
            "generation": None if generation is None else int(generation),
            "theta": round(theta, 6),
            "budget": int(cfg.max_selected),
            "fusion": cfg.fusion,
            "expand_depth": int(cfg.expand_depth),
            "n_seed": n_seed,
            "cand": [int(x) for x in cand[i]],
            "provenance": ["seed" if j < n_seed else "expand"
                           for j in range(cand.shape[1])],
            "probs": [round(float(x), 4) for x in p],
            "selected": selected,
            "n_over_theta": over,
            "skipped_over_theta": max(0, over - len(selected)),
            "fusion_contrib": contrib,
        })
    return records


@dataclasses.dataclass
class BatchRecord:
    size: int          # real queries in the batch (before padding)
    bucket: int        # padded bucket it ran in
    compiled: bool     # this batch triggered a jit compile for its bucket
    ms: float


class ServeStats:
    """Serving counters, registry-backed and bounded.

    Cumulative counts (queries, batches, compile batches, prefetch,
    reloads, steady time) live as counters in a MetricsRegistry — exact
    over the engine's whole lifetime. Per-batch records land in a ring
    (`deque(maxlen=window)`, default 8192) plus the registry's
    `serve.batch_ms` histogram, so a long soak holds memory constant:
    `latency_percentiles()` / `per_query_ms()` cover the most recent
    `window` steady batches (identical to the old unbounded list until
    the window overflows), while `steady_qps()` stays lifetime-exact
    from the cumulative counters."""

    WINDOW = 8192

    def __init__(self, registry=None, window=WINDOW):
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        self.window = int(window)
        reg = self.registry
        self._queries = reg.counter("serve.queries")
        self._batches = reg.counter("serve.batches")
        self._compile_batches = reg.counter("serve.compile_batches")
        self._steady_queries = reg.counter("serve.steady_queries")
        self._steady_ms = reg.counter("serve.steady_ms")
        self._batch_ms_hist = reg.histogram("serve.batch_ms",
                                            ring=self.window)
        self._prefetch_enqueued = reg.counter("serve.prefetch_enqueued")
        self._prefetch_errors = reg.counter("serve.prefetch_errors")
        self._reloads = reg.counter("serve.reloads")
        self._selector_reloads = reg.counter("serve.selector_reloads")
        self.batches = collections.deque(maxlen=self.window)
        self._compiled_bucket_set = set()

    # cumulative counts read back from the registry
    @property
    def n_queries(self):
        return int(self._queries.value)

    @property
    def n_batches(self):
        return int(self._batches.value)

    @property
    def n_compile_batches(self):
        return int(self._compile_batches.value)

    @property
    def prefetch_enqueued(self):
        return int(self._prefetch_enqueued.value)

    @property
    def prefetch_errors(self):
        return int(self._prefetch_errors.value)

    @property
    def reloads(self):
        return int(self._reloads.value)

    @property
    def selector_reloads(self):
        return int(self._selector_reloads.value)

    def record(self, size, bucket, compiled, ms):
        self._queries.inc(size)
        self._batches.inc()
        if compiled:
            self._compile_batches.inc()
            self._compiled_bucket_set.add(bucket)
        else:
            self._steady_queries.inc(size)
            self._steady_ms.inc(ms)
            self._batch_ms_hist.observe(ms)
        self.batches.append(BatchRecord(size, bucket, compiled, ms))

    def record_prefetch(self, n):
        self._prefetch_enqueued.inc(n)

    def record_prefetch_error(self):
        self._prefetch_errors.inc()

    def record_reload(self):
        self._reloads.inc()

    def record_selector_reload(self):
        self._selector_reloads.inc()

    @property
    def batch_ms(self):
        return [b.ms for b in self.batches]

    @property
    def compiled_buckets(self):
        return sorted(self._compiled_bucket_set)

    def _steady(self):
        return [b for b in self.batches if not b.compiled]

    def per_query_ms(self):
        """Per-query latencies, excluding jit-compile batches (recent
        `window` batches)."""
        return [b.ms / b.size for b in self._steady()]

    def steady_qps(self):
        t = float(self._steady_ms.value)
        return float(self._steady_queries.value) / (t / 1e3) if t else 0.0

    def latency_percentiles(self):
        """Steady-state (compile batches excluded) batch-latency summary."""
        steady = [b.ms for b in self._steady()]
        if not steady:
            return {}
        lat = np.asarray(steady)
        return {"p50_ms": round(float(np.percentile(lat, 50)), 3),
                "p99_ms": round(float(np.percentile(lat, 99)), 3),
                "mean_ms": round(float(lat.mean()), 3)}

    def reset(self):
        """Zero every counter and drop the batch window (the registry
        metrics this instance registered are reset in place)."""
        for c in (self._queries, self._batches, self._compile_batches,
                  self._steady_queries, self._steady_ms,
                  self._prefetch_enqueued, self._prefetch_errors,
                  self._reloads, self._selector_reloads):
            c.reset()
        self._batch_ms_hist.reset()
        self.batches.clear()
        self._compiled_bucket_set.clear()


class RetrievalEngine:
    """Unified serving layer over a ClusterStore backend."""

    _PF_CHUNK = 8            # blocks per prefetch fetch (lock granularity)

    def __init__(self, cfg, index, store=None, *, max_batch=256,
                 cache_capacity=512, prefetch=True, prefetch_depth=None,
                 k=None, reader=None, use_adc=None, metrics=None,
                 tracer=None, trace_sample_rate=None, fusion=None,
                 explain=None):
        # per-engine fusion override ("interp" | "rrf"): wins over the
        # manifest config and is re-applied across index/selector reloads
        from repro.core.fusion import FUSION_METHODS
        if fusion is not None and fusion not in FUSION_METHODS:
            raise ValueError(f"fusion must be one of {FUSION_METHODS}, "
                             f"got {fusion!r}")
        self._fusion_override = fusion
        cfg = self._apply_cfg_overrides(cfg)
        self.cfg = cfg
        # index arrays (and a device store's) go to the device ONCE; every
        # compiled stage takes them as jit arguments (engine/pipeline.py)
        self.index = jax.device_put(index)
        self.store = self._place_store(
            store if store is not None
            else stores_lib.store_for_index(self.index))
        self.is_host = bool(getattr(self.store, "is_host", False))
        self.max_batch = max(1, max_batch)
        self.k = k or cfg.k_final
        self.reader = reader            # IndexReader backing reload_index()
        # ADC serving: score raw PQ codes against per-query LUTs on the
        # host path. None = auto (on exactly when the store is code-backed);
        # True demands a code-backed store; False forces decode-then-score.
        self._explicit_use_adc = use_adc
        self.use_adc = self._resolve_use_adc(self.store)
        self._codebooks = stores_lib.place_codebooks(self.store) \
            if self.use_adc else None
        # observability (repro.obs): the registry backs stats()/ServeStats;
        # the tracer emits per-batch stage spans when trace_sample_rate > 0
        # (0 by default: the disabled path hands out a shared no-op trace).
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        if tracer is None:
            tracer = Tracer(sample_rate=trace_sample_rate or 0.0)
        elif trace_sample_rate is not None:
            tracer.sample_rate = float(trace_sample_rate)
        use_profiler_annotations(tracer)
        self.tracer = tracer
        # sampled per-query explain telemetry (repro.obs.ExplainLogger);
        # None (the default) costs a single attribute check per batch.
        # Covers the host serving path — the fully-fused device path has
        # no per-stage host visibility to explain.
        self.explain = explain
        self._adc_ms = self.metrics.counter("serve.adc_ms")
        # host->device bytes of every batch; the selection's size over the
        # queries it was counted on (every host-path batch, sampled
        # device-path batches: reading it there costs a device->host copy)
        self._h2d_bytes = self.metrics.counter("serve.h2d_bytes")
        self._clusters_selected = self.metrics.counter(
            "serve.clusters_selected")
        self._selected_queries = self.metrics.counter(
            "serve.selected_queries")
        self._prefetch_enabled = bool(prefetch)
        self._swap_lock = threading.RLock()   # serving vs reload_index
        self._pf_drop = False           # quiesce flag across index swaps
        self.serve_stats = ServeStats(self.metrics)
        self._cache_capacity = cache_capacity
        self.cache = self._make_cache(self.store) \
            if (self.is_host and cache_capacity) else None
        # prefetch candidates a bit past the selection budget: Stage-II
        # mostly keeps high-ranked Stage-I candidates, so this covers the
        # selection without reading the whole candidate list. An explicit
        # depth is pinned; the default tracks cfg.max_selected across
        # reloads (a calibrated publish may raise the budget).
        self._explicit_prefetch_depth = prefetch_depth
        self.prefetch_depth = prefetch_depth if prefetch_depth is not None \
            else self._default_prefetch_depth(cfg)
        self._fns: Dict[Any, Any] = {}          # (kind, bucket) -> jitted fn
        self._pf_q = None
        self._pf_thread = None
        self._start_prefetch()

    # cumulative fused-ADC pass wall time (steady-state only);
    # registry-backed so stats(), metrics exports, and reset_stats() agree
    @property
    def adc_ms(self):
        return float(self._adc_ms.value)

    # -- lifecycle ----------------------------------------------------------

    def _resolve_use_adc(self, store):
        coded = bool(getattr(store, "is_coded", False))
        if self._explicit_use_adc is None:
            return self.is_host and coded
        if self._explicit_use_adc and not coded:
            raise ValueError("use_adc=True needs a code-backed store "
                             "(is_coded); this store serves float blocks")
        return bool(self._explicit_use_adc) and self.is_host

    @staticmethod
    def _place_store(store):
        """Device stores are pytrees of arrays: place them once. Host
        stores do their own block I/O and stay as they are."""
        if getattr(store, "is_host", False):
            return store
        return jax.device_put(store)

    def _make_cache(self, store):
        """Byte-budgeted cache sized in float32-block equivalents when the
        store's geometry is known (identical behavior to the old
        entry-count bound for float stores; ~4*dim/nsub more clusters for
        code-backed stores), else the legacy entry-count bound."""
        cap = getattr(store, "cap", None)
        dim = getattr(store, "dim", None)
        if cap and dim:
            return BlockCache(
                capacity_bytes=int(self._cache_capacity) * int(cap)
                * int(dim) * 4)
        return BlockCache(self._cache_capacity)

    def _apply_cfg_overrides(self, cfg):
        if self._fusion_override is not None \
                and cfg.fusion != self._fusion_override:
            cfg = dataclasses.replace(cfg, fusion=self._fusion_override)
        return cfg

    @staticmethod
    def _default_prefetch_depth(cfg):
        # expansion widens the candidate list; the prefetch window still
        # tracks the selection budget, capped at the EXPANDED width
        return min(cfg.n_candidates_total,
                   cfg.max_selected + cfg.max_selected // 2)

    def _refresh_prefetch_depth(self, cfg):
        if self._explicit_prefetch_depth is None:
            self.prefetch_depth = self._default_prefetch_depth(cfg)

    def _start_prefetch(self):
        if self._prefetch_enabled and self.is_host and self.cache is not None:
            self._pf_q = queue.Queue(maxsize=64)
            self._pf_thread = threading.Thread(target=self._prefetch_worker,
                                               daemon=True)
            self._pf_thread.start()

    def _stop_prefetch(self):
        if self._pf_q is not None:
            self._pf_q.put(None)
            # unbounded join: the queue is bounded and fetches are chunked,
            # so drain is finite — and stats() after close() must be final
            self._pf_thread.join()
            self._pf_q = None
            self._pf_thread = None

    def close(self):
        self._stop_prefetch()

    def reload_index(self, reader=None, *, verify="none"):
        """Hot-swap to the index's current committed generation with no
        downtime: re-reads the manifest (`IndexReader.refresh`), rebuilds
        the arrays/store, and atomically replaces them between batches —
        compiled buckets and the block cache are invalidated (geometry and
        doc membership may have changed), and the prefetch worker is
        stopped across the swap so an in-flight prefetch of the OLD
        generation can never repopulate the fresh cache.

        `reader` defaults to the one the engine was constructed with
        (`IndexReader.engine()` wires it). Returns the generation now
        being served. Safe to call from a control thread while another
        thread serves: in-flight batches finish on the old generation.

        Stats semantics: every cumulative counter in stats() — I/O
        ops/bytes, decode_ms, cache hit/miss/eviction/clear, adc/LUT
        times — is ENGINE-lifetime. The swap carries the old store's
        counters onto the new store, so a reload never zeroes history;
        `reset_stats()` is the only reset."""
        reader = reader if reader is not None else self.reader
        if reader is None:
            raise ValueError("reload_index needs an IndexReader (construct "
                             "the engine via IndexReader.engine, or pass "
                             "reader=)")
        tr = self.tracer.trace("reload_index")
        with tr.span("reload"):
            reader.refresh(verify=verify)
            cfg, index = reader.load_index()
            cfg = self._apply_cfg_overrides(cfg)
            index = jax.device_put(index)
            store = reader.open_store(cluster_docs=index.cluster_docs)
            # quiesce prefetch: drop queued candidate ids and wait out any
            # fetch against the old store before the cache is cleared
            restart = self._pf_thread is not None
            self._pf_drop = True
            if restart:
                self._stop_prefetch()
            with self._swap_lock:
                old_store = self.store
                self.cfg, self.index, self.store = cfg, index, store
                self.reader = reader
                self.use_adc = self._resolve_use_adc(store)
                self._codebooks = stores_lib.place_codebooks(store) \
                    if self.use_adc else None
                self._refresh_prefetch_depth(cfg)
                self._fns.clear()           # bucket shapes/geometry changed
                self._carry_store_counters(old_store, store)
                if self.cache is not None:
                    # block ids now name new-gen blocks, and the new
                    # geometry may change the byte budget (cap/dim moved):
                    # replace the cache but carry the lifetime counters —
                    # a swap IS a clear, stats() must not lose history
                    # across generations
                    old = self.cache
                    new = self._make_cache(store)
                    new.hits, new.misses = old.hits, old.misses
                    new.evictions, new.clears = old.evictions, old.clears + 1
                    self.cache = new
                self.serve_stats.record_reload()
            self._pf_drop = False
            if restart:
                self._start_prefetch()
        tr.finish(generation=reader.generation)
        return reader.generation

    @staticmethod
    def _stage1_cfg(cfg):
        """The config slice compiled into Stage-I buckets (candidate
        generation + sparse depth). A selector publish that changes any of
        these must invalidate stage1 fns too."""
        return (cfg.k_sparse, cfg.bins, cfg.n_candidates, cfg.expand_depth,
                cfg.n_candidates_total, cfg.u_bins)

    @staticmethod
    def _carry_store_counters(old_store, new_store):
        """Copy cumulative I/O + host-decode counters from the outgoing
        store onto its replacement, keeping stats() engine-lifetime (the
        cache carries its counters the same way). Before this,
        `decode_ms` and IOStats silently reset on reload_index but
        survived reload_selector — now both paths behave identically."""
        if new_store is old_store:
            return
        old_io = getattr(old_store, "stats", None)
        new_io = getattr(new_store, "stats", None)
        if old_io is not None and new_io is not None \
                and hasattr(old_io, "n_ops") and hasattr(new_io, "add"):
            new_io.add(old_io.n_ops, old_io.bytes, old_io.wall_ms)
        if hasattr(old_store, "decode_ms") and hasattr(new_store,
                                                       "decode_ms"):
            new_store.decode_ms += old_store.decode_ms

    def reload_selector(self, reader=None, *, verify="none"):
        """Hot-swap ONLY the Stage-II selector: adopt a newer committed
        generation's LSTM weights + calibrated theta/budget (published by
        repro.train.publish_selector) without touching the store, the
        block cache, the prefetch worker, or the compiled Stage-I
        buckets. Far cheaper than `reload_index()` — selector publishes
        rewrite zero corpus bytes, so corpus-derived state stays valid.

        If the refreshed manifest shows the corpus itself moved too
        (arrays/block shards differ — e.g. a delta landed between
        publishes), this falls back to a full `reload_index()`. Returns
        the generation now being served."""
        reader = reader if reader is not None else self.reader
        if reader is None:
            raise ValueError("reload_selector needs an IndexReader "
                             "(construct the engine via IndexReader.engine, "
                             "or pass reader=)")
        before = (reader.manifest.get("arrays"),
                  reader.manifest.get("block_shards"))
        reader.refresh(verify=verify)
        after = (reader.manifest.get("arrays"),
                 reader.manifest.get("block_shards"))
        if before != after:
            return self.reload_index(reader, verify="none")
        tr = self.tracer.trace("reload_selector")
        with tr.span("reload"):
            cfg = self._apply_cfg_overrides(reader.config())
            params = jax.device_put(reader.lstm_params())
            with self._swap_lock:
                old_cfg = self.cfg
                self.cfg = cfg
                self.index = dataclasses.replace(self.index,
                                                 lstm_params=params)
                self.reader = reader
                # the calibrated budget may exceed the old one: keep the
                # prefetch window covering the selection
                self._refresh_prefetch_depth(cfg)
                # only selector-dependent compilations are stale: stage2
                # closes over (theta, max_selected); the fused
                # device path and the fused host tails close over the
                # whole (re-read) config. Stage-I buckets, the LUT builder
                # (codebooks only), and the block cache survive — the
                # corpus didn't move. (The new params are an argument.)
                stale = {"stage2", "device", "adc", "dot"}
                if self._stage1_cfg(old_cfg) != self._stage1_cfg(cfg):
                    # a publish may also retune candidate generation
                    # (expansion depth / width): those values are BAKED
                    # into the compiled Stage-I buckets, so keeping them
                    # would serve the old candidate shape forever
                    stale.add("stage1")
                for key in [k for k in self._fns if k[0] in stale]:
                    del self._fns[key]
                self.serve_stats.record_selector_reload()
        tr.finish(generation=reader.generation)
        return reader.generation

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- prefetch -----------------------------------------------------------

    def _cache_fill_fn(self):
        """What a cache miss fetches: raw CODE blocks under ADC serving
        (the cache must hold one consistent record type per generation —
        the fused scorer consumes whatever the prefetcher cached), float
        blocks otherwise."""
        store = self.store
        if self.use_adc:
            return lambda c: np.asarray(
                store.fetch_code_blocks(np.asarray(c))[0])
        return lambda c: np.asarray(store.fetch_blocks(np.asarray(c))[0])

    def _prefetch_worker(self):
        while True:
            cids = self._pf_q.get()
            if cids is None:
                return
            if self._pf_drop:
                continue        # reload in progress: stale candidate ids
            try:
                # record=False: prefetch probes must not skew the serving
                # hit-rate; single-flight inside keeps the serving thread
                # from re-reading blocks this fetch is already pulling.
                # Fetch in small chunks so the serving thread never waits
                # behind the whole candidate set for its selected blocks.
                fill = self._cache_fill_fn()
                for i in range(0, len(cids), self._PF_CHUNK):
                    self.cache.get_or_fetch_many(
                        cids[i:i + self._PF_CHUNK], fill, record=False)
            except Exception:       # prefetch is best-effort; never kill serving
                self.serve_stats.record_prefetch_error()

    def _enqueue_prefetch(self, cand):
        """cand: (B, n_candidates) host array, stage-1 ordered."""
        q = self._pf_q     # snapshot: reload_index() may null the attribute
        if q is None:      # between this check and the put (TOCTOU)
            return
        cids = np.unique(np.asarray(cand)[:, :self.prefetch_depth])
        cids = [int(c) for c in cids if int(c) not in self.cache]
        if not cids:
            return
        try:
            q.put_nowait(cids)
            self.serve_stats.record_prefetch(len(cids))
        except queue.Full:
            pass

    # -- compiled stages ----------------------------------------------------

    def _fn(self, kind, bucket, builder):
        key = (kind, bucket)
        fn = self._fns.get(key)
        if fn is None:
            fn = builder()
            self._fns[key] = fn
            self._built_fn = True     # this batch pays a compile somewhere
        return fn

    def _device_fn(self, bucket):
        return self._fn("device", bucket,
                        lambda: pipe_lib.build_device_fn(self.cfg, k=self.k))

    def _stage1_fn(self, bucket):
        return self._fn("stage1", bucket,
                        lambda: pipe_lib.build_stage1_fn(self.cfg))

    def _stage2_fn(self, bucket):
        return self._fn("stage2", bucket,
                        lambda: pipe_lib.build_stage2_fn(self.cfg))

    def _lut_fn(self, bucket):
        """Per-query ADC LUT build (rotation folded in). Keyed per bucket
        only — survives selector reloads (reads the codebooks alone)."""
        return self._fn("lut", bucket, pipe_lib.build_lut_fn)

    def _fused_fn(self, kind, bucket, ubucket):
        """One compiled score->fuse->top-k tail per (mode, batch bucket,
        unique-block bucket)."""
        def build():
            return pipe_lib.build_fused_scorer(self.cfg, self.index.n_docs,
                                               k=self.k, mode=kind)
        return self._fn(kind, (bucket, ubucket), build)

    # -- serving ------------------------------------------------------------

    def retrieve(self, q_dense, q_terms, q_weights, *, k=None):
        """Serve a query batch of any size. Returns (ids, scores) with the
        caller's batch dimension preserved."""
        if k is not None and k != self.k:
            raise ValueError("per-call k would defeat bucketed compilation; "
                             "construct the engine with the serving k")
        n = int(np.asarray(q_dense).shape[0])
        if n < 1:
            raise ValueError("empty query batch")
        out_ids, out_scores = [], []
        for lo in range(0, n, self.max_batch):
            hi = min(lo + self.max_batch, n)
            ids, scores = self._retrieve_chunk(
                q_dense[lo:hi], q_terms[lo:hi], q_weights[lo:hi])
            out_ids.append(ids)
            out_scores.append(scores)
        if len(out_ids) == 1:
            return out_ids[0], out_scores[0]
        return (jnp.concatenate(out_ids, axis=0),
                jnp.concatenate(out_scores, axis=0))

    def _retrieve_chunk(self, q_dense, q_terms, q_weights):
        n = int(np.asarray(q_dense).shape[0])
        bucket = bucket_size(n, self.max_batch)
        tr = self.tracer.trace("batch", size=n, bucket=bucket)
        # one chunk serves entirely on one index generation: reload_index
        # takes the same lock, so swaps land between chunks, never inside
        with tr.span("lock_wait"):
            self._swap_lock.acquire()
        try:
            self._built_fn = False
            with tr.span("pad"):
                pad = bucket - n
                qd, qt, qw = (_pad_rows(x, pad)
                              for x in (q_dense, q_terms, q_weights))
                self._h2d_bytes.inc(_h2d_nbytes(qd, qt, qw))
                qd, qt, qw = jnp.asarray(qd), jnp.asarray(qt), \
                    jnp.asarray(qw)
            # batch_ms starts AFTER input pad/transfer, matching the
            # pre-obs measurement exactly (the `pad` span still shows it)
            t0 = time.perf_counter()
            if self.is_host:
                ids, scores = self._serve_host(bucket, qd, qt, qw, tr, n=n)
                ids.block_until_ready()
            else:
                with tr.span("device_pipeline"):
                    with tr.span("device_dispatch"):
                        ids, scores, n_sel = self._device_fn(bucket)(
                            self.index, self.store, qd, qt, qw)
                    with tr.region("device_wait"):
                        ids.block_until_ready()
            ms = (time.perf_counter() - t0) * 1e3
            if tr is not NOOP_TRACE and not self.is_host:
                self._count_selected(np.asarray(n_sel)[:n].sum(), n)
            # a batch "compiled" if ANY stage built a new jitted fn for it
            # (stage buckets, but also a first-seen unique-block bucket of
            # the fused tail) — steady-state latency stats exclude those,
            # but traces flag them (`compiled`) instead of dropping them
            tr.finish(compiled=self._built_fn, batch_ms=round(ms, 3))
            self.serve_stats.record(n, bucket, self._built_fn, ms)
            return ids[:n], scores[:n]
        finally:
            self._swap_lock.release()

    def _count_selected(self, n_clusters, n_queries):
        self._clusters_selected.inc(int(n_clusters))
        self._selected_queries.inc(int(n_queries))

    @staticmethod
    def _pow2(n):
        b = 1
        while b < n:
            b *= 2
        return b

    def _serve_host(self, bucket, qd, qt, qw, tr=NOOP_TRACE, n=None):
        n = bucket if n is None else n
        with tr.span("stage1"):
            with tr.span("stage1_dispatch"):
                sid, ss, cand, feats = self._stage1_fn(bucket)(self.index,
                                                               qd, qt, qw)
            with tr.region("stage1_wait"):
                cand_np = np.asarray(cand)      # device sync for Stage I
            # overlap: start pulling candidate blocks while Stage II runs
            # (the enqueue itself is host work, charged to this span)
            with tr.span("prefetch_enqueue"):
                self._enqueue_prefetch(cand_np)
        lut = None
        if self.use_adc:
            # the LUT depends only on the queries: dispatched here, it runs
            # on the device behind Stage II while the prefetcher pulls
            # candidate code blocks (the fused tail's call waits for it)
            with tr.span("lut_build"):
                lut = self._lut_fn(bucket)(*self._codebooks, qd)
        with tr.span("stage2_select"):
            with tr.span("stage2_dispatch"):
                sel_ids, sel_mask, probs = self._stage2_fn(bucket)(
                    self.index, cand, feats)
            with tr.region("stage2_wait"):
                sel_np = np.asarray(sel_ids)    # device sync for Stage II
                mask_np = np.asarray(sel_mask)
        self._count_selected(mask_np[:n].sum(), n)
        with tr.span("fuse"):               # host glue: dedup + positions
            uniq, pos = pipe_lib.dedup_selected(sel_np, mask_np)
        if bool(mask_np.any()):
            with tr.span("cache_fetch", n_blocks=len(uniq)) as sp:
                fetch = pipe_lib.fetch_unique_code_blocks if self.use_adc \
                    else pipe_lib.fetch_unique_blocks
                blocks = fetch(self.store, uniq, self.cache, trace=tr)
                sp.annotate(bytes=int(blocks.nbytes))
        else:       # nothing selected: zero placeholder, no I/O
            blocks = np.zeros(
                (1, self.store.cap,
                 self.store.nsub if self.use_adc else self.store.dim),
                np.uint8 if self.use_adc else np.float32)
        with tr.span("fused_score_topk"):
            # pad the unique-block axis to a power of two so fused-tail
            # compilations stay bounded (pos only ever indexes real rows)
            with tr.span("tail_pad"):
                ub = self._pow2(blocks.shape[0])
                if ub > blocks.shape[0]:
                    blocks = np.concatenate(
                        [blocks,
                         np.zeros((ub - blocks.shape[0],) + blocks.shape[1:],
                                  blocks.dtype)])
            kind = "adc" if self.use_adc else "dot"
            fn = self._fused_fn(kind, bucket, ub)
            t0 = time.perf_counter()
            with tr.span("tail_h2d"):
                self._h2d_bytes.inc(_h2d_nbytes(blocks, pos))
                blocks_d, pos_d = jnp.asarray(blocks), jnp.asarray(pos)
            with tr.span("tail_dispatch"):
                ids, scores = fn(self.index.cluster_docs,
                                 lut if self.use_adc else qd, sid, ss,
                                 sel_ids, sel_mask, blocks_d, pos_d)
            with tr.region("tail_wait"):
                ids.block_until_ready()
            if self.use_adc and not self._built_fn:
                # steady-state only (no compile skew)
                self._adc_ms.inc((time.perf_counter() - t0) * 1e3)
        if self.explain is not None and self.explain.sample():
            for rec in build_explain_records(
                    self.cfg,
                    qid_base=self.serve_stats.n_queries,
                    generation=None if self.reader is None
                    else self.reader.generation,
                    n=n, cand=cand_np, probs=probs, sel_ids=sel_np,
                    sel_mask=mask_np, final_ids=ids, sparse_ids=sid,
                    doc_cluster=self.index.doc_cluster):
                self.explain.emit(rec)
        return ids, scores

    # -- introspection ------------------------------------------------------

    def _sync_gauges(self):
        """Mirror cache/IOStats/store counters into registry gauges so a
        metrics export (`--metrics-out`, Prometheus scrape) carries them
        without callers having to join stats() themselves."""
        reg = self.metrics
        if self.cache is not None:
            for k, v in self.cache.stats().items():
                if isinstance(v, (int, float)) and v is not None:
                    reg.gauge(f"cache.{k}").set(v)
        io = getattr(self.store, "stats", None)
        if io is not None and hasattr(io, "n_ops"):
            reg.gauge("io.n_ops").set(io.n_ops)
            reg.gauge("io.bytes").set(io.bytes)
            reg.gauge("io.wall_ms").set(round(io.wall_ms, 2))
            reg.gauge("io.model_ms").set(round(io.model_ms(), 2))
        decode_ms = getattr(self.store, "decode_ms", None)
        if decode_ms is not None:
            reg.gauge("serve.decode_ms").set(round(decode_ms, 2))
        if self.reader is not None:
            reg.gauge("serve.generation").set(self.reader.generation)

    def stats(self):
        self._sync_gauges()
        ss = self.serve_stats
        out = {"n_queries": ss.n_queries,
               "n_batches": ss.n_batches,
               "n_compile_batches": ss.n_compile_batches,
               "compiled_buckets": ss.compiled_buckets,
               "qps_steady": round(ss.steady_qps(), 1),
               "prefetch_enqueued": ss.prefetch_enqueued,
               "prefetch_errors": ss.prefetch_errors,
               "reloads": ss.reloads,
               "selector_reloads": ss.selector_reloads,
               "fusion": self.cfg.fusion,
               "expand_depth": self.cfg.expand_depth,
               **ss.latency_percentiles()}
        if self.reader is not None:
            out["generation"] = self.reader.generation
        if self.cache is not None:
            out["cache"] = self.cache.stats()
        io = getattr(self.store, "stats", None)
        if io is not None and hasattr(io, "n_ops"):
            out["io"] = {"n_ops": io.n_ops, "bytes": io.bytes,
                         "wall_ms": round(io.wall_ms, 2),
                         "model_ms": round(io.model_ms(), 2)}
        if self.is_host:
            out["use_adc"] = self.use_adc
            decode_ms = getattr(self.store, "decode_ms", None)
            if decode_ms is not None:
                out["decode_ms"] = round(decode_ms, 2)
            if self.use_adc:
                out["adc_ms"] = round(self.adc_ms, 2)
        out["h2d_bytes"] = int(self._h2d_bytes.value)
        out["clusters_selected"] = int(self._clusters_selected.value)
        out["selected_queries"] = int(self._selected_queries.value)
        return out

    def reset_stats(self):
        """Zero every serving statistic, in place, without touching
        compiled functions, the cached blocks themselves, or the tracer's
        retained traces.

        Semantics: stats() counters are ENGINE-lifetime — they survive
        both `reload_index()` (I/O, decode, and cache counters are carried
        onto the new store/cache) and `reload_selector()`, and reset ONLY
        here. After reset: batch/latency windows, compile-batch history,
        prefetch/reload counts, adc/decode times, h2d bytes and the
        selection counts, cache
        hit/miss/eviction/clear counts, and store IOStats all read zero;
        the next stats() reflects serving from this instant."""
        with self._swap_lock:
            self.metrics.reset()
            self.serve_stats.reset()
            if self.cache is not None:
                with self.cache._lock:
                    self.cache.hits = self.cache.misses = 0
                    self.cache.evictions = self.cache.clears = 0
            io = getattr(self.store, "stats", None)
            if io is not None and hasattr(io, "n_ops"):
                io.n_ops, io.bytes, io.wall_ms = 0, 0, 0.0
            if getattr(self.store, "decode_ms", None) is not None:
                self.store.decode_ms = 0.0
