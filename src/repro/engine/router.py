"""Multi-host scatter-gather serving tier: ShardRouter + simulated hosts.

The CluSD pipeline selects a small set of clusters per query, which makes
the dense side naturally partitionable: each host only needs the shard
blocks it owns. This module promotes the repo's dormant distributed
design (core/distributed.py's replicated-selection / sharded-scoring
split) into the engine as a real serving tier that runs on one machine
with N simulated hosts (each a thread-backed `EngineHost` with its own
shard-subset ShardedDiskStore/ShardedPQStore + BlockCache):

  router (ShardRouter)                 host (EngineHost)
  --------------------                 -----------------
  sparse retrieval + Stage I           fetch owned blocks (cache -> disk)
  ADC LUT build (v2)                   score owned selected slots
  Stage-II LSTM selection              partial top-k (score desc, id asc)
  scatter selections to owners   --->
                                 <---  per-host partial lists
  merge partial top-k (exact tie rule)
  fuse with sparse side + final top-k

Shard placement: block shard s (a contiguous cluster range from the index
manifest) is served by replica hosts [(s + r) % n_hosts for r in
range(replication)]. A slot's owner is looked up by searchsorted over the
manifest's shard upper bounds — the same balanced contiguous ownership
rule as core.distributed.shard_ranges.

Merge tie rule: per-host partial results merge under (score desc, doc id
asc) — exactly `train/labels.py`'s streaming `_merge_topk` lexsort rule,
which is also `lax.top_k`'s tie rule over an id-indexed array. Entry
MULTIPLICITY is preserved (no id-dedup): the single-host fused tail
sums duplicate selected slots, so the router must too; double
counting across hosts cannot happen because shard slot-sets partition the
selection and each shard group is accepted from exactly one replica.

Exactness: hosts run the same elementwise score ops as the single-host
fused tail (ADC LUT scoring / block dot), the merged dense candidate
list is the same multiset as the single-host (B, S*cap) slot list, and
fusion runs the same `fuse_topk` sort-merge — so `method="interp"` (the
paper default) is BITWISE identical to the single-host engine. RRF
breaks exact-score ties by list position, so rrf parity is exact except
on exact dense-score ties across distinct docs.

Failover: per-host timeout (futures), retry with exponential backoff
(injectable `sleep` for tests), per-host cooldown health tracking, and
replica failover — a killed host's shard groups are reassigned to the
next live replica; `failed_requests` stays 0 as long as one replica per
shard survives. When EVERY replica of a shard is down the request still
completes in degraded mode: the missing shard's slots are simply absent
from the merged list (exactly equal to serving without that shard), the
batch is counted in `degraded_requests`, and `stats()` raises the
`degraded` flag with the `missing_shards` list while the outage lasts.

Generation hops roll host-by-host: `reload_index()` prepares the new
generation on every host (new shard-subset store + cache alongside the
old), flips the router's own arrays/compiled buckets atomically, then
retires the old generation through each host's serve queue — in-flight
batches finish on the generation they started on, every response is
served from exactly one generation, and zero requests fail.
`reload_selector()` is router-local (selection runs at the router).
"""

import collections
import dataclasses
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine import pipeline as pipe_lib
from repro.engine import stores as stores_lib
from repro.engine.cache import BlockCache
from repro.engine.server import (ServeStats, _pad_rows, bucket_size,
                                 build_explain_records,
                                 use_profiler_annotations)
from repro.core import fusion as fusion_lib
from repro.kernels import adc as adc_ops
from repro.obs import NOOP_TRACE, MetricsRegistry, Tracer

# pads/invalid entries in merged partial top-k lists; sorts after every
# real doc id on score ties (same value as train/labels._PAD_ID)
MERGE_SENTINEL = np.int64(1) << 62


# ---------------------------------------------------------------------------
# partial top-k merge
# ---------------------------------------------------------------------------

def merge_partial_topk(parts, k):
    """Merge per-host partial top-k lists into one (B, k) list under the
    (score desc, doc id asc) tie rule — the exact rule of
    train/labels.py's streaming `_merge_topk` (np.lexsort((i, -s))) and of
    `lax.top_k` over an id-indexed score array.

    parts: list of (ids (B, Ki) int, scores (B, Ki) float) — Ki may vary
    per part. Entries with non-finite scores or sentinel ids are treated
    as padding. Duplicate ids are KEPT at their multiplicity (the fused
    tail sums duplicate slots; at-most-once delivery per shard
    group is the router's job, not the merge's).

    Returns (ids (B, k) int64, scores (B, k) float32); when fewer than k
    real entries exist, the tail is (MERGE_SENTINEL, -inf).
    """
    if not parts:
        raise ValueError("merge_partial_topk needs at least one part")
    ids = np.concatenate([np.asarray(p[0], np.int64) for p in parts], axis=1)
    ss = np.concatenate(
        [np.asarray(p[1], np.float32) for p in parts], axis=1)
    if ids.shape != ss.shape:
        raise ValueError(f"ids/scores shapes differ: {ids.shape} vs {ss.shape}")
    B, L = ids.shape
    if L < k:
        ids = np.concatenate(
            [ids, np.full((B, k - L), MERGE_SENTINEL, np.int64)], axis=1)
        ss = np.concatenate(
            [ss, np.full((B, k - L), -np.inf, np.float32)], axis=1)
    invalid = ~np.isfinite(ss) | (ids >= MERGE_SENTINEL) | (ids < 0)
    ids = np.where(invalid, MERGE_SENTINEL, ids)
    ss = np.where(invalid, np.float32(-np.inf), ss).astype(np.float32)
    # primary key: score desc; secondary: id asc (sentinels sort last).
    # np.lexsort sorts by the LAST key first.
    order = np.lexsort((ids, -ss), axis=-1)[:, :k]
    return (np.take_along_axis(ids, order, axis=-1),
            np.take_along_axis(ss, order, axis=-1))


# ---------------------------------------------------------------------------
# placement
# ---------------------------------------------------------------------------

class ShardPlacement:
    """Maps index block shards to replica hosts.

    Default rule: replicas of shard s are [(s + r) % n_hosts for r in
    range(replication)] — every host owns a balanced subset, consecutive
    shards land on different primaries, and replication R survives any
    R-1 host failures. An explicit `replicas` dict {shard: [hosts]}
    overrides the rule (a shard mapped to [] is served by nobody —
    permanently degraded, used as the "serving without that shard"
    reference in tests)."""

    def __init__(self, n_shards, n_hosts, replication=1, replicas=None):
        if n_hosts < 1 or n_shards < 1:
            raise ValueError(f"need >=1 hosts and shards, got "
                             f"{n_hosts}/{n_shards}")
        if not (1 <= replication <= n_hosts):
            raise ValueError(f"replication {replication} must be in "
                             f"[1, n_hosts={n_hosts}]")
        self.n_shards, self.n_hosts = int(n_shards), int(n_hosts)
        self.replication = int(replication)
        if replicas is None:
            replicas = {s: [(s + r) % n_hosts for r in range(replication)]
                        for s in range(n_shards)}
        else:
            replicas = {int(s): list(hs) for s, hs in replicas.items()}
            for s in range(n_shards):
                replicas.setdefault(s, [])
        self.replicas = replicas

    def hosts_for(self, shard):
        return list(self.replicas[int(shard)])

    def shards_of(self, host):
        return sorted(s for s, hs in self.replicas.items() if host in hs)


# ---------------------------------------------------------------------------
# host tier
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class HostRequest:
    generation: int
    mode: str                    # "adc" | "dot"
    q_or_lut: np.ndarray         # (B, nsub, 256) LUT or (B, dim) queries
    sel_ids: np.ndarray          # (B, S) selected cluster ids
    mine: np.ndarray             # (B, S) bool: selected AND owned here
    uniq: np.ndarray             # sorted unique owned cluster ids to fetch
    trace: bool = False          # record host-side span timings


@dataclasses.dataclass
class HostResponse:
    host_id: int
    generation: int
    ids: np.ndarray              # (B, Kp) int64, (score desc, id asc)
    scores: np.ndarray           # (B, Kp) float32, -inf padding
    # host-side span records when req.trace (else None): list of
    # {"name", "t0" (absolute perf_counter at span start), "dur_ms",
    #  "parent" (local index, -1 = root), "annot"} — record 0 is the
    # "host_serve" root. Absolute perf_counter timestamps are valid
    # across the HostRequest boundary because hosts are threads in THIS
    # process (one clock); a real RPC transport would need clock-offset
    # translation here.
    spans: Any = None


class HostDown(RuntimeError):
    pass


@dataclasses.dataclass
class _HostGen:
    store: Any
    cache: Optional[BlockCache]


class EngineHost:
    """One simulated serving host: a shard-subset store + BlockCache behind
    the engine's fetch->score ops, driven through a single-worker executor
    (its "process"). Thread-backed stand-in for a real remote host; the
    HostRequest/HostResponse boundary is the wire format.

    Fault injection (tests/bench):
      kill()/revive()            — hard down: every serve raises HostDown
      inject_delay(ms, times=N)  — next N serves sleep first (timeouts)
      sim_latency=(base_ms, per_block_ms) — EVERY serve sleeps
          base + per_block * len(uniq), modeling a remote block store's
          RTT + payload time (the QPS-scaling bench measures how the
          scatter splits this bill across hosts)."""

    def __init__(self, host_id, reader, shard_ids, *, cache_capacity=512,
                 use_adc=None, sim_latency=None, sleep=time.sleep):
        if not shard_ids:
            raise ValueError(f"host {host_id} owns no shards; use fewer "
                             f"hosts or more index shards")
        self.host_id = int(host_id)
        self.shard_ids = sorted(int(s) for s in shard_ids)
        self._cache_capacity = int(cache_capacity)
        self._use_adc = bool(reader.is_pq) if use_adc is None else bool(use_adc)
        self.sim_latency = sim_latency
        self._sleep = sleep
        self._lock = threading.Lock()
        self._gens: Dict[int, _HostGen] = {}
        self._fns: Dict[Any, Any] = {}
        self._alive = True
        self._delay_ms = 0.0
        self._delay_times = 0
        self.served = 0
        self._exec = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix=f"host{host_id}")
        self.prepare_generation(reader, reader.generation).result()

    # -- lifecycle ----------------------------------------------------------

    @property
    def alive(self):
        return self._alive

    def kill(self):
        self._alive = False

    def revive(self):
        self._alive = True

    def inject_delay(self, ms, times=1):
        with self._lock:
            self._delay_ms = float(ms)
            self._delay_times = int(times)

    def close(self):
        self._exec.shutdown(wait=True)

    def prepare_generation(self, reader, generation):
        """Open the reader's CURRENT manifest state as `generation` on this
        host, alongside any generations already serving (blue/green).
        Runs through the serve queue, so it serializes with in-flight
        requests on this host. Returns the future."""
        return self._exec.submit(self._prepare, reader, int(generation))

    def _prepare(self, reader, generation):
        store = reader.open_store(shards=self.shard_ids)
        cache = None
        if self._cache_capacity:
            cap = getattr(store, "cap", None)
            dim = getattr(store, "dim", None)
            if cap and dim:
                cache = BlockCache(capacity_bytes=self._cache_capacity
                                   * int(cap) * int(dim) * 4)
            else:
                cache = BlockCache(self._cache_capacity)
        with self._lock:
            self._gens[generation] = _HostGen(store, cache)
        return generation

    def retire_generation(self, generation):
        """Drop a generation's store/cache/compiled fns through the serve
        queue — every request enqueued before the retire (which can only
        be for an older generation) is served first."""
        def _retire():
            with self._lock:
                self._gens.pop(int(generation), None)
                for key in [k for k in self._fns if k[0] == int(generation)]:
                    del self._fns[key]
        return self._exec.submit(_retire)

    def generations(self):
        with self._lock:
            return sorted(self._gens)

    # -- serving ------------------------------------------------------------

    def submit(self, req: HostRequest):
        """Enqueue a request on this host's serve queue; returns a Future
        resolving to a HostResponse (or raising HostDown)."""
        return self._exec.submit(self._serve, req)

    @staticmethod
    def _pow2(n):
        b = 1
        while b < n:
            b *= 2
        return b

    def _score_fn(self, generation, mode, B, U, S):
        key = (generation, mode, B, U, S)
        fn = self._fns.get(key)
        if fn is None:
            if mode == "adc":
                def clusd_shard_score_adc(lut, blocks, pos):
                    with jax.named_scope("dense_score"):
                        return adc_ops.adc_score_blocks(lut, blocks, pos)
                fn = jax.jit(clusd_shard_score_adc)
            else:
                def clusd_shard_score_dot(q, blocks, pos):
                    with jax.named_scope("dense_score"):
                        vecs = jnp.take(blocks, pos, axis=0)  # (B,S,cap,dim)
                        return jnp.einsum("bd,bscd->bsc", q, vecs)
                fn = jax.jit(clusd_shard_score_dot)
            self._fns[key] = fn
        return fn

    def _serve(self, req: HostRequest):
        if not self._alive:
            raise HostDown(f"host {self.host_id} is down")
        with self._lock:
            gen = self._gens.get(req.generation)
            delay = 0.0
            if self._delay_times > 0:
                delay = self._delay_ms
                self._delay_times -= 1
        if gen is None:
            raise HostDown(f"host {self.host_id} lacks generation "
                           f"{req.generation} (has {self.generations()})")
        # host-side span records (router grafts them under its scatter
        # span): opened before the fault-injection sleeps so host_serve
        # covers the host's whole wall time for this request
        spans = None
        if req.trace:
            spans = [{"name": "host_serve", "t0": time.perf_counter(),
                      "dur_ms": 0.0, "parent": -1,
                      "annot": {"generation": req.generation}}]

        def _rec(name, t0, **annot):
            if spans is not None:
                spans.append({"name": name, "t0": t0,
                              "dur_ms": (time.perf_counter() - t0) * 1e3,
                              "parent": 0, "annot": annot})
        if delay:
            self._sleep(delay / 1e3)
        if self.sim_latency:
            base_ms, per_block_ms = self.sim_latency
            self._sleep((base_ms + per_block_ms * len(req.uniq)) / 1e3)
        store, cache = gen.store, gen.cache
        uniq = np.asarray(req.uniq, np.int64)
        if uniq.size:
            fetch = pipe_lib.fetch_unique_code_blocks if req.mode == "adc" \
                else pipe_lib.fetch_unique_blocks
            t0 = time.perf_counter()
            blocks = fetch(store, uniq, cache)
            _rec("block_fetch", t0, n_blocks=int(uniq.size),
                 bytes=int(blocks.nbytes))
        else:
            blocks = np.zeros(
                (1, store.cap,
                 store.nsub if req.mode == "adc" else store.dim),
                np.uint8 if req.mode == "adc" else np.float32)
            uniq = np.zeros((1,), np.int64)
        ub = self._pow2(blocks.shape[0])
        if ub > blocks.shape[0]:
            blocks = np.concatenate(
                [blocks, np.zeros((ub - blocks.shape[0],) + blocks.shape[1:],
                                  blocks.dtype)])
        sel = np.asarray(req.sel_ids)
        mine = np.asarray(req.mine, bool)
        B, S = sel.shape
        # compact each request's columns down to this host's own slots:
        # scoring is elementwise per slot, so dropping the ~(H-1)/H columns
        # owned by other hosts changes no kept score bit while cutting this
        # host's compute to its share of the selection. The stable argsort
        # preserves slot order (ties in the merge are identical (id, score)
        # pairs, so relative order never affects the fused result).
        t0 = time.perf_counter()
        sc = self._pow2(max(int(mine.sum(axis=1).max()), 1))
        if sc < S:
            keep = np.argsort(~mine, axis=1, kind="stable")[:, :sc]
            sel = np.take_along_axis(sel, keep, axis=1)
            mine = np.take_along_axis(mine, keep, axis=1)
            S = sc
        pos = np.searchsorted(uniq, np.where(mine, sel, uniq[0]))
        _rec("compact", t0, n_slots=int(S))
        t0 = time.perf_counter()
        fn = self._score_fn(req.generation, req.mode, B, ub, S)
        scores3 = np.asarray(fn(jnp.asarray(req.q_or_lut),
                                jnp.asarray(blocks),
                                jnp.asarray(pos.astype(np.int32))))
        _rec("score", t0, mode=req.mode)
        t0 = time.perf_counter()
        docs = store.cluster_docs_np[sel]                  # (B, S, cap)
        cap = docs.shape[-1]
        valid = (docs >= 0) & mine[:, :, None]
        flat_ids = np.where(valid, docs, MERGE_SENTINEL) \
            .reshape(B, S * cap).astype(np.int64)
        flat_ss = np.where(valid.reshape(B, S * cap),
                           scores3.reshape(B, S * cap),
                           -np.inf).astype(np.float32)
        # partial top-k: (score desc, id asc); truncate the all-pad tail
        order = np.lexsort((flat_ids, -flat_ss), axis=-1)
        kp = max(1, int(valid.reshape(B, -1).sum(axis=1).max()))
        order = order[:, :kp]
        _rec("partial_topk", t0, kp=int(kp))
        self.served += 1
        if spans is not None:
            spans[0]["dur_ms"] = \
                (time.perf_counter() - spans[0]["t0"]) * 1e3
        return HostResponse(
            host_id=self.host_id, generation=req.generation,
            ids=np.take_along_axis(flat_ids, order, axis=-1),
            scores=np.take_along_axis(flat_ss, order, axis=-1),
            spans=spans)

    # -- introspection ------------------------------------------------------

    def stats(self):
        with self._lock:
            gens = sorted(self._gens)
            out = {"host": self.host_id, "alive": self._alive,
                   "shards": self.shard_ids, "served": self.served,
                   "generations": gens}
            newest = self._gens.get(gens[-1]) if gens else None
        if newest is not None:
            io = getattr(newest.store, "stats", None)
            if io is not None and hasattr(io, "n_ops"):
                out["io"] = {"n_ops": io.n_ops, "bytes": io.bytes}
            if newest.cache is not None:
                out["cache"] = newest.cache.stats()
        return out


# ---------------------------------------------------------------------------
# router
# ---------------------------------------------------------------------------

class ShardRouter:
    """Scatter-gather front-end over a fleet of EngineHost-compatible
    handles. Runs sparse retrieval + Stage I/II + (v2) the ADC LUT build
    replicated at the router — mirroring core/distributed.py's
    replicated-selection design — then scatters each batch's selected
    slots to the hosts owning their shards, gathers per-host partial
    top-k lists, merges them under the (score desc, id asc) rule, and
    fuses with the sparse side. See the module docstring for exactness,
    failover, and generation-hop semantics."""

    def __init__(self, cfg, index, reader, hosts, placement, *,
                 max_batch=256, k=None, metrics=None, tracer=None,
                 trace_sample_rate=None, fusion=None, explain=None,
                 host_timeout=10.0, max_retries=3, backoff_ms=20.0,
                 host_cooldown=2.0, sleep=time.sleep):
        from repro.core.fusion import FUSION_METHODS
        if fusion is not None and fusion not in FUSION_METHODS:
            raise ValueError(f"fusion must be one of {FUSION_METHODS}, "
                             f"got {fusion!r}")
        self._fusion_override = fusion
        self.cfg = self._apply_cfg_overrides(cfg)
        # Stage I/II + the LUT build run here: their index arrays go to the
        # device once and are passed to the compiled stages as arguments
        self.index = jax.device_put(index)
        self.reader = reader
        self.hosts: List[Any] = list(hosts)
        self.placement = placement
        if placement.n_hosts != len(self.hosts):
            raise ValueError(f"placement maps {placement.n_hosts} hosts, "
                             f"got {len(self.hosts)}")
        self.max_batch = max(1, max_batch)
        self.k = k or cfg.k_final
        self.use_adc = bool(reader.is_pq)
        self._codebooks = stores_lib.place_codebooks(reader) \
            if self.use_adc else None
        self.host_timeout = float(host_timeout)
        self.max_retries = int(max_retries)
        self.backoff_ms = float(backoff_ms)
        self.host_cooldown = float(host_cooldown)
        self._sleep = sleep
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        if tracer is None:
            tracer = Tracer(sample_rate=trace_sample_rate or 0.0)
        elif trace_sample_rate is not None:
            tracer.sample_rate = float(trace_sample_rate)
        use_profiler_annotations(tracer)
        self.tracer = tracer
        # sampled per-query explain telemetry (repro.obs.ExplainLogger);
        # router records add per-host score attribution (host_contrib)
        self.explain = explain
        self.serve_stats = ServeStats(self.metrics)
        self._failed = self.metrics.counter("router.failed_requests")
        self._degraded = self.metrics.counter("router.degraded_requests")
        self._retries = self.metrics.counter("router.retries")
        self._failovers = self.metrics.counter("router.failovers")
        self._swap_lock = threading.RLock()
        self._fns: Dict[Any, Any] = {}
        self._generation = reader.generation
        self._shard_his = self._read_shard_his(reader)
        # per-host health: monotonic time before which the host is skipped
        self._down_until = collections.defaultdict(float)
        # per-batch metadata ring for tests/debugging: generation served,
        # degraded flag, shards that had no live replica, hosts used
        self.last_batches = collections.deque(maxlen=256)

    @staticmethod
    def _read_shard_his(reader):
        return np.asarray([s["cluster_hi"]
                           for s in reader.manifest["block_shards"]],
                          np.int64)

    def _apply_cfg_overrides(self, cfg):
        if self._fusion_override is not None \
                and cfg.fusion != self._fusion_override:
            cfg = dataclasses.replace(cfg, fusion=self._fusion_override)
        return cfg

    @classmethod
    def local(cls, reader, n_hosts, replication=1, *, cfg=None, index=None,
              cache_capacity=512, sim_latency=None, placement=None,
              **router_kw):
        """Build a router over `n_hosts` thread-backed EngineHosts serving
        the reader's index with the default placement rule."""
        if index is None:
            loaded_cfg, index = reader.load_index()
            cfg = cfg if cfg is not None else loaded_cfg
        cfg = cfg if cfg is not None else reader.config()
        n_shards = reader.n_block_shards()
        if placement is None:
            placement = ShardPlacement(n_shards, n_hosts, replication)
        hosts = []
        for h in range(n_hosts):
            owned = placement.shards_of(h)
            hosts.append(EngineHost(h, reader, owned,
                                    cache_capacity=cache_capacity,
                                    sim_latency=sim_latency))
        return cls(cfg, index, reader, hosts, placement, **router_kw)

    def close(self):
        for h in self.hosts:
            close = getattr(h, "close", None)
            if close:
                close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- compiled router stages --------------------------------------------

    def _fn(self, kind, bucket, builder):
        key = (kind, bucket)
        fn = self._fns.get(key)
        if fn is None:
            fn = builder()
            self._fns[key] = fn
            self._built_fn = True
        return fn

    def _stage1_fn(self, bucket):
        return self._fn("stage1", bucket,
                        lambda: pipe_lib.build_stage1_fn(self.cfg))

    def _stage2_fn(self, bucket):
        return self._fn("stage2", bucket,
                        lambda: pipe_lib.build_stage2_fn(self.cfg))

    def _lut_fn(self, bucket):
        return self._fn("lut", bucket, pipe_lib.build_lut_fn)

    def _fuse_fn(self, bucket, kd):
        """Fuse the merged dense candidate list with the sparse side — the
        same fuse_topk the single-host fused tail ends in."""
        def build():
            cfg, n_docs, k = self.cfg, self.index.n_docs, self.k

            def clusd_fuse(sid, ss, did, dscore, dmask):
                return fusion_lib.fuse_topk(
                    sid, ss, did, jnp.where(dmask, dscore, 0.0), dmask,
                    n_docs, cfg.alpha, k, method=cfg.fusion, rrf_k=cfg.rrf_k)
            return jax.jit(clusd_fuse)
        return self._fn("fuse", (bucket, kd), build)

    # -- failover helpers ---------------------------------------------------

    def _host_live(self, h, now):
        return self.hosts[h].alive and self._down_until[h] <= now

    def _pick_host(self, shard, tried):
        """Choose a replica for `shard`: prefer live hosts not yet tried
        this request; else re-try a live host (timeouts may be transient);
        else, if every replica is hard-down, nobody (None)."""
        now = time.monotonic()
        replicas = self.placement.hosts_for(shard)
        for h in replicas:
            if h not in tried and self._host_live(h, now):
                return h
        for h in replicas:
            if self._host_live(h, now):
                return h
        # everything in cooldown or dead: probe a not-killed host anyway
        # (cooldown must not turn a transient timeout into an outage)
        for h in replicas:
            if self.hosts[h].alive:
                return h
        return None

    def _mark_failed(self, h):
        self._down_until[h] = time.monotonic() + self.host_cooldown

    def missing_shards(self):
        """Shards with NO live replica right now (degraded mode while
        non-empty: their slots are skipped, requests still complete)."""
        now = time.monotonic()
        return sorted(
            s for s in range(self.placement.n_shards)
            if not any(self.hosts[h].alive
                       for h in self.placement.hosts_for(s)))

    # -- serving ------------------------------------------------------------

    def retrieve(self, q_dense, q_terms, q_weights, *, k=None):
        """Serve a query batch of any size. Returns (ids, scores) with the
        caller's batch dimension preserved."""
        if k is not None and k != self.k:
            raise ValueError("per-call k would defeat bucketed compilation; "
                             "construct the router with the serving k")
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
        with self._swap_lock:
            try:
                return self._retrieve_locked(q_dense, q_terms, q_weights)
            except Exception:
                self._failed.inc()
                raise

    def _retrieve_locked(self, q_dense, q_terms, q_weights):
        n = int(np.asarray(q_dense).shape[0])
        bucket = bucket_size(n, self.max_batch)
        self._built_fn = False
        generation = self._generation
        tr = self.tracer.trace("batch", size=n, bucket=bucket,
                               generation=generation)
        with tr.span("pad"):
            pad = bucket - n
            qd = jnp.asarray(_pad_rows(q_dense, pad))
            qt = jnp.asarray(_pad_rows(q_terms, pad))
            qw = jnp.asarray(_pad_rows(q_weights, pad))
        t0 = time.perf_counter()
        with tr.span("stage1"):
            sid, ss, cand, feats = self._stage1_fn(bucket)(self.index,
                                                           qd, qt, qw)
        q_or_lut = qd
        if self.use_adc:
            with tr.span("lut_build"):
                q_or_lut = self._lut_fn(bucket)(*self._codebooks, qd)
                q_or_lut.block_until_ready()
        with tr.span("stage2_select"):
            sel_ids, sel_mask, probs = self._stage2_fn(bucket)(self.index,
                                                               cand, feats)
            sel_np = np.asarray(sel_ids)
            mask_np = np.asarray(sel_mask)
        mode = "adc" if self.use_adc else "dot"
        q_host = np.asarray(q_or_lut)
        # slot ownership: shard = searchsorted over manifest cluster_hi
        shard_of = np.searchsorted(self._shard_his,
                                   np.where(mask_np, sel_np, 0),
                                   side="right")
        responses, meta = self._scatter_gather(
            generation, mode, q_host, sel_np, mask_np, shard_of, tr)
        B, S = sel_np.shape
        cap = int(self.index.cluster_docs.shape[1])
        kd = S * cap
        with tr.span("merge", n_parts=len(responses)):
            if responses:
                mids, mscores = merge_partial_topk(
                    [(r.ids, r.scores) for r in responses], kd)
            else:
                mids = np.full((B, kd), MERGE_SENTINEL, np.int64)
                mscores = np.full((B, kd), -np.inf, np.float32)
            dmask = np.isfinite(mscores)
            did = np.where(dmask, mids, 0).astype(np.int32)
            dscore = np.where(dmask, mscores, 0.0).astype(np.float32)
        with tr.span("fuse"):
            ids, scores = self._fuse_fn(bucket, kd)(
                sid, ss, jnp.asarray(did), jnp.asarray(dscore),
                jnp.asarray(dmask))
            ids.block_until_ready()
        ms = (time.perf_counter() - t0) * 1e3
        gens = {r.generation for r in responses} or {generation}
        assert gens == {generation}, \
            f"mixed-generation responses: {gens} (router at {generation})"
        meta.update(generation=generation, size=n, bucket=bucket)
        self.last_batches.append(meta)
        if meta["degraded"]:
            self._degraded.inc()
        if self.explain is not None and self.explain.sample():
            recs = build_explain_records(
                self.cfg, qid_base=self.serve_stats.n_queries,
                generation=generation, n=n, cand=cand, probs=probs,
                sel_ids=sel_np, sel_mask=mask_np, final_ids=ids,
                sparse_ids=sid, doc_cluster=self.index.doc_cluster)
            final_np = np.asarray(ids)[:n]
            for i, rec in enumerate(recs):
                fset = {int(x) for x in final_np[i] if int(x) >= 0}
                contrib = {}
                for r in responses:
                    hit = len(fset & {int(x) for x in r.ids[i]
                                      if 0 <= int(x) < MERGE_SENTINEL})
                    if hit:
                        key = str(r.host_id)
                        contrib[key] = contrib.get(key, 0) + hit
                rec["host_contrib"] = contrib
                rec["degraded"] = meta["degraded"]
                self.explain.emit(rec)
        tr.finish(compiled=self._built_fn, batch_ms=round(ms, 3),
                  degraded=meta["degraded"])
        self.serve_stats.record(n, bucket, self._built_fn, ms)
        return ids[:n], scores[:n]

    def _scatter_gather(self, generation, mode, q_host, sel_np, mask_np,
                        shard_of, tr):
        """Scatter per-shard slot groups to live replicas, gather partial
        top-k responses with timeout/retry/backoff + replica failover.
        Returns (responses, meta)."""
        # pending: shard -> (B, S) bool slot mask still unserved
        pending = {}
        for s in np.unique(shard_of[mask_np]):
            pending[int(s)] = mask_np & (shard_of == int(s))
        meta = {"degraded": False, "missing_shards": [], "hosts": [],
                "retries": 0}
        responses = []
        if not pending:
            with tr.span("scatter", n_hosts=0):
                with tr.span("gather", n_hosts=0):
                    pass
            return responses, meta
        # hosts record spans only when this batch itself is traced
        trace_hosts = tr is not NOOP_TRACE
        tried = {s: set() for s in pending}
        attempt = 0
        while pending:
            # the scatter span COVERS the gather (its child), so host-side
            # spans grafted under scatter always fall inside the parent
            # window — the containment rule check_trace enforces
            with tr.span("scatter", attempt=attempt,
                         n_shards=len(pending)) as sp:
                groups = {}
                for s in sorted(pending):
                    h = self._pick_host(s, tried[s])
                    if h is None:
                        continue
                    if h != self.placement.hosts_for(s)[0]:
                        # served by a non-primary replica (primary dead,
                        # cooling down, or already tried this request)
                        self._failovers.inc()
                    groups.setdefault(h, []).append(s)
                futures = {}
                for h, shards in groups.items():
                    mine = np.zeros_like(mask_np)
                    for s in shards:
                        mine |= pending[s]
                    uniq = np.unique(sel_np[mine]) if mine.any() \
                        else np.zeros((0,), np.int64)
                    req = HostRequest(generation=generation, mode=mode,
                                      q_or_lut=q_host, sel_ids=sel_np,
                                      mine=mine, uniq=uniq,
                                      trace=trace_hosts)
                    futures[h] = (shards, self.hosts[h].submit(req))
                sp.annotate(n_hosts=len(futures))
                if not futures:    # every pending shard has no live replica
                    break
                with tr.span("gather", attempt=attempt,
                             n_hosts=len(futures)):
                    deadline = time.monotonic() + self.host_timeout
                    for h, (shards, fut) in futures.items():
                        try:
                            resp = fut.result(
                                timeout=max(0.0,
                                            deadline - time.monotonic()))
                            assert resp.generation == generation
                            responses.append(resp)
                            meta["hosts"].append(h)
                            for s in shards:
                                pending.pop(s, None)
                            if resp.spans:
                                self._graft_host_spans(tr, sp, h,
                                                       resp.spans)
                        except Exception:
                            # timeout, HostDown, or host-side error:
                            # discard (a late response is never merged),
                            # mark the host, fail shards over to a replica
                            fut.cancel()
                            self._mark_failed(h)
                            for s in shards:
                                tried[s].add(h)
            if pending:
                if attempt >= self.max_retries:
                    break
                self._retries.inc()
                meta["retries"] += 1
                self._sleep(self.backoff_ms * (2 ** attempt) / 1e3)
                attempt += 1
        if pending:
            # no live replica for these shards: complete without them —
            # results are exactly "serving without that shard"
            meta["degraded"] = True
            meta["missing_shards"] = sorted(pending)
        return responses, meta

    @staticmethod
    def _graft_host_spans(tr, scatter_sp, host_id, records):
        """Attach one host's completed span records under the router's
        open scatter span, preserving the host-local parent structure.
        Every grafted span is annotated host=<id> — the Chrome exporter
        routes those to per-host lanes, and check_trace requires the
        annotation on scatter children. Valid because hosts share this
        process's perf_counter clock (see HostResponse.spans)."""
        grafted = {}
        for j, rec in enumerate(records):
            parent = scatter_sp if rec["parent"] < 0 \
                else grafted[rec["parent"]]
            grafted[j] = tr.add_completed(
                rec["name"], t0_abs=rec["t0"], dur_ms=rec["dur_ms"],
                parent=parent, host=int(host_id), **rec["annot"])

    # -- generation hops ----------------------------------------------------

    def reload_index(self, *, verify="none"):
        """Roll the fleet to the index's current committed generation,
        host by host, with zero failed requests: prepare the new
        generation on every host (blue/green: old keeps serving), flip
        the router's arrays + compiled buckets atomically, then retire
        the old generation through each host's serve queue. Returns the
        generation now served."""
        tr = self.tracer.trace("reload_index")
        with tr.span("reload"):
            old_gen = self._generation
            self.reader.refresh(verify=verify)
            new_gen = self.reader.generation
            if new_gen == old_gen:
                tr.finish(generation=old_gen)
                return old_gen
            cfg, index = self.reader.load_index()
            cfg = self._apply_cfg_overrides(cfg)
            for host in self.hosts:        # roll host-by-host
                with tr.span("prepare_host", host=host.host_id):
                    host.prepare_generation(self.reader, new_gen).result()
            index = jax.device_put(index)
            codebooks = stores_lib.place_codebooks(self.reader) \
                if self.reader.is_pq else None
            with self._swap_lock:
                self.cfg, self.index = cfg, index
                self.use_adc = bool(self.reader.is_pq)
                self._codebooks = codebooks
                self._shard_his = self._read_shard_his(self.reader)
                self._fns.clear()
                self._generation = new_gen
                self.serve_stats.record_reload()
            for host in self.hosts:
                host.retire_generation(old_gen)
        tr.finish(generation=new_gen)
        return new_gen

    def reload_selector(self, *, verify="none"):
        """Hot-swap ONLY the Stage-II selector (selection runs at the
        router, so no host participates): adopt a newer generation's LSTM
        weights + calibrated theta/budget. Falls back to a full
        `reload_index()` when the corpus moved too."""
        from repro.engine.server import RetrievalEngine
        before = (self.reader.manifest.get("arrays"),
                  self.reader.manifest.get("block_shards"))
        self.reader.refresh(verify=verify)
        after = (self.reader.manifest.get("arrays"),
                 self.reader.manifest.get("block_shards"))
        if before != after:
            return self.reload_index(verify="none")
        if self.reader.generation == self._generation:
            return self._generation
        cfg = self._apply_cfg_overrides(self.reader.config())
        params = jax.device_put(self.reader.lstm_params())
        # a selector publish is still a generation hop: hosts key their
        # stores by generation, so they adopt it too (content-identical —
        # the prepare is mmap-open only)
        old_gen = self._generation
        for host in self.hosts:
            host.prepare_generation(self.reader, self.reader.generation) \
                .result()
        with self._swap_lock:
            old_cfg = self.cfg
            self.cfg = cfg
            self.index = dataclasses.replace(self.index, lstm_params=params)
            stale = {"stage2", "fuse"}
            if RetrievalEngine._stage1_cfg(old_cfg) != \
                    RetrievalEngine._stage1_cfg(cfg):
                stale.add("stage1")
            for key in [k for k in self._fns if k[0] in stale]:
                del self._fns[key]
            self._generation = self.reader.generation
            self.serve_stats.record_selector_reload()
        for host in self.hosts:
            host.retire_generation(old_gen)
        return self.reader.generation

    # -- introspection ------------------------------------------------------

    def _sync_gauges(self):
        """Mirror router + per-host state into registry gauges so one
        metrics export (`--metrics-out`, a /metrics scrape) captures the
        whole fleet. Before this, per-host cache/IO counters lived ONLY
        in stats()["per_host"] and were silently dropped from exports;
        now each host's numbers appear as `host<i>.cache.*` / `host<i>.
        io.*` / `host<i>.alive` / `host<i>.served` metrics."""
        reg = self.metrics
        missing = self.missing_shards()
        reg.gauge("router.generation").set(self._generation)
        reg.gauge("router.missing_shards").set(len(missing))
        reg.gauge("router.hosts_alive").set(
            sum(1 for h in self.hosts if h.alive))
        for h in self.hosts:
            st = h.stats()
            i = st["host"]
            reg.gauge(f"host{i}.alive").set(int(st["alive"]))
            reg.gauge(f"host{i}.served").set(int(st["served"]))
            for k, v in (st.get("cache") or {}).items():
                if isinstance(v, (int, float)):
                    reg.gauge(f"host{i}.cache.{k}").set(v)
            for k, v in (st.get("io") or {}).items():
                if isinstance(v, (int, float)):
                    reg.gauge(f"host{i}.io.{k}").set(v)
        return missing

    def stats(self):
        ss = self.serve_stats
        missing = self._sync_gauges()
        out = {"n_queries": ss.n_queries,
               "n_batches": ss.n_batches,
               "n_compile_batches": ss.n_compile_batches,
               "qps_steady": round(ss.steady_qps(), 1),
               "generation": self._generation,
               "hosts": len(self.hosts),
               "replication": self.placement.replication,
               "n_shards": self.placement.n_shards,
               "failed_requests": int(self._failed.value),
               "degraded_requests": int(self._degraded.value),
               "retries": int(self._retries.value),
               "failovers": int(self._failovers.value),
               "missing_shards": missing,
               "degraded": bool(missing),
               "reloads": ss.reloads,
               "selector_reloads": ss.selector_reloads,
               "fusion": self.cfg.fusion,
               "use_adc": self.use_adc,
               **ss.latency_percentiles()}
        out["per_host"] = [h.stats() for h in self.hosts]
        return out

    def reset_stats(self):
        with self._swap_lock:
            self.metrics.reset()
            self.serve_stats.reset()
            self.last_batches.clear()
