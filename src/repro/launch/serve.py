"""CluSD serving driver on the unified RetrievalEngine (repro.engine).

Builds the index over a synthetic corpus, trains the Stage-II LSTM, then
serves batched queries through `RetrievalEngine` — one select/score/fuse
pipeline (engine/pipeline.py) behind a pluggable ClusterStore backend:

  * default: in-memory backend; request batches are padded to power-of-two
    buckets so jit compiles once per bucket, not once per ragged tail.
  * --ondisk: DiskStore backend with a bounded LRU block cache and a
    background thread prefetching Stage-I candidate blocks while Stage-II
    LSTM selection runs; reports I/O ops/bytes and cache hit rate.

Reports latency percentiles and quality vs the full-retrieval oracle.

With --index-dir, the build step is skipped entirely: the engine serves a
persistent index built by `python -m repro.launch.build_index` — the
manifest is validated (at the --verify level: none/size/full), arrays are
mmapped, and cluster blocks are read from the per-shard files through a
`ShardedDiskStore` (v1 float blocks) or `ShardedPQStore` (v2 PQ code
shards built with `--format-version 2 [--memmap --chunk-docs N]`; codes
decode through the index codebooks at fetch time — exact-ADC numerics).
Indexes mutated by `repro.launch.update_index` serve their newest
generation; deleted docs are tombstone-masked at fetch.

--check-parity replays the queries through the in-memory pipeline and
exits non-zero on mismatch: exact top-k ids for v1 indexes; for v2 (PQ)
indexes — approximate by construction — an MRR@10 delta bound against the
float32 corpus, tunable with --parity-mrr-tol (default 0.02), and, for
the serving path alone, rank-wise agreement of the top-10 fused scores
with the index's own PQ codes scored in memory (the jnp ADC path of
core/quant.py).

--trace-out exports per-batch stage-span traces (stage1 -> stage2_select
-> cache/disk fetch -> fused_score_topk; `.jsonl` span lines or Chrome
trace JSON for Perfetto), sampled at --trace-sample-rate; --metrics-out
dumps the engine metrics registry (JSON or Prometheus text by suffix).
Catalog: docs/OBSERVABILITY.md.

Live observability (with --index-dir): --metrics-port P starts an HTTP
exporter over the serving engine/router BEFORE the first batch — GET
/metrics (Prometheus text), /metrics.json, /slo, /healthz (503 while the
SLO state is PAGE or any shard has lost every replica); P=0 binds an
ephemeral port (printed). --slo-config PATH loads declarative SLO
objectives (JSON {"objectives": [...]}; see docs/OBSERVABILITY.md) into
an SLOMonitor judging the run — without it --metrics-port uses the
default objective set. --explain-out PATH.jsonl emits sampled per-query
explain records (candidate provenance, selector probs vs theta/budget,
fusion contributions, per-host attribution on the router path) at
--explain-sample-rate; analyze with `python -m benchmarks.explain_report`.
--serve-seconds S keeps replaying the query set until the deadline so
the endpoints stay live under sustained traffic (the CI metrics-endpoint
smoke curls them mid-stream).

--hosts N (with --index-dir) serves through the multi-host scatter-gather
tier (engine/router.py) instead of a single engine: a ShardRouter runs
sparse retrieval + Stage I/II replicated and scatters the selected
clusters to N simulated hosts, each owning a balanced subset of the index
block shards behind its own store + cache; per-host partial top-k lists
merge under the exact (score desc, doc id asc) rule and fuse with the
sparse side — bitwise-identical results to the single-host engine under
interp fusion. --replication R places each shard on R hosts (replica
failover); --host-timeout-ms bounds each scatter leg; --kill-host I kills
host I after the first batch (fault injection: with R >= 2 serving must
continue with zero failed requests — the CI router-smoke job asserts
this plus parity vs the single-host engine). --check-parity on this path
replays the queries through a single-host engine and exits non-zero on
any id mismatch. Router traces add scatter/gather/merge spans.

--fusion overrides the final-list fusion method (interp = paper min-max
interpolation, rrf = weighted reciprocal-rank fusion); --expand-depth N
deepens Stage-I candidates through the cluster neighbor graph (LADR-style
hybrid candidate generation, N extra n_candidates blocks of clusters
considered per query at the same selection budget). Both default to the
served config (a calibrated publish may have set them); depth 0 + interp
is bitwise the classic pipeline.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --docs 20000 --queries 256 \
      [--ondisk] [--cache-blocks 512] [--no-prefetch] \
      [--fusion interp|rrf] [--expand-depth N]
  PYTHONPATH=src python -m repro.launch.serve --index-dir /tmp/idx \
      --queries 64 [--verify full] [--check-parity [--parity-mrr-tol T]] \
      [--trace-out trace.jsonl] [--metrics-out metrics.json]
  PYTHONPATH=src python -m repro.launch.serve --index-dir /tmp/idx \
      --hosts 3 --replication 2 [--host-timeout-ms 10000] [--kill-host 0] \
      --check-parity [--trace-out trace.jsonl]
"""

import argparse
import dataclasses
import os
import tempfile
import time

import jax
import numpy as np

from repro.common.compile_cache import place_compile_cache
from repro.configs import clusd_msmarco, get_config
from repro.core import clusd as cl
from repro.core import disk as dk
from repro.core import train_lstm as tl
from repro.data import mrr_at, recall_at, synth_corpus, synth_queries
from repro.engine import DiskStore, RetrievalEngine


def check_results(ids, scores, n_docs):
    """Sanity of a served result list: every id a real doc, no doc twice
    in a row, every score finite. Returns the problems found (empty when
    the results are well formed)."""
    ids = np.asarray(ids)
    problems = []
    if ((ids < 0) | (ids >= n_docs)).any():
        problems.append(f"{int(((ids < 0) | (ids >= n_docs)).sum())} ids "
                        f"outside [0, {n_docs})")
    srt = np.sort(ids, axis=1)
    dup_rows = int((srt[:, 1:] == srt[:, :-1]).any(axis=1).sum())
    if dup_rows:
        problems.append(f"{dup_rows} rows repeat a doc id")
    if not np.isfinite(np.asarray(scores)).all():
        problems.append("non-finite scores")
    return problems


def device_bytes_report(index):
    """One line naming the index arrays a serving engine holds on the
    device and their bytes."""
    parts = {"embeddings": index.embeddings,
             "postings": index.sparse_index,
             "pq": index.quantizer,
             "cluster tables": (index.cluster_docs, index.doc_cluster,
                                index.centroids, index.neighbor_ids,
                                index.neighbor_sims, index.bin_ids)}
    sizes = {k: sum(x.nbytes for x in jax.tree.leaves(v))
             for k, v in parts.items()}
    total = sum(sizes.values())
    return ", ".join([f"index {total / 2**30:.3f} GiB"] + [
        f"{k} {v / 2**30:.3f} GiB" for k, v in sizes.items() if v])


def _apply_hybrid_flags(cfg, args):
    """Overlay --fusion / --expand-depth on the served config (None =
    keep what the config/manifest says, e.g. a calibrated publish)."""
    changes = {}
    if args.fusion is not None:
        changes["fusion"] = args.fusion
    if args.expand_depth is not None:
        changes["expand_depth"] = args.expand_depth
    return dataclasses.replace(cfg, **changes) if changes else cfg


def _write_obs(args, engine):
    """Export --metrics-out / --trace-out from a served engine."""
    from repro.obs import write_metrics, write_trace
    if args.metrics_out:
        engine.stats()          # folds cache/io/decode counters into gauges
        write_metrics(engine.metrics, args.metrics_out)
        print(f"metrics -> {args.metrics_out}")
    if args.trace_out:
        write_trace(engine.tracer, args.trace_out)
        print(f"trace -> {args.trace_out} "
              f"({engine.tracer.started} trace(s) at "
              f"sample rate {engine.tracer.sample_rate})")


def _make_explain(args):
    """--explain-out: a sampled per-query ExplainLogger for the engine/
    router ctor (None when the flag is absent — zero serving cost)."""
    if not getattr(args, "explain_out", None):
        return None
    from repro.obs import ExplainLogger
    return ExplainLogger(args.explain_out,
                         sample_rate=args.explain_sample_rate)


def _start_exporter(args, target):
    """--metrics-port / --slo-config: attach an SLOMonitor and start the
    live HTTP endpoint over the serving target. Returns (exporter, slo),
    either of which may be None."""
    from repro.obs import MetricsExporter, SLOMonitor, default_objectives
    slo = None
    if getattr(args, "slo_config", None):
        slo = SLOMonitor.from_config(target.metrics, args.slo_config)
    elif args.metrics_port is not None:
        slo = SLOMonitor(target.metrics, default_objectives())
    exp = None
    if args.metrics_port is not None:
        exp = MetricsExporter(target, port=args.metrics_port,
                              slo=slo).start()
        print(f"metrics endpoint: http://127.0.0.1:{exp.port}/metrics "
              f"(also /metrics.json /slo /healthz)", flush=True)
    return exp, slo


def _finish_obs(args, exporter, slo, explain):
    """Tear down the live observability attachments, reporting state."""
    if slo is not None:
        slo.evaluate()
        print(f"SLO state: {slo.state} "
              f"(pages={slo.verdict()['pages']}, "
              f"warns={slo.verdict()['warns']})")
    if exporter is not None:
        exporter.stop()
    if explain is not None:
        explain.close()
        st = explain.stats()
        print(f"explain -> {st['path']} ({st['n_records']} record(s), "
              f"{st['n_sampled']}/{st['n_sampled'] + st['n_skipped']} "
              f"batches sampled)")


def _sustain(args, serve_pass, slo=None):
    """--serve-seconds: keep replaying the query set until the deadline
    (keeps the metrics endpoints live under sustained traffic)."""
    if not args.serve_seconds:
        return
    deadline = time.monotonic() + args.serve_seconds
    passes = 0
    while time.monotonic() < deadline:
        serve_pass(deadline)
        passes += 1
        if slo is not None:
            slo.evaluate()
    print(f"sustained serving: {passes} extra pass(es) over "
          f"{args.serve_seconds:.0f}s window")


def serve_from_router(args, reader, cfg, index, test_q):
    """Serve through the multi-host scatter-gather tier (--hosts N)."""
    from repro import index as index_lib
    from repro.engine import ShardRouter

    trace_rate = args.trace_sample_rate if args.trace_out else None
    with ShardRouter.local(
            reader, n_hosts=args.hosts, replication=args.replication,
            cfg=cfg, index=index, max_batch=args.batch,
            cache_capacity=args.cache_blocks,
            host_timeout=args.host_timeout_ms / 1e3,
            trace_sample_rate=trace_rate,
            explain=_make_explain(args)) as router:
        # endpoints come up before the first (compiling) batch, so a
        # scraper polling /metrics gets 200 while serving warms up
        exporter, slo = _start_exporter(args, router)
        all_ids = []
        for bi, i in enumerate(range(0, args.queries, args.batch)):
            ids, _ = router.retrieve(test_q.q_dense[i:i + args.batch],
                                     test_q.q_terms[i:i + args.batch],
                                     test_q.q_weights[i:i + args.batch])
            all_ids.append(np.asarray(ids))
            if args.kill_host is not None and bi == 0:
                router.hosts[args.kill_host].kill()
                print(f"injected failure: host {args.kill_host} killed "
                      f"after batch 0 (replication {args.replication})",
                      flush=True)
        ids = np.concatenate(all_ids)

        def _replay(deadline):
            for i in range(0, args.queries, args.batch):
                router.retrieve(test_q.q_dense[i:i + args.batch],
                                test_q.q_terms[i:i + args.batch],
                                test_q.q_weights[i:i + args.batch])
                if time.monotonic() >= deadline:
                    return
        _sustain(args, _replay, slo)
        st = router.stats()
        print(f"router: {st['hosts']} hosts x replication "
              f"{st['replication']} over {st['n_shards']} shards, "
              f"generation {st['generation']}")
        print(f"served {args.queries} queries: "
              f"MRR@10={mrr_at(ids, test_q.rel_doc):.4f}, "
              f"failed={st['failed_requests']} "
              f"degraded={st['degraded_requests']} "
              f"failovers={st['failovers']} retries={st['retries']} "
              f"missing_shards={st['missing_shards']}")
        _write_obs(args, router)
        _finish_obs(args, exporter, slo, router.explain)

        ok = True
        if args.check_parity:
            # reference: a fresh single-host engine over the same index —
            # results must match exactly (same pipeline, v1 and v2 alike)
            ref_reader = index_lib.IndexReader.open(args.index_dir,
                                                    verify="none")
            refs = []
            with ref_reader.engine(max_batch=args.batch,
                                   prefetch=False) as eng:
                for i in range(0, args.queries, args.batch):
                    r, _ = eng.retrieve(test_q.q_dense[i:i + args.batch],
                                        test_q.q_terms[i:i + args.batch],
                                        test_q.q_weights[i:i + args.batch])
                    refs.append(np.asarray(r))
            ref_ids = np.concatenate(refs)
            if not np.array_equal(ids, ref_ids):
                bad = int((ids != ref_ids).any(axis=1).sum())
                print(f"PARITY FAIL: {bad}/{args.queries} queries differ "
                      f"from the single-host engine")
                ok = False
            else:
                print(f"parity OK: {args.hosts}-host scatter-gather matches "
                      f"the single-host engine exactly")
        if st["failed_requests"]:
            print(f"FAIL: {st['failed_requests']} failed request(s)")
            ok = False
    return 0 if ok else 1


def _corpus_as_built(reader):
    """True while the served documents are still those of the generation-0
    build, so the synthetic-corpus recipe reproduces them: selector
    publishes add generations but rewrite no corpus array or block shard
    (index deltas and compactions do)."""
    if reader.generation == 0:
        return True
    from repro.index import format as fmt
    try:
        g0 = fmt.load_manifest(reader.index_dir, generation=0)
    except fmt.IndexFormatError:        # compaction dropped generation 0
        return False
    return all(g0.get(k) == reader.manifest.get(k)
               for k in ("arrays", "block_shards"))


def serve_from_index(args):
    """Serve a persistent index built by repro.launch.build_index."""
    from repro import index as index_lib
    from repro.engine import InMemoryStore, PQStore, pipeline as pipe_lib

    t0 = time.perf_counter()
    reader = index_lib.IndexReader.open(args.index_dir, verify=args.verify)
    cfg, index = reader.load_index()
    cfg = _apply_hybrid_flags(cfg, args)
    open_ms = (time.perf_counter() - t0) * 1e3
    meta = reader.manifest.get("extra", {}).get("corpus")
    if meta is None or meta.get("kind") != "synthetic":
        raise SystemExit("index lacks synthetic-corpus metadata; cannot "
                         "regenerate queries for quality evaluation")
    corpus = synth_corpus(meta["seed"], meta["n_docs"], meta["dim"],
                          meta["vocab"])
    test_q = synth_queries(9, corpus, args.queries)

    if args.hosts:
        return serve_from_router(args, reader, cfg, index, test_q)

    trace_rate = args.trace_sample_rate if args.trace_out else None
    with reader.engine(cfg=cfg, index=index, max_batch=args.batch,
                       cache_capacity=args.cache_blocks,
                       prefetch=not args.no_prefetch,
                       trace_sample_rate=trace_rate,
                       explain=_make_explain(args)) as engine:
        exporter, slo = _start_exporter(args, engine)
        t1 = time.perf_counter()
        first_ids, first_scores = engine.retrieve(
            test_q.q_dense[:args.batch], test_q.q_terms[:args.batch],
            test_q.q_weights[:args.batch])
        first_ms = (time.perf_counter() - t1) * 1e3
        all_ids = [np.asarray(first_ids)]
        all_scores = [np.asarray(first_scores)]
        for i in range(args.batch, args.queries, args.batch):
            ids, scores = engine.retrieve(test_q.q_dense[i:i + args.batch],
                                          test_q.q_terms[i:i + args.batch],
                                          test_q.q_weights[i:i + args.batch])
            all_ids.append(np.asarray(ids))
            all_scores.append(np.asarray(scores))

        def _replay(deadline):
            for i in range(0, args.queries, args.batch):
                engine.retrieve(test_q.q_dense[i:i + args.batch],
                                test_q.q_terms[i:i + args.batch],
                                test_q.q_weights[i:i + args.batch])
                if time.monotonic() >= deadline:
                    return
        _sustain(args, _replay, slo)
        _finish_obs(args, exporter, slo, engine.explain)
    ids = np.concatenate(all_ids)
    st = engine.stats()
    io, cache = st.get("io", {}), st.get("cache", {})
    print(f"index: {reader.index_dir} "
          f"(format v{reader.format_version}, "
          f"{reader.manifest['total_bytes'] / 2**20:.1f} MiB, "
          f"{len(reader.manifest['block_shards'])} shard(s), verify={args.verify})")
    print(f"cold open {open_ms:.0f} ms, first batch {first_ms:.0f} ms "
          f"(incl. compile), steady {st.get('mean_ms', float('nan'))} ms/"
          f"batch of {args.batch}")
    print(f"on device: {device_bytes_report(engine.index)}")
    print(f"served {args.queries} queries: "
          f"MRR@10={mrr_at(ids, test_q.rel_doc):.4f}, "
          f"{io.get('n_ops', 0)} I/O ops, "
          f"{io.get('bytes', 0) / 2**20:.1f} MiB read, "
          f"cache hit rate {cache.get('hit_rate', 0.0):.2f}, "
          f"use_adc={st.get('use_adc')}, "
          f"prefetch_errors={st['prefetch_errors']}")
    _write_obs(args, engine)
    problems = check_results(ids, np.concatenate(all_scores),
                             index.n_docs)
    if problems or st["prefetch_errors"]:
        print(f"FAIL: {'; '.join(problems) or ''} "
              f"prefetch_errors={st['prefetch_errors']}")
        return 1

    if args.check_parity:
        if not _corpus_as_built(reader):
            print("PARITY UNAVAILABLE: this index has been incrementally "
                  f"updated (generation {reader.generation}); the "
                  "synthetic-corpus recipe no longer reproduces its "
                  "documents, so the in-memory baseline would be stale. "
                  "Use repro.launch.update_index --check-parity (compares "
                  "against a compacted copy) instead.")
            return 1
        def reference(store):
            # one serving batch at a time: the in-memory gather holds
            # (batch, max_selected * cap, dim) floats
            out = [pipe_lib.retrieve(cfg, index, store,
                                     test_q.q_dense[i:i + args.batch],
                                     test_q.q_terms[i:i + args.batch],
                                     test_q.q_weights[i:i + args.batch])
                   for i in range(0, args.queries, args.batch)]
            return (np.concatenate([np.asarray(o[0]) for o in out]),
                    np.concatenate([np.asarray(o[1]) for o in out]))

        # the float32 corpus in memory: exact ids for v1; for v2 (PQ,
        # approximate by construction) a bounded MRR@10 delta
        with jax.default_matmul_precision("highest"):
            ref_ids, _ = reference(jax.device_put(InMemoryStore(
                corpus.embeddings, index.cluster_docs)))
        if not reader.is_pq:
            if not np.array_equal(ids, ref_ids):
                bad = int((ids != ref_ids).any(axis=1).sum())
                print(f"PARITY FAIL: {bad}/{args.queries} queries differ "
                      f"from the in-memory pipeline")
                return 1
            print("parity OK: sharded on-disk serving matches the "
                  "in-memory pipeline exactly")
            return 0
        ref_mrr = mrr_at(ref_ids, test_q.rel_doc[:args.queries])
        got_mrr = mrr_at(ids, test_q.rel_doc[:args.queries])
        ok = abs(ref_mrr - got_mrr) <= args.parity_mrr_tol
        print(f"parity {'OK' if ok else 'FAIL'} (float corpus): PQ MRR@10 "
              f"{got_mrr:.4f} vs in-memory float32 {ref_mrr:.4f} "
              f"(tol {args.parity_mrr_tol})")
        # and the serving path alone: the index's own PQ codes scored in
        # memory by the jnp ADC path (core/quant.py, an f32 LUT like the
        # kernel's). ADC reassociates the sum, so ids may swap at near-ties,
        # but each rank's fused score must agree
        codes_ids, codes_scores = reference(
            PQStore(reader.quantizer(), index.cluster_docs))
        top = min(10, ids.shape[1])
        n_diff = int((ids[:, :top] != codes_ids[:, :top]).any(axis=1).sum())
        dev = float(np.abs(np.concatenate(all_scores)[:, :top]
                           - codes_scores[:, :top]).max())
        codes_ok = dev <= PQ_SCORE_TOL
        print(f"parity {'OK' if codes_ok else 'FAIL'} (same PQ codes): "
              f"{n_diff}/{args.queries} queries differ in the top {top} ids, "
              f"max top-{top} fused-score deviation {dev:.3g} "
              f"(tol {PQ_SCORE_TOL})")
        if not (ok and codes_ok):
            print("PARITY FAIL")
            return 1
        print("parity OK")
    return 0


# --check-parity on PQ indexes: the largest rank-wise fused-score deviation
# allowed between the serving path and the same codes scored in memory.
# Fused scores lie in [0, 1]; f32 rounding moves them by ~1e-6, a LUT built
# with bf16 MXU passes by ~1e-3.
PQ_SCORE_TOL = 1e-4


def build_cfg(args):
    """The served config for the build-in-memory path (no --index-dir):
    --variant smoke keeps the small CPU-sized geometry below; --variant
    full serves the paper's MS MARCO config (configs/clusd_msmarco.full())
    at its published widths, cut only by --docs / --clusters."""
    if args.variant == "full":
        cfg = clusd_msmarco.from_cli(args)
    else:
        a = clusd_msmarco.smoke_sizes(args)
        cfg = dataclasses.replace(
            get_config("clusd-msmarco", "smoke"),
            n_docs=a["docs"], dim=a["dim"], n_clusters=a["clusters"],
            vocab=a["vocab"], k_sparse=512, bins=(10, 25, 50, 100, 200, 512),
            n_candidates=32, max_selected=16, k_final=256,
            train_queries=a["train_queries"], epochs=a["epochs"])
    return _apply_hybrid_flags(cfg, args)


def main(argv=None):
    # __doc__ IS the epilog: the module docstring and --help can never
    # drift apart (CI smoke-tests --help for every repro.launch CLI)
    ap = argparse.ArgumentParser(
        description="Serve CluSD retrieval through the unified "
                    "RetrievalEngine (in-memory, on-disk, or a persistent "
                    "built index).",
        epilog=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--variant", default="smoke", choices=("smoke", "full"),
                    help="clusd-msmarco config built in memory (no "
                         "--index-dir): smoke (small geometry) or full (the "
                         "paper's MS MARCO widths; --docs/--clusters are its "
                         "only cuts)")
    ap.add_argument("--docs", type=int, default=None,
                    help="corpus size (default: 20000 smoke, the config's "
                         "under full)")
    ap.add_argument("--dim", type=int, default=None, help="smoke only (64)")
    ap.add_argument("--clusters", type=int, default=None,
                    help="cluster count (default: 256 smoke, the config's "
                         "under full)")
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--train-queries", type=int, default=None,
                    help="selector training queries (default: 512 smoke, "
                         "the config's under full)")
    ap.add_argument("--epochs", type=int, default=None,
                    help="selector epochs (default: 40 smoke, the config's "
                         "under full)")
    ap.add_argument("--ondisk", action="store_true")
    ap.add_argument("--fusion", default=None, choices=("interp", "rrf"),
                    help="final-list fusion method override (default: the "
                         "served config's; interp = paper min-max "
                         "interpolation, rrf = weighted reciprocal-rank)")
    ap.add_argument("--expand-depth", type=int, default=None,
                    help="Stage-I neighbor-graph expansion depth override "
                         "(0 = off; widens candidates to n_candidates * "
                         "(1 + depth) at the same selection budget)")
    ap.add_argument("--cache-blocks", type=int, default=512)
    ap.add_argument("--no-prefetch", action="store_true")
    ap.add_argument("--hosts", type=int, default=0,
                    help="with --index-dir: serve through the multi-host "
                         "scatter-gather router over N simulated hosts "
                         "(0 = single-host engine)")
    ap.add_argument("--replication", type=int, default=1,
                    help="replicas per index shard across the host fleet "
                         "(R >= 2 survives any R-1 host failures)")
    ap.add_argument("--host-timeout-ms", type=float, default=10000.0,
                    help="per-host scatter-leg timeout before the router "
                         "retries / fails over to a replica")
    ap.add_argument("--kill-host", type=int, default=None, metavar="I",
                    help="fault injection: kill host I after the first "
                         "batch (with --replication >= 2 serving must "
                         "continue with zero failed requests)")
    ap.add_argument("--index-dir", default=None,
                    help="serve a built index (repro.launch.build_index) "
                         "instead of rebuilding in memory")
    ap.add_argument("--verify", default="size",
                    choices=("none", "size", "full"),
                    help="built-index integrity check level at open")
    ap.add_argument("--check-parity", action="store_true",
                    help="with --index-dir: compare against the in-memory "
                         "pipeline, exit non-zero on mismatch (exact ids "
                         "for v1; for PQ/v2 indexes an MRR@10 tolerance "
                         "vs the float32 corpus and top-10 score agreement "
                         "with the same PQ codes in memory)")
    ap.add_argument("--parity-mrr-tol", type=float, default=0.02,
                    help="allowed MRR@10 delta between PQ serving and the "
                         "float32 corpus")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="export per-batch stage-span traces after serving "
                         "(.jsonl = one span per line, anything else = "
                         "Chrome trace JSON; see docs/OBSERVABILITY.md)")
    ap.add_argument("--trace-sample-rate", type=float, default=1.0,
                    help="fraction of batches traced when --trace-out is "
                         "set (deterministic: 0.25 = every 4th batch)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="dump the engine metrics registry after serving "
                         "(.prom/.txt = Prometheus text, else JSON)")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="P",
                    help="with --index-dir: serve live /metrics, "
                         "/metrics.json, /slo, and /healthz over HTTP on "
                         "port P while serving runs (0 = ephemeral port, "
                         "printed at startup)")
    ap.add_argument("--slo-config", default=None, metavar="PATH",
                    help="JSON SLO objectives ({\"objectives\": [...]}; "
                         "schema in docs/OBSERVABILITY.md) judging the run "
                         "via an SLOMonitor; default objectives are used "
                         "when --metrics-port is set without this")
    ap.add_argument("--explain-out", default=None, metavar="PATH",
                    help="with --index-dir: write sampled per-query "
                         "explain records (JSONL; schema in "
                         "docs/OBSERVABILITY.md) for "
                         "benchmarks.explain_report")
    ap.add_argument("--explain-sample-rate", type=float, default=1.0,
                    help="fraction of batches explained when --explain-out "
                         "is set (deterministic accumulator sampling)")
    ap.add_argument("--serve-seconds", type=float, default=0.0, metavar="S",
                    help="after the scored pass, keep replaying the query "
                         "set for S more seconds so the live endpoints "
                         "can be scraped under sustained traffic")
    args = ap.parse_args(argv)
    place_compile_cache()

    if args.index_dir:
        return serve_from_index(args)

    cfg = build_cfg(args)

    print(f"config {cfg.name} ({args.variant}): {cfg.n_docs} docs x "
          f"{cfg.dim} dim, N={cfg.n_clusters} cap={cfg.cluster_cap}, "
          f"vocab={cfg.vocab} max_postings={cfg.max_postings}, "
          f"k_sparse={cfg.k_sparse} n={cfg.n_candidates} "
          f"max_selected={cfg.max_selected} k_final={cfg.k_final}, "
          f"train_queries={cfg.train_queries} epochs={cfg.epochs}",
          flush=True)
    t0 = time.perf_counter()
    corpus = synth_corpus(0, cfg.n_docs, cfg.dim, cfg.vocab)
    t1 = time.perf_counter()
    index = cl.build_index(cfg, jax.random.key(0), corpus.embeddings,
                           corpus.doc_terms, corpus.doc_weights)
    jax.block_until_ready(index)
    t2 = time.perf_counter()
    train_q = synth_queries(1, corpus, cfg.train_queries)
    _, feats, labels = tl.make_labels(cfg, index, train_q.q_dense,
                                      train_q.q_terms, train_q.q_weights)
    index.lstm_params, hist = tl.train_selector(
        cfg, jax.random.key(2), np.asarray(feats), np.asarray(labels))
    t3 = time.perf_counter()
    print(f"setup: corpus {t1 - t0:.1f}s, index build {t2 - t1:.1f}s, "
          f"labels + selector training {t3 - t2:.1f}s", flush=True)
    print(f"LSTM trained: loss {hist[0]:.4f} -> {hist[-1]:.4f}", flush=True)

    test_q = synth_queries(9, corpus, args.queries)
    engine = RetrievalEngine(
        cfg, index, max_batch=args.batch,
        trace_sample_rate=args.trace_sample_rate if args.trace_out else None)
    print(f"on device: {device_bytes_report(engine.index)}", flush=True)
    all_ids, all_scores = [], []
    for i in range(0, args.queries, args.batch):
        ids, scores = engine.retrieve(test_q.q_dense[i:i + args.batch],
                                      test_q.q_terms[i:i + args.batch],
                                      test_q.q_weights[i:i + args.batch])
        all_ids.append(np.asarray(ids))
        all_scores.append(np.asarray(scores))
    ids = np.concatenate(all_ids)
    st = engine.stats()
    lat = np.asarray(engine.serve_stats.per_query_ms())
    print(f"first batch {engine.serve_stats.batches[0].ms:.1f} ms "
          f"(incl. compile), steady {st.get('mean_ms', float('nan'))} ms/"
          f"batch of {args.batch}, prefetch_errors={st['prefetch_errors']}",
          flush=True)
    problems = check_results(ids, np.concatenate(all_scores), cfg.n_docs)

    oracle_ids, _ = cl.full_dense_topk(index.embeddings, test_q.q_dense, 64)
    print(f"CluSD   MRR@10={mrr_at(ids, test_q.rel_doc):.4f} "
          f"R@{cfg.k_final}={recall_at(ids, test_q.rel_doc, cfg.k_final):.4f}")
    print(f"oracle-dense MRR@10={mrr_at(np.asarray(oracle_ids), test_q.rel_doc):.4f}")
    if len(lat):
        print(f"serve latency/query: mean={lat.mean():.2f}ms "
              f"p99={np.percentile(lat, 99):.2f}ms "
              f"(buckets compiled: {st['compiled_buckets']})")
    _write_obs(args, engine)
    if problems or st["prefetch_errors"]:
        print(f"FAIL: {'; '.join(problems) or ''} "
              f"prefetch_errors={st['prefetch_errors']}")
        return 1

    if args.ondisk:
        tmp = tempfile.mkdtemp()
        blocks = dk.DiskClusterStore.pack(os.path.join(tmp, "blocks.bin"),
                                          corpus.embeddings,
                                          index.cluster_docs)
        nq = min(64, args.queries)
        with RetrievalEngine(cfg, index,
                             store=DiskStore(blocks, index.cluster_docs),
                             max_batch=args.batch,
                             cache_capacity=args.cache_blocks,
                             prefetch=not args.no_prefetch) as deng:
            t0 = time.perf_counter()
            ids_d, _ = deng.retrieve(test_q.q_dense[:nq], test_q.q_terms[:nq],
                                     test_q.q_weights[:nq])
            wall = time.perf_counter() - t0
        # stats after close(): the prefetch worker has drained, so I/O and
        # cache numbers are final
        ds = deng.stats()
        io, cache = ds["io"], ds.get("cache", {})
        qps = ds["qps_steady"]
        qps_str = f"{qps:.1f} QPS steady" if qps else \
            f"{nq / wall:.1f} QPS incl. compile"
        print(f"on-disk engine: {io['n_ops']} block reads, "
              f"{io['bytes'] / 2**20:.1f} MiB, model {io['model_ms']:.1f} ms, "
              f"cache hit rate {cache.get('hit_rate', 0.0):.2f}, "
              f"{qps_str}, "
              f"MRR@10={mrr_at(np.asarray(ids_d), test_q.rel_doc[:nq]):.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
