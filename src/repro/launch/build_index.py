"""Offline index build CLI: cluster, pack, and serialize once — then serve
from the built directory (`repro.launch.serve --index-dir`) without ever
rebuilding or materializing the embedding matrix at load time, and mutate
it later with `repro.launch.update_index` (incremental deltas).

  PYTHONPATH=src python -m repro.launch.build_index --out /tmp/idx \
      --docs 20000 --clusters 256 --shards 8 --train-queries 512

  # format v2: PQ code shards (4-16x smaller embedding store), built from
  # an np.memmap staged corpus with bounded-chunk reads (corpus > RAM path)
  PYTHONPATH=src python -m repro.launch.build_index --out /tmp/idx_pq \
      --format-version 2 --pq-nsub 8 --memmap --chunk-docs 4096

  # the paper's MS MARCO widths (dim 768, vocab 30,522, 4,096 postings per
  # term, ...), cut only in corpus size and cluster count
  PYTHONPATH=src python -m repro.launch.build_index --out /tmp/idx_full \
      --variant full --docs 1048576 --clusters 1024 --format-version 2 \
      --train-queries 0

Key flags (the full list with defaults is below / `--help`):
  --variant {smoke,full}  clusd-msmarco config; full = the paper's widths
  --format-version {1,2}  1 = float32 block shards; 2 = PQ code shards +
                          CSR postings (4-16x smaller; served via
                          decode-on-fetch ADC at exact-ADC numerics)
  --memmap                stage the synthetic corpus through an np.memmap
                          and build from it — the corpus>RAM path (LSTM
                          label generation still uses in-RAM embeddings)
  --chunk-docs N          bound every embedding read to N rows (0 = one
                          k-means shard per read); enforced by a capped-
                          read wrapper test in tests/test_index.py
  --pq-nsub N             PQ subspaces (v1: optional side artifacts;
                          v2: the code shards; defaults to 8 under v2)

Pipeline (repro/index/builder.py): sharded Lloyd's k-means over embedding
shards -> capacity-balanced cluster table -> neighbor graph -> sparse
inverted index -> optional LSTM selector training (labels need the full
embeddings; that is fine offline) -> optional PQ codebooks -> per-shard
cluster-block (v1) or code-block (v2) files + versioned, checksummed,
generation-0 manifest (see src/repro/index/README.md).
"""

import argparse
import dataclasses
import math
import os
import tempfile
import time

import jax
import numpy as np

from repro import index as index_lib
from repro.common.compile_cache import place_compile_cache
from repro.configs import clusd_msmarco, get_config
from repro.core import train_lstm as tl
from repro.data import synth_corpus, synth_queries


def build_cfg(args):
    """--variant smoke: the small CPU-sized geometry, derived from --docs /
    --dim / --clusters / --vocab. --variant full: the paper's MS MARCO
    config (configs/clusd_msmarco.full()) at its published widths; only
    --docs and --clusters cut it."""
    if args.variant == "full":
        return clusd_msmarco.from_cli(args)
    a = clusd_msmarco.smoke_sizes(args)
    k_sparse = max(32, min(512, a["docs"] // 4))
    bins = tuple(b for b in (10, 25, 50, 100, 200) if b < k_sparse) + (k_sparse,)
    return dataclasses.replace(
        get_config("clusd-msmarco", "smoke"),
        n_docs=a["docs"], dim=a["dim"], n_clusters=a["clusters"],
        vocab=a["vocab"], k_sparse=k_sparse, bins=bins,
        n_candidates=min(32, a["clusters"]), max_selected=16,
        k_final=min(256, a["docs"]),
        train_queries=a["train_queries"], epochs=a["epochs"])


def main(argv=None):
    # __doc__ IS the epilog: the module docstring and --help can never
    # drift apart (CI smoke-tests --help for every repro.launch CLI)
    ap = argparse.ArgumentParser(
        description="Build a persistent CluSD index offline (cluster, "
                    "pack, serialize + checksummed manifest).",
        epilog=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--out", required=True, help="index output directory")
    ap.add_argument("--variant", default="smoke", choices=("smoke", "full"),
                    help="clusd-msmarco config: smoke (small geometry from "
                         "the flags below) or full (the paper's MS MARCO "
                         "widths; --docs/--clusters are its only cuts)")
    ap.add_argument("--docs", type=int, default=None,
                    help="corpus size (default: 20000 smoke, the config's "
                         "under full)")
    ap.add_argument("--dim", type=int, default=None, help="smoke only (64)")
    ap.add_argument("--clusters", type=int, default=None,
                    help="cluster count (default: 256 smoke, the config's "
                         "under full)")
    ap.add_argument("--vocab", type=int, default=None,
                    help="smoke only (2048)")
    ap.add_argument("--shards", type=int, default=4,
                    help="block shard files (and k-means embedding shards)")
    ap.add_argument("--train-queries", type=int, default=None,
                    help="0 skips LSTM selector training (default: 512 "
                         "smoke, the config's under full)")
    ap.add_argument("--epochs", type=int, default=None,
                    help="default: 40 smoke, the config's under full")
    ap.add_argument("--pq-nsub", type=int, default=0,
                    help="train PQ codebooks with this many subspaces "
                         "(v1: extra pq/ artifacts; v2: the code shards; "
                         "defaults to 8 under --format-version 2)")
    ap.add_argument("--format-version", type=int, default=1, choices=(1, 2),
                    help="1 = float32 block shards, 2 = PQ code shards")
    ap.add_argument("--memmap", action="store_true",
                    help="stage embeddings through an np.memmap and build "
                         "from it (the corpus>RAM path; LSTM label "
                         "generation still uses in-RAM embeddings)")
    ap.add_argument("--chunk-docs", type=int, default=0,
                    help="bound every embedding read to this many rows "
                         "(0 = per-shard granularity)")
    ap.add_argument("--kmeans-iters", type=int, default=15)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    place_compile_cache()

    cfg = build_cfg(args)
    t0 = time.perf_counter()
    print(f"corpus: {cfg.n_docs} docs x {cfg.dim} dim ...", flush=True)
    corpus = synth_corpus(args.seed, cfg.n_docs, cfg.dim, cfg.vocab)
    emb = np.asarray(corpus.embeddings)
    if args.memmap:
        staged = os.path.join(tempfile.mkdtemp(), "embeddings.bin")
        np.asarray(emb, np.float32).tofile(staged)
        emb = np.memmap(staged, dtype=np.float32, mode="r", shape=emb.shape)
        print(f"staged embeddings -> np.memmap {staged}", flush=True)

    shard_docs = math.ceil(cfg.n_docs / max(1, args.shards))
    if args.chunk_docs > 0:
        shard_docs = min(shard_docs, args.chunk_docs)
    print(f"clustering: {cfg.n_clusters} clusters over "
          f"{args.shards} embedding shard(s) ...", flush=True)
    index = index_lib.build_index_offline(
        cfg, jax.random.key(args.seed), emb, corpus.doc_terms,
        corpus.doc_weights, shard_docs=shard_docs,
        kmeans_iters=args.kmeans_iters)

    if cfg.train_queries > 0:
        print(f"training LSTM selector on {cfg.train_queries} queries ...",
              flush=True)
        # labels need full dense retrieval — offline-only embedding use
        index.embeddings = corpus.embeddings
        tq = synth_queries(args.seed + 1, corpus, cfg.train_queries)
        _, feats, labels = tl.make_labels(cfg, index, tq.q_dense, tq.q_terms,
                                          tq.q_weights)
        index.lstm_params, hist = tl.train_selector(
            cfg, jax.random.key(args.seed + 2), np.asarray(feats),
            np.asarray(labels))
        print(f"  loss {hist[0]:.4f} -> {hist[-1]:.4f}", flush=True)
        index.embeddings = None

    pq_nsub = args.pq_nsub or (8 if args.format_version == 2 else 0)
    if pq_nsub > 0:
        from repro.core import quant as quant_lib
        print(f"training PQ codebooks (nsub={pq_nsub}) ...", flush=True)
        # streaming train/encode: bounded-chunk reads off the (possibly
        # memmap) source, so the v2 path never materializes the matrix
        index.quantizer = quant_lib.train_pq_stream(
            jax.random.key(args.seed + 3), emb, pq_nsub,
            chunk_docs=args.chunk_docs or index_lib.builder.DEFAULT_CHUNK_DOCS)

    manifest = index_lib.write_index(
        args.out, cfg, index, emb, n_shards=args.shards,
        format_version=args.format_version,
        chunk_docs=args.chunk_docs or index_lib.builder.DEFAULT_CHUNK_DOCS,
        extra={"corpus": {"kind": "synthetic", "seed": args.seed,
                          "n_docs": cfg.n_docs, "dim": cfg.dim,
                          "vocab": cfg.vocab}})
    wall = time.perf_counter() - t0
    g = manifest["geometry"]
    print(f"wrote {args.out} (format v{manifest['format_version']}): "
          f"{manifest['total_bytes'] / 2**20:.1f} MiB, "
          f"{len(manifest['block_shards'])} block shard(s), "
          f"N={g['n_clusters']} cap={g['cap']} dim={g['dim']}, "
          f"lstm={'yes' if manifest['lstm'] else 'no'}, "
          f"pq={'yes' if manifest['pq'] else 'no'}, "
          f"build {wall:.1f}s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
