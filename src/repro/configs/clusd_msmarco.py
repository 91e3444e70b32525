"""The paper's own system config: CluSD on MS MARCO passages.

N=8192 clusters, n=32 LSTM candidates, v=6 sparse-result bins, u=6
inter-cluster bins, m=128 neighbor graph, hidden=32, theta=0.02,
5000 training queries, 150 epochs (paper §2-3). `ondisk()` mirrors the
Table-4 setting (N=65000, smaller clusters for block-I/O control).
"""

import dataclasses

from repro.configs.base import CluSDConfig


def full() -> CluSDConfig:
    return CluSDConfig(name="clusd-msmarco")


def from_cli(args) -> CluSDConfig:
    """The launch CLIs' `--variant full` config: full() cut only in corpus
    size and cluster count (--docs / --clusters) and, for time, in selector
    training (--train-queries / --epochs); an unset flag keeps the paper's
    value. Widths are not cuts: --dim and --vocab are refused."""
    fixed = [f"--{k}" for k in ("dim", "vocab")
             if getattr(args, k, None) is not None]
    if fixed:
        raise SystemExit(f"{'/'.join(fixed)} fixed by --variant full (only "
                         "--docs and --clusters cut it)")
    cuts = {"n_docs": args.docs, "n_clusters": args.clusters,
            "train_queries": args.train_queries, "epochs": args.epochs}
    return dataclasses.replace(
        full(), **{k: v for k, v in cuts.items() if v is not None})


# the launch CLIs' `--variant smoke` sizes where a flag is left unset
SMOKE_CLI_DEFAULTS = {"docs": 20000, "dim": 64, "clusters": 256,
                      "vocab": 2048, "train_queries": 512, "epochs": 40}


def smoke_sizes(args) -> dict:
    """`--variant smoke`: each size flag's value, or its default above (a
    CLI without the flag gets the default)."""
    return {k: v if getattr(args, k, None) is None else getattr(args, k)
            for k, v in SMOKE_CLI_DEFAULTS.items()}


def ondisk() -> CluSDConfig:
    return CluSDConfig(name="clusd-msmarco-ondisk", n_clusters=65000,
                       max_selected=64)


def repllama() -> CluSDConfig:
    # Table 5: RepLLaMA 4096-dim embeddings, N=60000.
    return CluSDConfig(name="clusd-repllama", dim=4096, n_clusters=60000,
                       max_selected=64)


def smoke() -> CluSDConfig:
    return CluSDConfig(
        name="clusd-smoke",
        n_docs=4096, dim=32, n_clusters=64, vocab=512,
        max_postings=256, doc_terms=16,
        k_sparse=128, bins=(10, 25, 50, 128), n_candidates=16,
        lstm_hidden=16, n_neighbors=16, u_bins=4,
        max_selected=8, k_final=64,
        train_queries=64, epochs=10,
    )
