"""Distributed CluSD tests.

Two tiers, skipped independently:

  * pure-host invariants of the blocked layout + shard ownership
    (build_blocked_index, shard_ranges/owner_of,
    shard_postings_by_owner) — run everywhere, no mesh needed; these
    pin the non-divisible-N ownership fix (the old
    `cluster // (N // n_shards)` rule assigned tail clusters to a
    nonexistent shard and silently dropped their postings)
  * multi-device mesh tests (8 virtual CPU devices via subprocess so the
    main pytest process keeps its single-device view)
"""

import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

def _run(code):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_sharded_train_step_matches_single_device():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.configs import get_config
        from repro.models import transformer as tf
        from repro.models.sharding import rules_ctx, named_sharding
        from repro.optim import adamw_init

        cfg = dataclasses.replace(get_config("qwen2-1.5b", "smoke"),
                                  dtype="float32", param_dtype="float32",
                                  n_heads=4, n_kv_heads=2)
        params = tf.init_params(cfg, jax.random.key(0))
        opt = adamw_init(params)
        B, S = 8, 32
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size,
                                                    (B, S)), jnp.int32)}
        batch["labels"] = batch["tokens"]
        step = tf.make_train_step(cfg)
        # single device
        p1, _, m1 = jax.jit(step)(params, opt, batch)
        # sharded
        mesh = jax.make_mesh((2, 4), ("data", "model"),
            axis_types=(jax.sharding.AxisType.Auto,) * 2)
        with rules_ctx({}, mesh=mesh):
            psh = tf.param_shardings(cfg, mesh)
            osh = {"mu": psh, "nu": psh,
                   "count": NamedSharding(mesh, P())}
            bsh = {k: named_sharding(mesh, "batch", None) for k in batch}
            p2, _, m2 = jax.jit(step, in_shardings=(psh, osh, bsh))(
                params, opt, batch)
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4, (
            float(m1["loss"]), float(m2["loss"]))
        d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32)))), p1, p2)
        worst = max(jax.tree.leaves(d))
        assert worst < 5e-4, worst
        print("OK sharded == single", float(m1["loss"]))
    """)
    assert "OK sharded" in out


def test_distributed_clusd_serve_matches_host():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.data import synth_corpus, synth_queries, mrr_at
        from repro.core import clusd as cl, distributed as dist
        from repro.core import train_lstm as tl

        cfg = get_config("clusd-msmarco", "smoke")
        corpus = synth_corpus(0, cfg.n_docs, cfg.dim, cfg.vocab)
        index = cl.build_index(cfg, jax.random.key(0), corpus.embeddings,
                               corpus.doc_terms, corpus.doc_weights)
        tq = synth_queries(1, corpus, 128)
        _, feats, labels = tl.make_labels(cfg, index, tq.q_dense, tq.q_terms,
                                          tq.q_weights)
        index.lstm_params, _ = tl.train_selector(
            cfg, jax.random.key(2), np.asarray(feats), np.asarray(labels),
            epochs=10)
        bidx = dist.build_blocked_index(cfg, index)
        mesh = jax.make_mesh((2, 4), ("data", "model"),
            axis_types=(jax.sharding.AxisType.Auto,) * 2)
        pd, pw = dist.shard_postings_by_owner(bidx, 4)
        N, cap = bidx.blocks.shape[:2]
        serve = dist.make_serve_step(cfg, mesh,
            (N, cap, cfg.dim, cfg.vocab, pd.shape[2],
             bidx.neighbor_ids.shape[1]), feats.shape[-1])
        test_q = synth_queries(7, corpus, 16)
        ids, scores = jax.jit(serve)(
            jnp.asarray(bidx.blocks), jnp.asarray(pd), jnp.asarray(pw),
            jnp.asarray(bidx.centroids), jnp.asarray(bidx.neighbor_ids),
            jnp.asarray(bidx.neighbor_sims), index.lstm_params,
            test_q.q_dense, test_q.q_terms, test_q.q_weights)
        new_to_old = np.full(N * cap, -1, np.int64)
        o2n = bidx.old_to_new
        new_to_old[o2n[o2n >= 0]] = np.nonzero(o2n >= 0)[0]
        ids_orig = new_to_old[np.asarray(ids)]
        ids1, _, _ = cl.retrieve(cfg, index, test_q.q_dense, test_q.q_terms,
                                 test_q.q_weights)
        overlap = np.mean([len(set(ids_orig[b, :10])
                               & set(np.asarray(ids1)[b, :10])) / 10
                           for b in range(16)])
        assert overlap > 0.9, overlap
        print("OK dist overlap", overlap)
    """)
    assert "OK dist overlap" in out


def test_compressed_psum_shardmap():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.optim.compression import compressed_psum, ef_init
        mesh = jax.make_mesh((8,), ("data",),
            axis_types=(jax.sharding.AxisType.Auto,))
        g = {"w": jnp.asarray(np.random.default_rng(0).standard_normal(
            (8, 64)), jnp.float32)}
        e = {"w": jnp.zeros((8, 64), jnp.float32)}

        def f(g, e):
            return compressed_psum(g, e, "data", 8)

        out, new_e = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(P("data"), P("data")),
            out_specs=(P("data"), P("data"))))(
            {"w": g["w"]}, {"w": e["w"]})
        # each shard's dequantized sum approximates the true mean*8
        true = jnp.sum(g["w"], axis=0, keepdims=True)
        got = out["w"][0:1]
        err = float(jnp.max(jnp.abs(got - true)))
        scale = float(jnp.max(jnp.abs(true))) + 1e-6
        assert err / scale < 0.15, err / scale
        print("OK compressed psum", err / scale)
    """)
    assert "OK compressed psum" in out


def test_elastic_checkpoint_restore_new_mesh():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.checkpoint import save_checkpoint, restore_checkpoint
        mesh8 = jax.make_mesh((8,), ("data",),
            axis_types=(jax.sharding.AxisType.Auto,))
        mesh4 = jax.make_mesh((4, 2), ("data", "model"),
            axis_types=(jax.sharding.AxisType.Auto,) * 2)
        w = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                           NamedSharding(mesh8, P("data", None)))
        tree = {"w": w}
        with tempfile.TemporaryDirectory() as d:
            save_checkpoint(d, 5, tree)
            new_sh = {"w": NamedSharding(mesh4, P("model", "data"))}
            restored, _ = restore_checkpoint(d, 5, tree, new_sh)
            np.testing.assert_array_equal(np.asarray(restored["w"]),
                                          np.asarray(w))
            assert restored["w"].sharding == new_sh["w"]
        print("OK elastic restore")
    """)
    assert "OK elastic restore" in out


# ---------------------------------------------------------------------------
# pure-host layout + ownership invariants (no mesh; run on any jax)
# ---------------------------------------------------------------------------

def _tiny_blocked():
    from repro.configs import get_config
    from repro.core import clusd as cl, distributed as dist
    from repro.data import synth_corpus

    cfg = dataclasses.replace(get_config("clusd-msmarco", "smoke"),
                              n_docs=300, dim=16, n_clusters=9, vocab=128,
                              max_postings=64, k_sparse=32,
                              bins=(3, 6, 9), n_candidates=6,
                              max_selected=3, n_neighbors=4, u_bins=3,
                              k_final=16)
    corpus = synth_corpus(0, cfg.n_docs, cfg.dim, cfg.vocab)
    index = cl.build_index(cfg, jax.random.key(0), corpus.embeddings,
                           corpus.doc_terms, corpus.doc_weights)
    return cfg, corpus, index, dist.build_blocked_index(cfg, index)


def test_blocked_index_roundtrip_invariants():
    """doc id = c*cap + s renumbering is a bijection on live docs, blocks
    carry the right embeddings, and postings renumber consistently."""
    _, corpus, index, bidx = _tiny_blocked()
    cd = np.asarray(index.cluster_docs)
    N, cap = cd.shape
    assert bidx.blocks.shape[:2] == (N, cap)
    # bijection: every live doc appears exactly once, at the slot its
    # cluster_docs entry names
    o2n = bidx.old_to_new
    live = o2n >= 0
    assert live.sum() == (cd >= 0).sum()
    assert len(np.unique(o2n[live])) == int(live.sum())
    c_idx, s_idx = np.nonzero(cd >= 0)
    np.testing.assert_array_equal(
        o2n[cd[c_idx, s_idx]], c_idx * cap + s_idx)
    # blocked id -> cluster is pure arithmetic
    np.testing.assert_array_equal((o2n[live] // cap),
                                  np.asarray(index.doc_cluster)[live])
    # block contents match the embeddings they renumber
    emb = np.asarray(corpus.embeddings)
    np.testing.assert_array_equal(bidx.blocks[c_idx, s_idx],
                                  emb[cd[c_idx, s_idx]])
    np.testing.assert_array_equal(bidx.valid, cd >= 0)
    # postings renumbered with pads preserved
    pd_old = np.asarray(index.sparse_index.postings_docs)
    assert bidx.postings_docs.shape == pd_old.shape
    np.testing.assert_array_equal(bidx.postings_docs < 0, pd_old < 0)
    real = pd_old >= 0
    np.testing.assert_array_equal(bidx.postings_docs[real],
                                  o2n[pd_old[real]])


def test_shard_ranges_balanced_total():
    from repro.core import distributed as dist
    for n_clusters in (1, 7, 8, 9, 64, 65):
        for n_shards in (1, 2, 3, 4, 8):
            if n_clusters < n_shards:
                with pytest.raises(ValueError):
                    dist.shard_ranges(n_clusters, n_shards)
                continue
            ranges = dist.shard_ranges(n_clusters, n_shards)
            assert ranges[0][0] == 0 and ranges[-1][1] == n_clusters
            sizes = [hi - lo for lo, hi in ranges]
            assert all(a == b for (_, a), (b, _)
                       in zip(ranges[:-1], ranges[1:]))   # no gaps
            assert max(sizes) - min(sizes) <= 1           # balanced
            # ownership total + consistent with the ranges
            owner = dist.owner_of(np.arange(n_clusters), ranges)
            for s, (lo, hi) in enumerate(ranges):
                np.testing.assert_array_equal(owner[lo:hi], s)
    with pytest.raises(ValueError):
        dist.owner_of([7], dist.shard_ranges(7, 2))       # id == n_clusters


def test_shard_postings_by_owner_covers_non_divisible():
    """Every posting lands on exactly one shard — the shard owning its
    doc's cluster — including when n_clusters % n_shards != 0 (the old
    owner rule silently dropped the tail clusters' postings)."""
    from repro.core import distributed as dist
    _, _, _, bidx = _tiny_blocked()
    N, cap = bidx.blocks.shape[:2]
    assert N == 9
    for n_shards in (2, 3, 4):             # 9 % 2, 9 % 4 != 0
        docs, ws = dist.shard_postings_by_owner(bidx, n_shards)
        V = bidx.postings_docs.shape[0]
        assert docs.shape[:2] == (V, n_shards)
        ranges = dist.shard_ranges(N, n_shards)
        total = 0
        for t in range(V):
            orig = bidx.postings_docs[t]
            orig_real = np.sort(orig[orig >= 0])
            got = docs[t][docs[t] >= 0]
            # nothing dropped, nothing duplicated
            np.testing.assert_array_equal(np.sort(got), orig_real)
            total += len(got)
            # every posting sits on the shard owning its cluster
            for s in range(n_shards):
                mine = docs[t, s][docs[t, s] >= 0]
                if len(mine):
                    np.testing.assert_array_equal(
                        dist.owner_of(mine // cap, ranges), s)
                # weights travel with their docs
                k = len(mine)
                assert (ws[t, s, k:] == 0).all()
        assert total == int((bidx.postings_docs >= 0).sum())
