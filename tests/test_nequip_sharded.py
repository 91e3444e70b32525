"""Owner-sharded NequIP message passing (§Perf) must match the pjit
reference forward, and the edge partitioner must preserve every edge."""

import os
import subprocess
import sys
import textwrap

import numpy as np

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_shard_edges_by_owner_preserves_edges():
    from repro.models.nequip_sharded import shard_edges_by_owner
    rng = np.random.default_rng(0)
    N, E, S = 100, 400, 8
    src = rng.integers(0, N, E).astype(np.int32)
    dst = rng.integers(0, N, E).astype(np.int32)
    es, ed, em = shard_edges_by_owner(src, dst, np.ones(E), N, S)
    kept = [(int(s), int(d)) for sh in range(S)
            for s, d, m in zip(es[sh], ed[sh], em[sh]) if m > 0]
    assert sorted(kept) == sorted(zip(src.tolist(), dst.tolist()))
    # ownership: every kept edge's dst lands in its shard's node range
    n_loc = -(-N // S)
    for sh in range(S):
        d = ed[sh][em[sh] > 0]
        assert ((d // n_loc) == sh).all()


def test_owner_sharded_forward_matches_pjit():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    code = """
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.models import nequip as nq
        from repro.models import nequip_sharded as nqs
        cfg = get_config("nequip", "smoke")
        rng = np.random.default_rng(0)
        N, E = 64, 300
        pos = jnp.asarray(rng.standard_normal((N, 3)) * 2, jnp.float32)
        src = rng.integers(0, N, E).astype(np.int32)
        dst = rng.integers(0, N, E).astype(np.int32)
        params = nq.init_params(cfg, jax.random.key(0))
        batch = {"positions": pos,
                 "species": jnp.asarray(rng.integers(0, 8, N), jnp.int32),
                 "edge_src": jnp.asarray(src), "edge_dst": jnp.asarray(dst),
                 "edge_mask": jnp.ones(E),
                 "graph_id": jnp.zeros(N, jnp.int32),
                 "energy_target": jnp.zeros(1)}
        e_ref = nq.forward(cfg, params, batch)
        mesh = jax.make_mesh((2, 4), ("data", "model"),
            axis_types=(jax.sharding.AxisType.Auto,) * 2)
        es, ed, em = nqs.shard_edges_by_owner(src, dst, np.ones(E), N, 8)
        bs = {k: v for k, v in batch.items()
              if not k.startswith("edge_")}
        bs.update({"edge_src_sharded": jnp.asarray(es),
                   "edge_dst_sharded": jnp.asarray(ed),
                   "edge_mask_sharded": jnp.asarray(em)})
        e_sh = jax.jit(lambda p, b: nqs.forward_sharded(cfg, p, b, mesh))(
            params, bs)
        np.testing.assert_allclose(np.asarray(e_sh), np.asarray(e_ref),
                                   rtol=2e-4, atol=2e-5)
        print("OK owner-sharded == pjit")
    """
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "OK owner-sharded" in r.stdout
