"""shard_map MoE dispatch (§Perf optimized paths) must match the pjit
baseline numerically in the no-capacity-drop regime, for both the
expert-parallel and the few-experts tensor-parallel variants."""

import os
import subprocess
import sys
import textwrap

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


def test_shardmap_moe_matches_baseline():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.models import moe as moe_lib
        mesh = jax.make_mesh((2, 4), ("data", "model"),
            axis_types=(jax.sharding.AxisType.Auto,) * 2)
        rng = np.random.default_rng(0)
        T, d, f, E, K = 64, 32, 16, 8, 2
        x = jnp.asarray(rng.standard_normal((T, d)) * 0.5, jnp.float32)
        rw = jnp.asarray(rng.standard_normal((d, E)) * 0.3, jnp.float32)
        wg = jnp.asarray(rng.standard_normal((E, d, f)) * 0.2, jnp.float32)
        wu = jnp.asarray(rng.standard_normal((E, d, f)) * 0.2, jnp.float32)
        wd = jnp.asarray(rng.standard_normal((E, f, d)) * 0.2, jnp.float32)
        ref, _ = jax.jit(lambda *a: moe_lib.moe_ffn(
            *a, top_k=K, ep=False))(x, rw, wg, wu, wd)
        for fn in (moe_lib.moe_ffn_tp_shardmap, moe_lib.moe_ffn_ep_shardmap):
            got, _ = jax.jit(lambda *a: fn(*a, top_k=K, mesh=mesh))(
                x, rw, wg, wu, wd)
            err = float(jnp.max(jnp.abs(got - ref)))
            assert err < 1e-4, (fn.__name__, err)
        print("OK moe dispatch equivalence")
    """)
    assert "OK moe" in out


def test_shardmap_moe_transformer_grad_flows():
    """Full train step with the shard_map dispatch: finite loss + grads."""
    out = _run("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.models import transformer as tf
        from repro.models.sharding import rules_ctx, named_sharding
        from repro.optim import adamw_init
        from jax.sharding import PartitionSpec as P, NamedSharding

        cfg = dataclasses.replace(get_config("mixtral-8x7b", "smoke"),
                                  moe_impl="tp_shard_map")
        mesh = jax.make_mesh((2, 4), ("data", "model"),
            axis_types=(jax.sharding.AxisType.Auto,) * 2)
        params = tf.init_params(cfg, jax.random.key(0))
        batch = {"tokens": jnp.asarray(np.random.default_rng(0).integers(
            0, cfg.vocab_size, (8, 32)), jnp.int32)}
        batch["labels"] = batch["tokens"]
        with rules_ctx({}, mesh=mesh):
            psh = tf.param_shardings(cfg, mesh)
            osh = {"mu": psh, "nu": psh, "count": NamedSharding(mesh, P())}
            bsh = {k: named_sharding(mesh, "batch", None) for k in batch}
            step = jax.jit(tf.make_train_step(cfg),
                           in_shardings=(psh, osh, bsh))
            p, o, m = step(params, adamw_init(params), batch)
        assert np.isfinite(float(m["loss"])), float(m["loss"])
        print("OK shard_map train step loss", float(m["loss"]))
    """)
    assert "OK shard_map train step" in out
