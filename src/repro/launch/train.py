"""End-to-end training driver (CPU-scale by default; the same code path the
production mesh would run — select any arch with --arch).

Runs inside the fault-tolerant restartable loop: periodic async sharded
checkpoints, simulated-failure injection for drills, straggler monitoring.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b \
      --variant smoke --steps 100 --batch 8 --seq 128 [--fail-at 37]
"""

import argparse
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.compile_cache import place_compile_cache
from repro.common.metrics import MetricLogger
from repro.configs import get_config
from repro.configs.base import TrainConfig
from repro.data.pipeline import Prefetcher, lm_synthetic_batches
from repro.data.recsys_stream import RecsysStream
from repro.data.graphs import synth_molecules
from repro.optim import adamw_init, make_schedule
from repro.runtime.fault import FailureInjector, restartable_train


def main():
    # __doc__ IS the epilog: the module docstring and --help can never
    # drift apart (CI smoke-tests --help for every repro.launch CLI)
    ap = argparse.ArgumentParser(
        description="Fault-tolerant end-to-end model training driver "
                    "(LM/recsys/GNN archs; the CluSD selector has its own "
                    "driver: repro.launch.train_selector).",
        epilog=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--variant", default="smoke")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--fail-at", type=int, nargs="*", default=[])
    ap.add_argument("--log", default=None)
    args = ap.parse_args()
    place_compile_cache()

    cfg = get_config(args.arch, args.variant)
    tc = TrainConfig(lr=args.lr, total_steps=args.steps,
                     warmup_steps=max(args.steps // 20, 1))
    sched = make_schedule("cosine", tc.lr, tc.warmup_steps, tc.total_steps)
    rng = jax.random.key(0)
    logger = MetricLogger(args.log)

    if cfg.family == "lm":
        from repro.models import transformer as model
        params = model.init_params(cfg, rng)
        step_fn_inner = jax.jit(model.make_train_step(cfg, sched, tc))

        def batches_fn(start):
            return iter(Prefetcher(lm_synthetic_batches(
                cfg.vocab_size, args.batch, args.seq,
                args.steps, seed=1000)))
        # deterministic restart: skip consumed batches
        def batches_at(start):
            it = batches_fn(0)
            for _ in range(start):
                next(it)
            return it
    elif cfg.family == "recsys":
        from repro.models import recsys as model
        params = model.init_params(cfg, rng)
        step_fn_inner = jax.jit(model.make_train_step(cfg, tc))

        def batches_at(start):
            stream = RecsysStream(cfg, seed=7)
            def gen():
                for _ in range(start):
                    stream.batch(args.batch)
                while True:
                    yield stream.batch(args.batch)
            return iter(Prefetcher(gen()))
    else:  # gnn
        from repro.models import nequip as model
        params = model.init_params(cfg, rng)
        step_fn_inner = jax.jit(model.make_train_step(cfg, tc))

        def batches_at(start):
            def gen():
                s = start
                while True:
                    yield synth_molecules(1234 + s % 16, 8, 12, 32,
                                          n_species=cfg.n_species)
                    s += 1
            return iter(Prefetcher(gen()))

    opt = adamw_init(params)
    state = {"params": params, "opt": opt}

    def step_fn(state, batch):
        p, o, m = step_fn_inner(state["params"], state["opt"], batch)
        return {"params": p, "opt": o}, m

    state, history, restarts = restartable_train(
        init_state=state, step_fn=step_fn, batches_fn=batches_at,
        total_steps=args.steps, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every,
        failure_injector=FailureInjector(args.fail_at), logger=logger)
    first = [h for h in history if "loss" in h][:3]
    last = [h for h in history if "loss" in h][-3:]
    print(f"done: steps={len(history)} restarts={restarts} "
          f"loss {np.mean([h['loss'] for h in first]):.4f} -> "
          f"{np.mean([h['loss'] for h in last]):.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
