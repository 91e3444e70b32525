"""Sweep of offered rates for an open-loop cell, to find its knee: the
highest rate at which the completed rate keeps up with the offered one
and the backlog does not grow. Run once on the chip when the rate of an
open-loop traffic file is set:

    python3 benchmarks/chip/sweep.py --workload <name> \
        --rates 50,100,150 --seconds 8

One set-up, then one window per rate with the cell's own traffic at that
rate. Prints one JSON line per rate: offered and completed rate, p50 and
p95 latency, and the backlog trend (median latency of the last tenth of
the requests over that of the first tenth)."""

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

import deploy  # noqa: E402
import harness  # noqa: E402
import loadgen  # noqa: E402
import run  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = harness.resolve(args.workload)
    sys.path.insert(0, str(harness.ROOT / "src"))
    import jax
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    from repro.common.compile_cache import place_compile_cache
    from repro.obs import Tracer
    place_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    run.run_as_stated()
    rates = [float(r) for r in args.rates.split(",")]
    conf, traffic = cell.config, cell.traffic
    batch = int(traffic["batch"])
    n = int(max(rates) * args.seconds * 1.1 + 1) * batch
    mod = harness.load_module(cell.deployment_file)
    dep = mod.build(conf, tracer=Tracer(), pools={
        "window": (args.seed, n, deploy.WINDOW),
        "warm": (conf["data_seed"], run.WARM_QUERIES, deploy.WARM)})
    clock = run.CompileClock()
    run.warm_up(dep, mod, batch, clock)
    qd, qt, qw, _ = dep.pools["window"]
    try:
        for rate in rates:
            t = dict(traffic, rate_qps=rate)
            c0 = clock.count
            with run.GCPauses() as gcp:
                w = loadgen.run(t, lambda f, k: dep.serve(
                    qd[f:f + k], qt[f:f + k], qw[f:f + k]), len(qd),
                    args.seconds, args.seed)
            lat = w.latencies_ms()
            tenth = max(1, len(lat) // 10)
            print(json.dumps({
                "rate": rate, "requests": len(w.requests),
                "completed_per_s": w.completed_in_window() / args.seconds,
                "p50_ms": float(np.percentile(lat, 50)),
                "p95_ms": float(np.percentile(lat, 95)),
                "backlog_trend": float(np.median(lat[-tenth:])
                                       / np.median(lat[:tenth])),
                "late_max_ms": 1e3 * max(w.late_s),
                "compiles": clock.count - c0,
                "gc_max_ms": 1e3 * max(gcp.pauses, default=0.0)}),
                flush=True)
    finally:
        dep.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
