"""Where a whole-process stall goes: one cell's traffic for a window with
a watchdog on, printing one JSON line per stall.

    python3 benchmarks/chip/stalls.py --workload <name> --seconds 40 \
        --out <file for stack dumps>

A heartbeat thread wakes every TICK_S and re-arms `faulthandler`'s
timer; where the heartbeat cannot run for DUMP_AFTER_S (the GIL held by
one thread, or the process off the CPU), faulthandler's own C thread
writes the stack of every Python thread to the dump file. Each request
of the window is timed, with the process's CPU time, its voluntary and
involuntary context switches, and the host's steal time around it, so a
stall reads as host CPU taken away (steal, involuntary switches), the
process busy on its own CPU (CPU time ~ wall time), or waiting (neither).
The benchmark's own runs never run this."""

import argparse
import faulthandler
import json
import os
import resource
import sys
import threading
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

import deploy  # noqa: E402
import harness  # noqa: E402
import run  # noqa: E402

TICK_S = 0.005
DUMP_AFTER_S = 0.05
STALL_FACTOR = 1.5


def steal_ticks():
    """The host's steal time in clock ticks (/proc/stat), or 0."""
    try:
        with open("/proc/stat") as f:
            return int(f.readline().split()[8])
    except (OSError, IndexError, ValueError):
        return 0


def snapshot():
    r = resource.getrusage(resource.RUSAGE_SELF)
    return (time.perf_counter(), r.ru_utime + r.ru_stime, r.ru_nvcsw,
            r.ru_nivcsw, steal_ticks())


def watch(dump_file, stop):
    """Heartbeat: while it runs, faulthandler never fires."""
    late = []
    while not stop.is_set():
        t = time.perf_counter()
        faulthandler.dump_traceback_later(DUMP_AFTER_S, repeat=False,
                                          file=dump_file)
        time.sleep(TICK_S)
        late.append(time.perf_counter() - t - TICK_S)
    faulthandler.cancel_dump_traceback_later()
    return late


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
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
    conf, traffic = cell.config, cell.traffic
    batch = int(traffic["batch"])
    mod = harness.load_module(cell.deployment_file)
    dep = mod.build(conf, tracer=Tracer(), pools={
        "window": (args.seed, int(traffic["pool"]), deploy.WINDOW),
        "warm": (conf["data_seed"], run.WARM_QUERIES, deploy.WARM)})
    run.warm_up(dep, mod, batch, run.CompileClock())
    qd, qt, qw, _ = dep.pools["window"]
    hz = os.sysconf("SC_CLK_TCK")
    rows = []
    stop = threading.Event()
    late = []
    with open(args.out, "w") as dump:
        t = threading.Thread(target=lambda: late.extend(watch(dump, stop)))
        t.start()
        t_end = time.perf_counter() + args.seconds
        first = 0
        try:
            while time.perf_counter() < t_end:
                if first + batch > len(qd):
                    first = 0
                a = snapshot()
                dep.serve(qd[first:first + batch], qt[first:first + batch],
                          qw[first:first + batch])
                rows.append((a, snapshot()))
                first += batch
        finally:
            stop.set()
            t.join()
            dep.close()
    wall = np.array([b[0] - a[0] for a, b in rows])
    med = float(np.median(wall))
    for i, (a, b) in enumerate(rows):
        if wall[i] > STALL_FACTOR * med:
            print(json.dumps({
                "request": i, "at_s": a[0] - rows[0][0][0],
                "wall_ms": 1e3 * wall[i], "median_ms": 1e3 * med,
                "cpu_ms": 1e3 * (b[1] - a[1]),
                "voluntary_switches": b[2] - a[2],
                "involuntary_switches": b[3] - a[3],
                "host_steal_ms": 1e3 * (b[4] - a[4]) / hz}), flush=True)
    late = np.array(late)
    print(json.dumps({
        "requests": len(rows), "median_ms": 1e3 * med,
        "p95_ms": 1e3 * float(np.percentile(wall, 95)),
        "max_ms": 1e3 * float(wall.max()),
        "over_factor": int((wall > STALL_FACTOR * med).sum()),
        "heartbeat_late_max_ms": 1e3 * float(late.max(initial=0.0)),
        "heartbeats_late_over_20ms": int((late > 0.02).sum()),
        "dumps": Path(args.out).read_text().count("Thread 0x")}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
