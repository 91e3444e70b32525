"""Each layer's device time, the device's idle time inside the engine's
batches, how late the host notices a finished program, and the engine's
host->device bytes and selection size, from one traced window of a cell:

    python3 benchmarks/chip/layers.py --workload <name> --seed <n> \
        --seconds <s> [--save DIR]

One set-up and warm-up as `run.py` makes them, then one window of the
cell's traffic with the engine's spans on (sample rate 1), profiled like
a `--trace 1` run. Prints one JSON line: the numbers below, each a mean
per batch over the profiled part of the window, where a batch is one
`clusd.batch` region of the engine's host timeline (repro.obs Tracer with
the profiler hook) that starts in it:

  <scope>.device_ms     union of the device's op intervals in the named
                        scope (sparse_topk, stage1, selector,
                        dense_score, fuse_topk); ops in none are
                        `unscoped`, listed by name under `unscoped_ops`
  engine.idle_in_batch_ms  device idle time inside the batch region
  engine.wait_overrun_ms   over the batch's device-sync regions
                        (`clusd.stage1_wait`, `stage2_wait`, `tail_wait`,
                        `device_wait`; not `lock_wait`): the
                        wait's end less the end of the last program
                        that ended inside it (0 where none did); host
                        and device clocks agree to about a millisecond
  engine.h2d_bytes      the engine's `serve.h2d_bytes` over the window
                        per batch
  selector.selected_per_query  `serve.clusters_selected` over
                        `serve.selected_queries` in the window

Host regions are read from the trace's host plane, on the trace's own
clock. An op's scope is its `op_name` metadata (`jit(clusd_<stage>)/
<scope>/...`), which a TPU trace keeps as the `tf_op` stat of the op's
event metadata (a fusion carries its root's); ops the compiler made
without one take the scope of the op before them (scoped_ops).
`--save DIR` keeps the raw trace, gzipped. The reductions are pure
functions over (t0_ns, t1_ns, name) intervals, like tracereduce.py's.
"""

import argparse
import gzip
import json
import shutil
import struct
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from tracereduce import DEVICE_PREFIX, busy_ns, short_op  # noqa: E402

SCOPES = ("sparse_topk", "stage1", "selector", "dense_score", "fuse_topk")
REGION_PREFIX = "clusd."
BATCH = "clusd.batch"
# the engine's device syncs (repro.engine.server), timeline regions
WAITS = tuple(REGION_PREFIX + w for w in (
    "stage1_wait", "stage2_wait", "tail_wait", "device_wait"))


# -- the trace file ----------------------------------------------------------
# jax.profiler.ProfileData does not expose the stats of an event's
# metadata, where a TPU trace keeps an op's HLO attributes, so the device
# planes are read from the XSpace protobuf directly (tsl xplane.proto:
# XSpace.planes 1; XPlane id 1, name 2, lines 3, event_metadata 4,
# stat_metadata 5; XLine name 2, timestamp_ns 3, events 4; XEvent
# metadata_id 1, offset_ps 2, duration_ps 3, stats 4; XStat metadata_id 1,
# double 2, uint64 3, int64 4, str 5, bytes 6, ref 7; XEventMetadata id 1,
# name 2, display_name 4, stats 5; XStatMetadata id 1, name 2).

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo=0, hi=None):
    """(field number, value) of the message in buf[lo:hi]; a
    length-delimited value is its (start, end) in buf."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 1:
            v, i = buf[i:i + 8], i + 8
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _str(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _stat(buf, span, stat_names):
    """(name, value) of one XStat."""
    mid, val = None, None
    for f, v in _fields(buf, *span):
        if f == 1:
            mid = v
        elif f == 2:
            val = struct.unpack("<d", bytes(v))[0]
        elif f in (3, 4):
            val = v
        elif f in (5, 6):
            val = _str(buf, v)
        elif f == 7:
            val = stat_names.get(v)
    return stat_names.get(mid, str(mid)), val


def read_device_lines(data):
    """{plane name: {line name: [(t0_ns, t1_ns, name, stats)]}} of the
    "XLA Ops" and "XLA Modules" lines of the device planes of a
    serialized XSpace; `stats` holds the event's own
    stats over those of its metadata. Times are the line's timestamp_ns
    plus the event's offset, as jax.profiler.ProfileData gives them."""
    buf = memoryview(data)
    out = {}
    for f, span in _fields(buf):
        if f != 1:
            continue
        name, line_spans, meta_spans, stat_spans = None, [], [], []
        for pf, v in _fields(buf, *span):
            if pf == 2:
                name = _str(buf, v)
            elif pf == 3:
                line_spans.append(v)
            elif pf == 4:
                meta_spans.append(v)
            elif pf == 5:
                stat_spans.append(v)
        if name is None or not name.startswith(DEVICE_PREFIX):
            continue
        stat_names = {}
        for s in stat_spans:
            for mf, mv in _fields(buf, *s):
                if mf == 2:
                    sid = sname = None
                    for sf, sv in _fields(buf, *mv):
                        if sf == 1:
                            sid = sv
                        elif sf == 2:
                            sname = _str(buf, sv)
                    stat_names[sid] = sname
        meta = {}
        for s in meta_spans:
            for mf, mv in _fields(buf, *s):
                if mf != 2:
                    continue
                mid, mname, mstats = None, "", {}
                for ef, ev in _fields(buf, *mv):
                    if ef == 1:
                        mid = ev
                    elif ef == 2:
                        mname = _str(buf, ev)
                    elif ef == 5:
                        k, val = _stat(buf, ev, stat_names)
                        mstats[k] = val
                meta[mid] = (mname, mstats)
        plane = out.setdefault(name, {})
        for ls in line_spans:
            lname, ts, events = None, 0, []
            for lf, lv in _fields(buf, *ls):
                if lf == 2:
                    lname = _str(buf, lv)
                elif lf == 3:
                    ts = lv
                elif lf == 4:
                    events.append(lv)
            if lname not in ("XLA Ops", "XLA Modules"):
                continue
            dest = plane.setdefault(lname, [])
            for es in events:
                mid, off, dur, stats = None, 0, 0, {}
                for ef, ev in _fields(buf, *es):
                    if ef == 1:
                        mid = ev
                    elif ef == 2:
                        off = ev
                    elif ef == 3:
                        dur = ev
                    elif ef == 4:
                        k, val = _stat(buf, ev, stat_names)
                        stats[k] = val
                mname, mstats = meta.get(mid, ("", {}))
                t0 = ts + off / 1e3
                dest.append((t0, t0 + dur / 1e3, mname, {**mstats, **stats}))
    for plane in out.values():
        for evs in plane.values():
            evs.sort(key=lambda e: e[:2])
    return out


def host_regions(pd):
    """[(t0_ns, t1_ns, name)] of the host planes' `clusd.` events (a
    jax.profiler.ProfileData), sorted."""
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.start_ns, e.end_ns, e.name)
                           for e in line.events
                           if e.name.startswith(REGION_PREFIX))
    return sorted(out)


# -- scopes ------------------------------------------------------------------

def scope_of(op_name):
    """The first path component of an op_name that is a stage scope, or
    None: 'jit(clusd_stage1)/sparse_topk/jit(sort)/sort' -> sparse_topk."""
    for part in (op_name or "").split("/"):
        if part in SCOPES:
            return part
    return None


def scoped_ops(ops, modules):
    """[(t0, t1, scope or None, op)] of the device's ops, in time order.
    An op's scope is read from its `tf_op` stat (its op_name); an op the
    compiler made without one (the TPU's top-k sorts, a scatter's loop)
    takes the scope of the op that ran before it in the same program
    execution, as a program's ops run in schedule order."""
    starts = sorted(m[0] for m in modules)
    out, j, last = [], 0, None
    for t0, t1, name, stats in sorted(ops, key=lambda o: o[:2]):
        while j < len(starts) and starts[j] <= t0:
            j, last = j + 1, None           # a new program execution
        sc = scope_of(stats.get("tf_op")) or last
        last = sc
        out.append((t0, t1, sc, short_op(name)))
    return out


# -- reductions --------------------------------------------------------------

def batches_in(regions, lo, hi):
    """The `clusd.batch` regions that start in [lo, hi)."""
    return [r for r in regions if r[2] == BATCH and lo <= r[0] < hi]


def scope_ms(scoped, n_batches, lo, hi):
    """{scope: device ms per batch}, with `unscoped` for ops in none."""
    out = {}
    for sc in (*SCOPES, None):
        iv = [o for o in scoped if o[2] == sc]
        out[sc or "unscoped"] = busy_ns(iv, lo, hi) / n_batches / 1e6
    return out


def unscoped_ops(scoped, lo, hi, n=10):
    """[[op, ms in the window]] of the n unscoped ops that took longest."""
    tot = {}
    for t0, t1, sc, op in scoped:
        if sc is None and t1 > lo and t0 < hi:
            tot[op] = tot.get(op, 0) + min(t1, hi) - max(t0, lo)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e6] for k, v in best]


def idle_in_batch_ms(ops, batches):
    """Mean device idle ms inside each batch region."""
    if not batches:
        return None
    return sum((b1 - b0) - busy_ns(ops, b0, b1)
               for b0, b1, _ in batches) / len(batches) / 1e6


def wait_overrun_ms(regions, modules, batches):
    """Mean over batches of the summed overrun of their device-sync
    regions (WAITS): the wait's end less the end of the last program that
    ended inside it (0 where none did)."""
    if not batches:
        return None
    waits = [r for r in regions if r[2] in WAITS]
    ends = sorted(m[1] for m in modules)
    total = 0.0
    for b0, b1, _ in batches:
        for w0, w1, _ in waits:
            if not b0 <= w0 < b1:
                continue
            inside = [e for e in ends if w0 <= e <= w1]
            if inside:
                total += w1 - inside[-1]
    return total / len(batches) / 1e6


def counter_deltas(before, after, names):
    """{name: after - before} of registry counter snapshots."""
    return {n: after.get(n, 0) - before.get(n, 0) for n in names}


def reduce_window(pd, device, lo, hi):
    """The numbers of one traced window [lo, hi) (trace clock, ns): pd a
    jax.profiler.ProfileData, device read_device_lines() of the same file.
    -> dict, or None where the window holds no batch or no device op."""
    regions = host_regions(pd)
    batches = batches_in(regions, lo, hi)
    planes = sorted(device)
    if not batches or not planes:
        return None
    lines = device[planes[0]]
    ops = lines.get("XLA Ops", [])
    modules = [m[:3] for m in lines.get("XLA Modules", [])]
    if not ops:
        return None
    scoped = scoped_ops(ops, modules)
    n = len(batches)
    by_scope = scope_ms(scoped, n, lo, hi)
    out = {f"{k}.device_ms": v for k, v in by_scope.items()}
    busy = busy_ns(ops, lo, hi)
    out["busy_ms"] = busy / n / 1e6
    # the share of busy time in some stage scope (a union: ops that
    # overlap across scopes count once)
    out["scoped_share"] = busy_ns([o for o in scoped if o[2]], lo, hi) \
        / busy if busy else None
    out["engine.idle_in_batch_ms"] = idle_in_batch_ms(ops, batches)
    out["engine.wait_overrun_ms"] = wait_overrun_ms(regions, modules,
                                                    batches)
    out["batches"] = n
    out["unscoped_ops"] = unscoped_ops(scoped, lo, hi)
    out["programs"] = sorted({m[2].split("(", 1)[0] for m in modules})
    return out


# -- one traced window on the chip --------------------------------------------

COUNTERS = ("serve.h2d_bytes", "serve.batches", "serve.clusters_selected",
            "serve.selected_queries")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--save", default=None,
                    help="directory to keep the raw trace in (gzip)")
    args = ap.parse_args(argv)
    import numpy as np

    import deploy
    import harness
    import loadgen
    import run
    cell = harness.resolve(args.workload)
    sys.path.insert(0, str(harness.ROOT / "src"))
    import jax
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    from repro.obs import Tracer
    from repro.common.compile_cache import place_compile_cache
    place_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    run.run_as_stated()
    conf, traffic = cell.config, cell.traffic
    batch = int(traffic["batch"])
    tracer = Tracer(sample_rate=0.0, capacity=1 << 16)
    mod = harness.load_module(cell.deployment_file)
    dep = mod.build(conf, tracer=tracer, pools={
        "window": (args.seed, int(traffic["pool"]), deploy.WINDOW),
        "warm": (conf["data_seed"], run.WARM_QUERIES, deploy.WARM)})
    run.warm_up(dep, mod, batch, run.CompileClock())
    qd, qt, qw, _ = dep.pools["window"]
    reg = dep.engine.metrics
    prof = run.Profile(args.seconds)
    before = reg.snapshot()["counters"]
    tracer.sample_rate = 1.0
    try:
        window = loadgen.run(
            traffic, lambda f, k: dep.serve(qd[f:f + k], qt[f:f + k],
                                            qw[f:f + k]),
            len(qd), args.seconds, args.seed, on_open=prof.arm)
    finally:
        tracer.sample_rate = 0.0
        after = reg.snapshot()["counters"]
        dep.close()
    path = prof.path()
    data = Path(path).read_bytes()
    shutil.rmtree(prof.dir, ignore_errors=True)
    if args.save:
        Path(args.save).mkdir(parents=True, exist_ok=True)
        with gzip.open(Path(args.save) / (Path(path).name + ".gz"),
                       "wb") as f:
            f.write(data)
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(data)
    hosts = host_regions(pd)
    # the profiled part: from the first batch region to the last one's end
    lo = min((r[0] for r in hosts if r[2] == BATCH), default=0)
    hi = max((r[1] for r in hosts if r[2] == BATCH), default=0)
    out = reduce_window(pd, read_device_lines(data), lo, hi) or {}
    d = counter_deltas(before, after, COUNTERS)
    out["engine.h2d_bytes"] = (d["serve.h2d_bytes"] / d["serve.batches"]
                               if d["serve.batches"] else None)
    out["selector.selected_per_query"] = (
        d["serve.clusters_selected"] / d["serve.selected_queries"]
        if d["serve.selected_queries"] else None)
    out.update(workload=cell.name, seed=args.seed, seconds=args.seconds,
               queries=sum(r.n for r in window.requests),
               window_latency_p50_ms=float(np.percentile(
                   window.latencies_ms(), 50)))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
