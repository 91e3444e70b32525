"""Run one cell of the chip benchmark once and print its result line.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and per-layer metric readers are
found by name from BENCHMARK.json (harness.py). One run:

  set-up    build the deployment (corpus on the device from the
            configuration's data seed, index and selector through the
            program's builders), draw this seed's queries, and serve warm
            batches until every program the window can use is compiled
            (or loaded from the persistent compile cache)
  window    drive the engine with the traffic mix for --seconds
  after     read device memory, serve the fixed MRR@10 evaluation set,
            close the engine, and compare a seeded sample of the window's
            answers with the configuration's plain reference

--trace 0 prints the cell's end-to-end metrics; --trace 1 turns on the
engine's stage spans for the window, profiles a steady part of it, and
prints the per-layer metrics instead. The numbers compared for `correct`
are printed, each beside its limit, as the last lines of standard error
and under the last key of the result line. With no TPU, or fewer chips
than the cell asks for, the run prints no result and exits non-zero.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import deploy  # noqa: E402
import harness  # noqa: E402
import loadgen  # noqa: E402
import peaks as peaks_lib  # noqa: E402
import reference  # noqa: E402
import tracereduce  # noqa: E402

# the traced part of a --trace 1 window: it opens this far into the
# window and lasts at most TRACE_S (a profile of a whole long window is
# too large to read back within the run's time)
TRACE_AT = 0.3
TRACE_S = 4.0
# marks that put the trace's clock beside perf_counter
SYNC_MARKS = 8
# the measurement's fixed query sets: MRR@10 is taken over EVAL_QUERIES
# (the same set in every run of a configuration), warm-up serves from
# WARM_QUERIES, and `correct` compares a seeded sample of about
# CHECK_QUERIES of the window's answered queries with the reference
EVAL_QUERIES = 1024
WARM_QUERIES = 512
CHECK_QUERIES = 256


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# a sample may leave at most 1 in UNRESOLVED_SHARE of its queries to
# rounding (reference.py); the reference decides which, from the data
UNRESOLVED_SHARE = 10


def run_as_stated():
    """Run the program in the float32 its configurations state: its
    matmuls at the default precision would otherwise take one bfloat16
    pass on a TPU (the program's own parity checks serve under the same
    setting)."""
    import jax
    jax.config.update("jax_default_matmul_precision", "highest")


def in_use_bytes(dev):
    return (dev.memory_stats() or {}).get("bytes_in_use")


class CompileClock:
    """Counts XLA backend compiles (a persistent-cache hit is none)."""

    def __init__(self):
        import jax
        self.count = 0
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.secs += secs


class GCPauses:
    """Python garbage-collection pauses while open (they stall the load
    generator and the engine's host code alike)."""

    def __init__(self):
        self.pauses = []
        self._t = None

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append(time.perf_counter() - self._t)

    def __enter__(self):
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)

    def summary(self):
        p = self.pauses
        return (f"{len(p)} GC pauses, {1e3 * sum(p):.1f} ms in all, "
                f"longest {1e3 * max(p, default=0.0):.1f} ms")


class Profile:
    """The profiler over part of the window, started and stopped from
    timer threads so the traffic keeps its schedule."""

    def __init__(self, seconds):
        import jax
        self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        self.span = min(TRACE_S, seconds * 0.4)
        self.at = seconds * TRACE_AT
        self.t = [None, None]
        self.opts = jax.profiler.ProfileOptions()
        self.opts.python_tracer_level = 0
        self.done = threading.Event()
        self.sync = []              # perf_counter_ns of each sync mark

    def arm(self, t0):
        import jax

        def start():
            jax.profiler.start_trace(self.dir, profiler_options=self.opts)
            for _ in range(SYNC_MARKS):
                t = time.perf_counter_ns()
                with jax.profiler.TraceAnnotation("bench.sync"):
                    self.sync.append(t)
            self.t[0] = time.perf_counter()

        def stop():
            self.t[1] = time.perf_counter()
            jax.profiler.stop_trace()
            self.done.set()

        delay = self.at - (time.perf_counter() - t0)
        threading.Timer(max(0.0, delay), start).start()
        threading.Timer(max(0.0, delay) + self.span, stop).start()

    def path(self):
        self.done.wait()
        found = sorted(Path(self.dir).rglob("*.xplane.pb"))
        return found[-1] if found else None


def mrr_at_10(ids, rel):
    hit = np.asarray(ids)[:, :10] == np.asarray(rel)[:, None]
    rank = np.argmax(hit, axis=1) + 1.0
    return float(np.where(hit.any(axis=1), 1.0 / rank, 0.0).mean())


def serve_pool(dep, pool, batch):
    qd, qt, qw, _ = pool
    out = [dep.serve(qd[i:i + batch], qt[i:i + batch], qw[i:i + batch])
           for i in range(0, len(qd) - batch + 1, batch)]
    return np.concatenate([o[0] for o in out])


def warm_up(dep, mod, batch, clock):
    """Serve warm batches until every program the window can use exists:
    plain batches until two in a row compile nothing, then the
    deployment's own (e.g. one per unique-block bucket)."""
    qd, qt, qw, _ = pool = dep.pools["warm"]
    quiet, i = 0, 0
    while quiet < 2 and (i + 1) * batch <= len(qd):
        c = clock.count
        dep.serve(qd[i * batch:(i + 1) * batch],
                  qt[i * batch:(i + 1) * batch],
                  qw[i * batch:(i + 1) * batch])
        quiet = quiet + 1 if clock.count == c else 0
        i += 1
    extra, missed = mod.warm_batches(dep, pool, batch)
    for rows in extra:
        dep.serve(qd[rows], qt[rows], qw[rows])
    if missed:
        log(f"warm-up: no warm batch reaches unique-block buckets {missed}")
    # the deployment's objects are built: move them out of the collector's
    # reach, as a server does once loaded, so a window's full collections
    # walk only what serving allocates
    gc.collect()
    gc.freeze()
    return len(extra)


def sample_requests(window, seed, n_queries):
    """Answered requests drawn from the seed, about n_queries queries."""
    reqs = window.answered
    order = np.random.default_rng(seed).permutation(len(reqs))
    out, n = [], 0
    for j in order:
        if n >= n_queries:
            break
        out.append(reqs[j])
        n += reqs[j].n
    return out


def engine_spans(tracer, epoch_ns):
    """The engine's stage spans of the window, [(t0_ns, t1_ns, name)] on
    the perf_counter_ns clock."""
    out = []
    for tr in tracer.traces:
        base = epoch_ns + tr.t0_rel_ms * 1e6
        for sp in tr.spans:
            if sp.dur_ms is not None:
                t0 = base + sp.t0_ms * 1e6
                out.append((t0, t0 + sp.dur_ms * 1e6, sp.name))
    return out


def reduce_trace(prof, engine_span_list):
    """The traced part of the window, reduced: ({window_s, busy_s,
    modules_by_span}, breakdown), or (None, None) where the trace holds no
    device ops or cannot be put on the host's clock."""
    path = prof.path()
    if path is None:
        return None, None
    tr = tracereduce.load(path)
    shutil.rmtree(prof.dir, ignore_errors=True)
    off = tracereduce.clock_offset_ns(tr.marks, prof.sync, "bench.sync")
    if off is None or not tr.ops:
        return None, None
    lo, hi = prof.t[0] * 1e9 - off, prof.t[1] * 1e9 - off
    spans = [(a - off, b - off, n) for a, b, n in engine_span_list] + [
        m for m in tr.marks if m[2] == "bench.request"]
    planes = sorted(tr.ops)
    busy = np.mean([tracereduce.busy_ns(tr.ops[p], lo, hi) for p in planes])
    ops, mods = tr.ops[planes[0]], tr.modules.get(planes[0], [])
    reduced = {"window_s": (hi - lo) / 1e9, "busy_s": busy / 1e9,
               "modules_by_span": tracereduce.module_time_by_span(
                   mods, spans, lo, hi)}
    breakdown = {"device_ops": tracereduce.top_ops(ops, mods, lo, hi),
                 "idle_gaps": tracereduce.labelled_gaps(ops, spans, lo, hi)}
    return reduced, breakdown


class Context:
    """What the per-layer metric readers read (metrics/<name>.py)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def span_ms(self, name):
        """Mean host milliseconds of the engine span `name` per batch."""
        d = [(t1 - t0) / 1e6 for t0, t1, n in self.spans if n == name]
        return float(np.mean(d)) if d else None

    def module_ms(self, span):
        """Mean device milliseconds of the programs dispatched in the
        engine span `span`, per program, from the trace."""
        if self.trace is None:
            return None
        ns, n = self.trace["modules_by_span"].get(span, (0, 0))
        return ns / n / 1e6 if n else None


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.resolve(args.workload)
    src = harness.ROOT / "src"
    if not (src / "repro").is_dir():
        log(f"the program under test is not in {src}")
        return 2
    sys.path.insert(0, str(src))
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        log(f"needs {cell.chips} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s)")
        return 3
    dev = devs[0]
    peaks = peaks_lib.peaks_for(dev.device_kind)
    from repro.common.compile_cache import place_compile_cache
    log(f"compile cache: {place_compile_cache()}")
    # cache every program, however quickly it compiles, so that a cell's
    # second run in a checkout compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    run_as_stated()
    print(run_cell(cell, args.seed, args.seconds, args.trace, dev=dev,
                   n_devices=len(devs), peaks=peaks), flush=True)
    return 0


def run_cell(cell, seed, seconds, trace, *, dev, n_devices, peaks):
    """Everything of a run after the look for the chip. Returns the
    result line."""
    import jax
    from repro.obs import Tracer
    clock = CompileClock()
    conf, traffic = cell.config, cell.traffic
    batch = int(traffic["batch"])
    log(f"cell {cell.name}: config {cell.config_name}, traffic "
        f"{cell.traffic_name} {json.dumps(traffic)}; seed {seed}, "
        f"{seconds:g} s, trace {trace}")

    # -- set-up ------------------------------------------------------------
    if traffic["loop"] == "open":
        n_window = len(loadgen.arrivals(seed, traffic["rate_qps"],
                                        seconds)) * batch
    else:
        n_window = int(traffic["pool"])
    pools = {"window": (seed, max(n_window, batch), deploy.WINDOW),
             "eval": (conf["data_seed"], EVAL_QUERIES, deploy.EVAL),
             "warm": (conf["data_seed"], WARM_QUERIES, deploy.WARM)}
    epoch_ns = time.perf_counter_ns()
    tracer = Tracer(sample_rate=0.0, capacity=1 << 16)
    mod = harness.load_module(cell.deployment_file)
    dep = mod.build(conf, tracer=tracer, pools=pools)
    t_built = time.perf_counter()
    n_extra = warm_up(dep, mod, batch, clock)
    setup_s = time.perf_counter() - T_START
    in_use = {"setup": in_use_bytes(dev)}
    log(f"set-up {setup_s:.3f} s (build {t_built - T_START:.3f} s, "
        f"warm-up {time.perf_counter() - t_built:.3f} s with {n_extra} "
        f"extra batches); {clock.count} backend compiles "
        f"({clock.secs:.1f} s)")

    # -- window ------------------------------------------------------------
    qd, qt, qw, _ = dep.pools["window"]

    def serve(first, n):
        if not trace:
            return dep.serve(qd[first:first + n], qt[first:first + n],
                             qw[first:first + n])
        with jax.profiler.TraceAnnotation("bench.request"):
            return dep.serve(qd[first:first + n], qt[first:first + n],
                             qw[first:first + n])

    prof = Profile(seconds) if trace else None
    cache0 = dep.cache_counts()
    compiles0 = clock.count
    if trace:
        tracer.sample_rate = 1.0
        tracer.clear()
    with GCPauses() as gcp:
        window = loadgen.run(traffic, serve, len(qd), seconds, seed,
                             on_open=prof.arm if prof else None)
    tracer.sample_rate = 0.0
    compiles = clock.count - compiles0
    cache1 = dep.cache_counts()
    mem = dev.memory_stats() or {}
    peak_bytes = mem.get("peak_bytes_in_use")
    in_use["window"] = mem.get("bytes_in_use")
    log(f"device memory: peak {peak_bytes} bytes; in use after set-up "
        f"{in_use['setup']}, at the window's close {in_use['window']}")
    lat = window.latencies_ms()
    attempted = sum(r.n for r in window.requests)
    failed = attempted - sum(r.n for r in window.answered)
    late = np.asarray(window.late_s) * 1e3
    waits = np.asarray(window.waits_s) * 1e3
    log(f"window: {len(window.requests)} requests ({attempted} queries), "
        f"{failed} failed, {compiles} backend compiles; generator late "
        f"p50 {np.median(late) if len(late) else 0:.3f} ms max "
        f"{late.max() if len(late) else 0:.3f} ms; waits for a caller "
        f"thread over 1 ms: {int((waits > 1).sum())} (max "
        f"{waits.max() if len(waits) else 0:.3f} ms); {gcp.summary()}")

    # -- after the window: MRR@10 on the fixed set --------------------------
    ev = dep.pools["eval"]
    mrr = mrr_at_10(serve_pool(dep, ev, batch), ev[3][:len(ev[0]) // batch
                                                        * batch])
    # -- the reference, once the program's state is freed -------------------
    engine_span_list = engine_spans(tracer, epoch_ns) if trace else []
    dep.close()
    gc.collect()
    ref_mod = harness.load_module(cell.reference_file)
    t_ref = time.perf_counter()
    picks = sample_requests(window, seed, CHECK_QUERIES)
    answers, rows = [], []
    for r in picks:
        for j in range(r.n):
            answers.append((r.ids[j], r.scores[j]))
            rows.append(r.first + j)
    rows = np.asarray(rows, np.int64)
    rep = reference.check(answers, (qd[rows], qt[rows], qw[rows]),
                          dep.data, ref_mod.dense, conf)
    limit = conf["score_gap_limit"]
    gap = rep.gap
    checks = {"score_gap": (gap if np.isfinite(gap) else 1e30, limit),
              "malformed_answers": (rep.malformed, 0),
              "failed_requests": (failed, 0),
              "unresolved_queries": (rep.unresolved,
                                     len(answers) // UNRESOLVED_SHARE)}
    correct = (bool(rep.gaps) and gap <= limit and rep.malformed == 0
               and failed == 0
               and rep.unresolved <= len(answers) // UNRESOLVED_SHARE)
    df = np.bincount(np.asarray(dep.data["doc_terms"]).ravel(),
                     minlength=conf["vocab"])
    # what the answers that matched the reference selected (traced runs
    # read it: selector.clusters_per_query and the roofline work)
    selection = {} if not rep.selected else {
        "clusters_per_query": float(np.mean(rep.selected)),
        "rows_per_query": float(np.mean(rep.rows)),
        "postings_per_query": float(np.mean(
            [df[t[w > 0]].sum() for t, w in zip(qt[rows], qw[rows])]))}
    log(f"reference: {len(rep.gaps)} answers compared, {rep.unresolved} "
        f"left to rounding {json.dumps(rep.left)}, in {time.perf_counter() - t_ref:.1f} s; "
        f"selection {json.dumps(selection)}")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": n_devices, "memory_peak_bytes": peak_bytes,
              "bytes_in_use_after_setup": in_use["setup"],
              "bytes_in_use_at_window_close": in_use["window"]}
    metrics, breakdown = {}, None
    if not trace:
        values = {
            "qps": window.completed_in_window() / seconds,
            "latency_p50_ms": float(np.percentile(lat, 50)),
            "latency_p95_ms": float(np.percentile(lat, 95)),
            "mrr_at_10": mrr, "setup_s": setup_s}
        log(f"latency samples {len(lat)}; over p95: "
            f"{int((lat > values['latency_p95_ms']).sum())}")
        for m in cell.end_to_end:
            metrics[m["name"]] = (values[m["name"]], m["unit"], {})
    else:
        reduced, breakdown = reduce_trace(prof, engine_span_list)
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
        ctx = Context(trace=reduced, spans=[(a, b, n) for a, b, n in
                                          engine_span_list],
                      compiles_in_window=compiles, selection=selection,
                      cache=(None if cache0 is None else
                             (cache1[0] - cache0[0], cache1[1] - cache0[1])),
                      conf=conf, batch=batch, peaks=peaks)
        for m in cell.per_layer:
            v = harness.load_module(cell.reader_files[m["name"]]).read(ctx)
            log(f"{m['name']}: {v}")
            if v is not None:
                v, extra = v if isinstance(v, tuple) else (v, {})
                metrics[m["name"]] = (float(v), m["unit"], extra)
    for name, (v, lim) in checks.items():
        log(f"check {name}: {v} (limit {lim})")
    return harness.result_line(correct=correct, attempted=attempted,
                               failed=failed, metrics=metrics, device=device,
                               checks=checks, breakdown=breakdown)


if __name__ == "__main__":
    sys.exit(main())
