"""Reduction of a JAX profiler trace (`.xplane.pb`) to the benchmark's
device numbers. Pure functions over (start_ns, end_ns, name) intervals,
plus one loader, so every PR computes each number the same way.

What a TPU trace holds, as the loader reads it:
  /device:TPU:<n>   line "XLA Modules": one event per program execution,
                    named `<jit name>(<fingerprint>)`; line "XLA Ops":
                    one event per HLO op executed (async copies are on
                    their own line and are not counted as busy)
  /host:CPU         every thread's TraceMe events, among them the
                    benchmark's own `jax.profiler.TraceAnnotation`s
Host and device events share one time base (ns from the trace's start),
up to a skew of about a millisecond (on a v5e the device's events sit
that much early against the host's).

Busy time is the union of op intervals, so overlapping ops count once;
the idle share is 1 - busy / window. Device time of a program is the
length of its "XLA Modules" events. Every stage program of the engine is
a `jit_run`, so a program is told apart by the engine span (its
`repro.obs` Tracer span, moved onto the trace's clock) that dispatched
it: a module belongs to the innermost span that overlaps more than half
of it, which the skew does not move for a program longer than it.
"""

import dataclasses
import statistics

DEVICE_PREFIX = "/device:TPU:"


@dataclasses.dataclass
class Trace:
    ops: dict                   # device plane name -> [(t0, t1, name)]
    modules: dict               # device plane name -> [(t0, t1, name)]
    marks: list                 # host events named by the benchmark


def load(path_or_data, *, mark_prefix="bench.", device_prefix=DEVICE_PREFIX):
    """Read an `.xplane.pb` file (or a `jax.profiler.ProfileData`)."""
    from jax.profiler import ProfileData
    pd = path_or_data if isinstance(path_or_data, ProfileData) \
        else ProfileData.from_file(str(path_or_data))
    ops, modules, marks = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith(device_prefix):
            for line in plane.lines:
                dest = {"XLA Ops": ops, "XLA Modules": modules}.get(
                    line.name)
                if dest is not None:
                    dest.setdefault(plane.name, []).extend(
                        (e.start_ns, e.end_ns, e.name) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                marks.extend((e.start_ns, e.end_ns, e.name)
                             for e in line.events
                             if e.name.startswith(mark_prefix))
    for d in (ops, modules):
        for v in d.values():
            v.sort()
    marks.sort()
    return Trace(ops, modules, marks)


def merged(intervals, lo, hi):
    """The union of intervals clipped to [lo, hi], as sorted disjoint
    (t0, t1) pairs."""
    out = []
    for t0, t1, *_ in sorted(intervals):
        t0, t1 = max(t0, lo), min(t1, hi)
        if t1 <= t0:
            continue
        if out and t0 <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t1)
        else:
            out.append([t0, t1])
    return [tuple(p) for p in out]


def busy_ns(intervals, lo, hi):
    return sum(t1 - t0 for t0, t1 in merged(intervals, lo, hi))


def idle_gaps(intervals, lo, hi):
    """Uncovered stretches of [lo, hi], as (t0, t1) pairs."""
    gaps, t = [], lo
    for t0, t1 in merged(intervals, lo, hi):
        if t0 > t:
            gaps.append((t, t0))
        t = max(t, t1)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def clock_offset_ns(marks, host_starts_ns, name):
    """perf_counter_ns - trace time, from the benchmark's annotations
    named `name`, whose perf_counter_ns at entry the benchmark recorded
    (in order). The median shrugs off a thread switched out between the
    two readings."""
    starts = [m[0] for m in marks if m[2] == name]
    n = min(len(starts), len(host_starts_ns))
    if n == 0:
        return None
    return statistics.median(h - s for h, s in zip(host_starts_ns[:n],
                                                   starts[:n]))


def innermost(spans, t):
    """Name of the innermost (shortest) span covering time t, or None.
    spans: [(t0, t1, name)]."""
    best = None
    for t0, t1, name in spans:
        if t0 <= t < t1 and (best is None or t1 - t0 < best[1] - best[0]):
            best = (t0, t1, name)
    return best[2] if best else None


def owner(spans, t0, t1):
    """Name of the innermost span overlapping more than half of [t0, t1],
    or None."""
    best = None
    for s0, s1, name in spans:
        if min(s1, t1) - max(s0, t0) > 0.5 * (t1 - t0) and (
                best is None or s1 - s0 < best[1] - best[0]):
            best = (s0, s1, name)
    return best[2] if best else None


def module_time_by_span(modules, spans, lo, hi):
    """{span name: (device ns of the modules that ran inside spans of
    that name, number of modules)} for modules starting in [lo, hi)."""
    out = {}
    for t0, t1, _ in modules:
        if not lo <= t0 < hi:
            continue
        name = owner(spans, t0, t1)
        if name is None:
            continue
        ns, n = out.get(name, (0, 0))
        out[name] = (ns + (t1 - t0), n + 1)
    return out


def short_op(name):
    """'%fusion.44 = f32[...] fusion(...)' -> 'fusion.44'."""
    return name.split(" = ", 1)[0].lstrip("%")


def top_ops(ops, modules, lo, hi, n=10):
    """[[program/op, seconds]] of the n ops that took most device time in
    [lo, hi], each named with the program it ran in."""
    tot = {}
    mods = sorted(modules)
    j = 0
    for t0, t1, name in ops:
        if not lo <= t0 < hi:
            continue
        while j < len(mods) and mods[j][1] <= t0:
            j += 1
        prog = mods[j][2] if j < len(mods) and mods[j][0] <= t0 else "?"
        key = f"{prog}/{short_op(name)}"
        tot[key] = tot.get(key, 0) + (min(t1, hi) - t0)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in best]


def labelled_gaps(ops, spans, lo, hi, n=10):
    """[[host span, seconds]] of the n longest idle gaps of the device in
    [lo, hi], each labelled by the innermost host span covering its
    middle ("none" where the host was in no span)."""
    gaps = sorted(idle_gaps(ops, lo, hi), key=lambda g: g[0] - g[1])[:n]
    return [[innermost(spans, (a + b) / 2) or "none", (b - a) / 1e9]
            for a, b in gaps]
