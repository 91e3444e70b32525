"""The one load generator: reads a traffic mix (a JSON file of parameters
under `traffic/`) and drives a serving call with it for a fixed window.

Two loops, as the mix's `loop` says:

  closed  one caller sends batches of `batch` distinct queries back to
          back; a batch is due when it is sent.
  open    requests of `batch` queries arrive on a schedule at `rate_qps`
          requests per second (Poisson: exponential gaps) whatever the
          server does, and a pool of `callers` threads serves them. Each
          is timed from when it was due, so a stall is charged to every
          request that arrives behind it.

Every request of the window is waited for after the window closes (up to
`drain_s`); an answer that comes late is late, not missing.
"""

import dataclasses
import math
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

DRAIN_S = 60.0


@dataclasses.dataclass
class Request:
    first: int              # index of its first query in the pool
    n: int                  # queries in the request
    due: float              # seconds after the window opened
    done: float = math.nan  # when its answer was back on the host
    ids: object = None
    scores: object = None
    error: str = None


@dataclasses.dataclass
class Window:
    requests: list
    seconds: float                      # length of the window
    late_s: list                        # generator lateness per request
    waits_s: list                       # time spent waiting for a thread

    @property
    def answered(self):
        return [r for r in self.requests if r.error is None
                and not math.isnan(r.done)]

    def latencies_ms(self):
        """One sample per query: from when its request was due to when
        its answer was back."""
        return np.concatenate([np.full(r.n, (r.done - r.due) * 1e3)
                               for r in self.answered] or [np.zeros(0)])

    def completed_in_window(self):
        return sum(r.n for r in self.answered if r.done <= self.seconds)


def arrivals(seed, rate, seconds):
    """Due times (seconds after the window opens) of a Poisson process at
    `rate` per second. Every seed gets the same set of exponential gaps
    (their quantiles at n evenly spaced levels), in an order drawn from
    the seed, so seeds change the order of the load and not its amount."""
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps = np.random.default_rng(seed).permutation(gaps)
    due = np.cumsum(gaps) - gaps[0]
    return due[due < seconds]


def _serve_one(serve, req, t0):
    try:
        req.ids, req.scores = serve(req.first, req.n)
    except Exception as e:      # a failed request is counted, not fatal
        req.error = f"{type(e).__name__}: {e}"
    req.done = time.perf_counter() - t0


def run(traffic, serve, pool_size, seconds, seed, *, on_open=None):
    """Drive `serve(first, n) -> (ids, scores)` (answers on the host) for
    `seconds` as the mix `traffic` says. Queries are taken from a pool of
    `pool_size` in order, so none repeats until the pool is spent.
    `on_open(t0)` runs as the window opens (the traced run starts its
    profiler there)."""
    batch = int(traffic["batch"])
    if traffic["loop"] == "closed":
        return _closed(serve, batch, pool_size, seconds, on_open)
    if traffic["loop"] == "open":
        due = arrivals(seed, float(traffic["rate_qps"]), seconds)
        return _open(serve, batch, pool_size, seconds, due,
                     int(traffic["callers"]), on_open)
    raise ValueError(f"unknown loop {traffic['loop']!r}")


def _closed(serve, batch, pool_size, seconds, on_open):
    reqs = []
    t0 = time.perf_counter()
    if on_open:
        on_open(t0)
    first = 0
    while time.perf_counter() - t0 < seconds:
        if first + batch > pool_size:
            first = 0
        req = Request(first, batch, time.perf_counter() - t0)
        _serve_one(serve, req, t0)
        reqs.append(req)
        first += batch
    return Window(reqs, seconds, [0.0] * len(reqs), [0.0] * len(reqs))


def _open(serve, batch, pool_size, seconds, due, callers, on_open):
    reqs = [Request((i * batch) % max(1, pool_size - batch + 1), batch,
                    float(d)) for i, d in enumerate(due)]
    late, waits = [], []
    lock = threading.Lock()

    def task(req, submitted):
        with lock:
            waits.append(time.perf_counter() - submitted)
        _serve_one(serve, req, t0)

    with ThreadPoolExecutor(max_workers=callers) as ex:
        futures = []
        t0 = time.perf_counter()
        if on_open:
            on_open(t0)
        for req in reqs:
            delay = req.due - (time.perf_counter() - t0)
            if delay > 0:
                time.sleep(delay)
            now = time.perf_counter()
            late.append(now - t0 - req.due)
            futures.append(ex.submit(task, req, now))
        deadline = t0 + seconds + DRAIN_S
        for f in futures:
            f.result(timeout=max(0.0, deadline - time.perf_counter()))
    return Window(reqs, seconds, late, waits)
