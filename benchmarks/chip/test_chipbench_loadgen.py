"""The load generator: a seeded arrival schedule, requests timed from
when they were due, and a closed loop's counts."""

import threading
import time

import numpy as np

import loadgen


def test_schedule_is_a_pure_function_of_seed_and_rate():
    a = loadgen.arrivals(7, 100.0, 5.0)
    assert np.array_equal(a, loadgen.arrivals(7, 100.0, 5.0))
    b = loadgen.arrivals(8, 100.0, 5.0)
    assert not np.array_equal(a, b)
    # every seed draws its gaps from the same set, in another order
    n = 500
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / 100.0
    for due in (a, b):
        d = np.diff(due)
        assert np.abs(d[:, None] - gaps[None, :]).min(1).max() < 1e-12
        assert len(np.unique(np.round(d, 12))) == len(d)
    assert 480 <= len(a) <= 500 and a[0] == 0.0 and a[-1] < 5.0
    c = loadgen.arrivals(7, 200.0, 5.0)
    assert 960 <= len(c) <= 1000


class StallingEngine:
    """Serves one request at a time (as the engine's lock does), 1 ms
    each, except one that stalls for `stall_s`."""

    def __init__(self, stall_at, stall_s):
        self.lock = threading.Lock()
        self.calls = 0
        self.stall_at, self.stall_s = stall_at, stall_s

    def serve(self, first, n):
        with self.lock:
            self.calls += 1
            time.sleep(self.stall_s if self.calls == self.stall_at else 0.001)
        return np.zeros((n, 1), np.int32), np.zeros((n, 1), np.float32)


def test_a_stall_is_charged_to_every_request_due_behind_it():
    eng = StallingEngine(stall_at=40, stall_s=0.3)
    w = loadgen.run({"loop": "open", "batch": 1, "callers": 64,
                     "rate_qps": 100.0}, eng.serve, 10_000, 1.5, seed=3)
    assert len(w.answered) == len(w.requests) > 100
    by_due = sorted(w.requests, key=lambda r: r.due)
    stall_start = by_due[39].due
    stall_end = max(r.done for r in by_due[:40])
    for r in by_due[40:]:
        if r.due < stall_end - 0.05:
            # waited for the stall to clear: timed from when it was due
            assert r.done - r.due >= 0.9 * (stall_end - r.due) - 0.02, r
    behind = [r for r in by_due if stall_start < r.due < stall_end - 0.05]
    assert len(behind) >= 10
    lat = w.latencies_ms()
    assert len(lat) == len(w.requests)
    assert np.percentile(lat, 99) > 150.0
    assert max(w.waits_s) < 0.05           # the pool never ran short


def test_closed_loop_counts_queries_and_one_sample_per_query():
    eng = StallingEngine(stall_at=0, stall_s=0.0)
    w = loadgen.run({"loop": "closed", "batch": 4,
                     "pool": 64}, eng.serve, 64, 0.3, seed=1)
    assert len(w.latencies_ms()) == 4 * len(w.requests)
    assert w.completed_in_window() <= 4 * len(w.requests)
    assert all(r.first + r.n <= 64 for r in w.requests)
