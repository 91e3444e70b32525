"""A whole run of a cell at a tiny size on the CPU, past the harness's
look for a chip: the sound program comes out correct, and each fault a
serving cell can have, planted under the timed path, comes out not
correct."""

import dataclasses
import json

import jax
import numpy as np
import pytest

import deploy
import harness
import peaks
import run

# the paper's geometry cut to a CPU test (widths included: this is a
# rehearsal of the control flow, not a measurement)
TINY = {"n_docs": 4096, "dim": 64, "vocab": 2048, "max_postings": 256,
        "n_clusters": 16, "k_sparse": 128, "bins": [10, 25, 50, 128],
        "n_candidates": 8, "lstm_hidden": 16, "n_neighbors": 8, "u_bins": 4,
        "max_selected": 8, "k_final": 64, "train_queries": 64,
        "epochs": 2, "kmeans_iters": 3, "max_batch": 4, "pq_sample": 1024,
        "pq_iters": 2, "cache_blocks": 8, "score_gap_limit": 1e-4}
SETS = {"EVAL_QUERIES": 32, "WARM_QUERIES": 32, "CHECK_QUERIES": 16}
CLOSED = {"loop": "closed", "batch": 4, "pool": 256}
OPEN = {"loop": "open", "batch": 1, "callers": 4, "rate_qps": 20.0}
SEED = 2**31 + 77


@pytest.fixture(autouse=True)
def small_query_sets(monkeypatch):
    for name, n in SETS.items():
        monkeypatch.setattr(run, name, n)


def tiny_cell(workload, traffic):
    cell = harness.resolve(workload)
    return dataclasses.replace(cell, config={**cell.config, **TINY},
                               traffic=traffic)


def run_once(cell, trace=0):
    line = run.run_cell(cell, SEED, 1.0, trace, dev=jax.devices()[0],
                        n_devices=1, peaks=peaks.PEAKS["TPU v5 lite"])
    return json.loads(line)


@pytest.mark.parametrize("workload,traffic", [
    ("msmarco-hbm.batch16", CLOSED), ("msmarco-pq-host.batch16", CLOSED),
    ("msmarco-hbm.batch16", OPEN)])
def test_sound_run_is_correct(workload, traffic):
    out = run_once(tiny_cell(workload, traffic))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    cell = harness.resolve(workload)
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert list(out)[-1] == "checks"


def test_traced_run_reports_per_layer_metrics():
    out = run_once(tiny_cell("msmarco-hbm.batch16", CLOSED), trace=1)
    assert out["correct"], out["checks"]
    # the CPU has no TPU plane in its trace: the device numbers stay out
    assert out["metrics"]["engine.compiles_in_window"]["value"] == 0
    assert out["metrics"]["selector.clusters_per_query"]["value"] > 0
    assert "device.idle_share" not in out["metrics"]
    assert "busy_s" not in out["device"]


def _alter_one_answer(serve):
    def broken(self, qd, qt, qw):
        ids, scores = serve(self, qd, qt, qw)
        ids = ids.copy()
        ids[:, 3] = (ids[:, 3] + 1) % TINY["n_docs"]   # a wrong document
        return ids, scores
    return broken


def _leave_out_half(serve):
    def broken(self, qd, qt, qw):
        half = max(1, len(qd) // 2)
        ids, scores = serve(self, qd[:half], qt[:half], qw[:half])
        reps = -(-len(qd) // half)
        return (np.tile(ids, (reps, 1))[:len(qd)],
                np.tile(scores, (reps, 1))[:len(qd)])
    return broken


def _score_half_the_selection(select):
    def broken(*a, **kw):
        out = select(*a, **kw)
        mask = out["sel_mask"].at[:, 1::2].set(False)
        return {**out, "sel_mask": mask}
    return broken


@pytest.mark.parametrize("fault", [_alter_one_answer, _leave_out_half])
def test_faults_under_the_timed_path_are_not_correct(fault, monkeypatch):
    monkeypatch.setattr(deploy.Deployment, "serve",
                        fault(deploy.Deployment.serve))
    out = run_once(tiny_cell("msmarco-hbm.batch16", CLOSED))
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("workload", ["msmarco-hbm.batch16",
                                      "msmarco-pq-host.batch16"])
def test_a_selection_half_scored_is_not_correct(workload, monkeypatch):
    from repro.core import clusd
    monkeypatch.setattr(clusd, "stage2_select",
                        _score_half_the_selection(clusd.stage2_select))
    out = run_once(tiny_cell(workload, CLOSED))
    assert not out["correct"], out["checks"]
