"""The control at a size a test run holds: the reference put in the
program's place in the precision below the configuration's (bfloat16 for
the float32 corpus and for the PQ lookup tables) fails the limit on
every seed, while the program's own answers pass it."""

import pytest

import controls
import run
from test_chipbench_run import CLOSED, SETS, TINY, tiny_cell


@pytest.fixture(autouse=True)
def small_query_sets(monkeypatch):
    for name, n in SETS.items():
        monkeypatch.setattr(run, name, n)


@pytest.mark.parametrize("workload", ["msmarco-hbm.batch16",
                                      "msmarco-pq-host.batch16"])
def test_control_fails_where_the_program_passes(workload):
    rows = controls.readings(tiny_cell(workload, CLOSED), [3, 4, 2**33 + 5],
                             0.3)
    limit = TINY["score_gap_limit"]
    for r in rows:
        assert r["malformed"] == 0 and r["answers"] >= SETS["CHECK_QUERIES"]
        assert r["program"] <= limit < r["control"], r
