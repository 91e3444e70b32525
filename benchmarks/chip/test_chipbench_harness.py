"""BENCHMARK.json against the harness: every cell finds its files by
name, a cell added only as files and entries is found without editing a
file, and with no TPU (or without the program) a run prints no result
and exits non-zero."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import harness

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_its_files_by_name(workload):
    cell = harness.resolve(workload, BENCH)
    assert cell.config["name"] == cell.config_name
    assert cell.traffic["loop"] in ("open", "closed")
    for path in cell.reader_files.values():
        assert callable(harness.load_module(path).read)
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer


def test_benchmark_json_keeps_to_its_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert all(harness.ROOT.joinpath(p).is_dir() for p in BENCH["paths"])
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(BENCH["paths"][0] + "/")
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in e2e
    for entry in BENCH["configs"] + BENCH["workloads"] \
            + BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(entry["name"]), entry["name"]


def _add_cell(root):
    """A new configuration, traffic mix, per-layer metric and cell, added
    as files and entries only."""
    here = root / "benchmarks" / "chip"
    conf = json.loads((here / "configs" / "msmarco-hbm.json").read_text())
    conf["name"] = "toy"
    (here / "configs" / "toy.json").write_text(json.dumps(conf))
    (here / "configs" / "toy.reference.py").write_text(
        "def dense(data, q, ids):\n    return None\n")
    (here / "traffic" / "toy-mix.json").write_text(json.dumps(
        {"loop": "closed", "batch": 2, "pool": 64}))
    (here / "metrics" / "toy.metric.py").write_text(
        "def read(ctx):\n    return 1.0\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy", "source": "test",
                             "file": "benchmarks/chip/configs/toy.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy.toy-mix", "config": "toy",
                               "traffic": "toy-mix", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "toy.metric", "unit": "count",
                               "better": "lower", "source": "host_clock",
                               "layer": "toy", "moves": "latency_p50_ms",
                               "workloads": ["toy.toy-mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def _copy_benchmark(dest):
    shutil.copy(harness.ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    shutil.copytree(harness.HERE, dest / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))


def test_a_cell_added_as_files_is_found_without_editing_any(tmp_path):
    _copy_benchmark(tmp_path)
    here = tmp_path / "benchmarks" / "chip"
    before = {p: p.read_bytes() for p in here.rglob("*") if p.is_file()}
    _add_cell(tmp_path)
    cell = harness.resolve("toy.toy-mix", root=tmp_path, here=here)
    assert cell.config["name"] == "toy" and cell.traffic["batch"] == 2
    assert "toy.metric" in cell.reader_files
    assert harness.load_module(cell.reader_files["toy.metric"]).read(
        None) == 1.0
    assert cell.deployment_file == here / "deployments" / "inmemory.py"
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"


def _run(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "msmarco-hbm.batch16", "--seed", "5", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_without_a_tpu_the_run_prints_nothing_and_fails():
    p = _run(harness.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_with_only_the_benchmark_files_the_run_fails(tmp_path):
    _copy_benchmark(tmp_path)
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
