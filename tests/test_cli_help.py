"""Every repro.launch CLI must answer `--help` with exit code 0, and the
build/serve help text must be the single source of truth for the flags it
documents (the PR-3 flags drifted out of the old epilogs once — this
pins them)."""

import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

CLIS = ["repro.launch.build_index", "repro.launch.serve",
        "repro.launch.update_index", "repro.launch.train",
        "repro.launch.train_selector", "repro.launch.dryrun"]


def _help_output(module):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO_ROOT, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, "-m", module, "--help"],
        capture_output=True, text=True, env=env, cwd=REPO_ROOT, timeout=300)
    assert proc.returncode == 0, \
        f"{module} --help exited {proc.returncode}:\n{proc.stderr[-2000:]}"
    return proc.stdout


@pytest.mark.parametrize("module", CLIS)
def test_cli_help_exits_zero(module):
    out = _help_output(module)
    assert "usage:" in out


def test_build_index_help_documents_current_flags():
    out = _help_output("repro.launch.build_index")
    for flag in ("--format-version", "--memmap", "--chunk-docs", "--pq-nsub",
                 "--shards", "--kmeans-iters"):
        assert flag in out, f"build_index --help no longer documents {flag}"


def test_serve_help_documents_current_flags():
    out = _help_output("repro.launch.serve")
    for flag in ("--index-dir", "--verify", "--check-parity",
                 "--parity-mrr-tol", "--cache-blocks", "--no-prefetch",
                 "--trace-out", "--trace-sample-rate", "--metrics-out",
                 "--fusion", "--expand-depth", "--hosts", "--replication",
                 "--host-timeout-ms", "--kill-host",
                 "--metrics-port", "--slo-config", "--explain-out",
                 "--explain-sample-rate", "--serve-seconds"):
        assert flag in out, f"serve --help no longer documents {flag}"


def test_soak_help_documents_current_flags():
    out = _help_output("benchmarks.soak")
    for flag in ("--index-dir", "--duration", "--generations", "--queries",
                 "--upserts", "--deletes", "--p99-gate-ms", "--drift-gate",
                 "--out", "--seed"):
        assert flag in out, f"soak --help no longer documents {flag}"
    assert "SLOMonitor" in out          # epilog = module docstring


def test_explain_report_help_documents_current_flags():
    out = _help_output("benchmarks.explain_report")
    for flag in ("--index-dir", "--queries", "--batch", "--query-seed",
                 "--out"):
        assert flag in out, \
            f"explain_report --help no longer documents {flag}"
    # the three-way gap decomposition is the contract
    for word in ("candidate_miss", "selector_miss", "budget_cutoff"):
        assert word in out


def test_update_index_help_documents_current_flags():
    out = _help_output("repro.launch.update_index")
    for flag in ("--upserts", "--deletes", "--compact", "--check-parity",
                 "--serve-queries", "--recluster-overflow",
                 "--trace-out", "--metrics-out"):
        assert flag in out, f"update_index --help no longer documents {flag}"


def test_train_selector_help_documents_current_flags():
    out = _help_output("repro.launch.train_selector")
    for flag in ("--index-dir", "--train-queries", "--holdout-queries",
                 "--chunk-clusters", "--label-cache", "--pos-weight",
                 "--no-bucket", "--use-kernel", "--ckpt-every", "--resume",
                 "--thetas", "--budgets", "--target-recall",
                 "--target-budget", "--expand-depths", "--fusion",
                 "--publish", "--serve-check",
                 "--trace-out", "--metrics-out"):
        assert flag in out, \
            f"train_selector --help no longer documents {flag}"
    # the epilog is the module docstring: the four pipeline stages must be
    # documented in help verbatim
    for word in ("LABELS", "TRAIN", "CALIBRATE", "PUBLISH"):
        assert word in out


def test_train_help_is_docstring_backed():
    out = _help_output("repro.launch.train")
    for flag in ("--arch", "--variant", "--steps", "--ckpt-every",
                 "--fail-at"):
        assert flag in out, f"train --help no longer documents {flag}"
    # epilog = module docstring (the restartable-loop description)
    assert "fault-tolerant" in out or "restartable" in out


def test_chip_smoke_fails_without_a_tpu():
    """chip_smoke.py is a chip-only run: on the CPU it must exit non-zero
    before any phase and never print its success line."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py")],
        capture_output=True, text=True, env=env, cwd=REPO_ROOT, timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout + proc.stderr
    assert "no TPU" in proc.stderr
