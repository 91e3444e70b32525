"""System-level invariants: registry completeness, dry-run cell coverage,
artifact schema, and the roofline parser's trip-count math."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ASSIGNED_ARCHS, cells, get_config
from repro.analysis.hlo import collective_bytes, hlo_cost

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts", "dryrun")


def test_cell_enumeration_is_40():
    all_cells = cells(include_skipped=True)
    assert len(all_cells) == 40  # 10 archs x 4 shapes
    skipped = [c for c in all_cells if c[2]]
    # long_500k skipped exactly for the 4 pure-full-attention LMs
    assert sorted(c[0] for c in skipped) == sorted(
        ["arctic-480b", "qwen2-1.5b", "deepseek-67b", "qwen2.5-32b"])


def test_dryrun_artifacts_complete_and_green():
    if not os.path.isdir(ART):
        import pytest
        pytest.skip("dry-run artifacts not generated in this checkout")
    ok = skip = fail = 0
    for f in os.listdir(ART):
        if not f.endswith(".json") or "_opt" in f or "paper_" in f:
            continue
        d = json.load(open(os.path.join(ART, f)))
        s = d.get("status")
        ok += s == "ok"
        skip += s == "skip"
        fail += s == "fail"
    assert fail == 0
    assert ok == 72 and skip == 8  # 36 runnable cells x 2 meshes


def test_hlo_parser_counts_loop_trips():
    L, d = 6, 64

    def scanned(ws, x):
        def body(c, w):
            return c @ w, ()
        out, _ = jax.lax.scan(body, x, ws)
        return out

    comp = jax.jit(scanned).lower(
        jax.ShapeDtypeStruct((L, d, d), jnp.float32),
        jax.ShapeDtypeStruct((d, d), jnp.float32)).compile()
    got = hlo_cost(comp.as_text())["flops"]
    want = L * 2 * d * d * d
    assert abs(got - want) / want < 0.05, (got, want)
    # XLA's own analysis counts the body once — that's why we parse
    assert comp.cost_analysis()["flops"] < want / 2


def test_model_flops_sane():
    from repro.analysis.roofline import model_flops
    from repro.configs.shapes import LM_SHAPES
    cfg = get_config("deepseek-67b")
    mf = model_flops(cfg, LM_SHAPES["train_4k"])
    # 6 * 67e9 * 1.05e6 tokens ~ 4.2e17, plus attention
    assert 3e17 < mf < 1e18
