import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e): AOT-lower + compile every
(architecture x input-shape) cell on the single-pod 16x16 mesh AND the
2x16x16 multi-pod mesh; record memory_analysis / cost_analysis / collective
bytes per cell into artifacts/dryrun/<cell>.json.

No arrays are allocated: inputs are ShapeDtypeStructs; results feed
EXPERIMENTS.md §Dry-run and §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun [--arch A] [--shape S]
      [--mesh single|multi|both] [--force] [--rules k=v,...] [--tag T]
"""

import argparse
import json
import time
import traceback

import jax

from repro.analysis.hlo import collective_bytes, hlo_cost
from repro.analysis.roofline import model_flops, roofline_terms
from repro.common.compile_cache import place_compile_cache
from repro.configs import cells, get_config
from repro.configs.shapes import shapes_for
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import build_cell
from repro.models.sharding import rules_ctx

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "artifacts", "dryrun")


def run_cell(arch, shape, multi_pod, extra_rules=None, save_hlo=False,
             overrides=None):
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    cell = build_cell(arch, shape, mesh, multi_pod=multi_pod,
                      overrides=overrides)
    rules = dict(cell.rules)
    if extra_rules:
        rules.update(extra_rules)
    # train: donate params+opt; decode: donate the KV cache (otherwise the
    # input and output caches double HBM)
    donate = {"train": (0, 1), "decode": (1,)}.get(cell.meta.get("mode"), ())
    t0 = time.time()
    with rules_ctx(rules, mesh=mesh, pod_dp=multi_pod):
        lowered = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                          donate_argnums=donate).lower(*cell.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    xla_cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo, n_dev)
    parsed = hlo_cost(hlo)
    cost = {"flops": parsed["flops"], "bytes accessed": parsed["hbm_bytes"]}
    terms = roofline_terms(cost, coll, n_dev)
    terms["xla_flops_per_device_loopbody_once"] = float(
        xla_cost.get("flops", 0.0))
    cfg = get_config(arch)
    mf = model_flops(cfg, shape)
    hbm = {
        "argument_gb": mem.argument_size_in_bytes / 2**30,
        "output_gb": mem.output_size_in_bytes / 2**30,
        "temp_gb": mem.temp_size_in_bytes / 2**30,
        "code_gb": mem.generated_code_size_in_bytes / 2**30,
        "alias_gb": mem.alias_size_in_bytes / 2**30,
    }
    hbm["peak_gb"] = (hbm["argument_gb"] + hbm["output_gb"] + hbm["temp_gb"]
                      - hbm["alias_gb"])
    rec = {
        "arch": arch, "shape": shape.name, "mode": shape.mode,
        "mesh": "2x16x16" if multi_pod else "16x16", "n_devices": n_dev,
        "status": "ok",
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": hbm,
        "fits_hbm_16g": hbm["peak_gb"] <= 16.0,
        "roofline": terms,
        "model_flops": mf,
        "useful_flops_ratio": mf / max(terms["global_flops"], 1.0),
        "rules": {k: list(v) if isinstance(v, tuple) else v
                  for k, v in rules.items()},
    }
    if save_hlo:
        rec["hlo_path"] = os.path.join(ART_DIR, f"{_cell_key(arch, shape.name, multi_pod)}.hlo")
        with open(rec["hlo_path"], "w") as f:
            f.write(hlo)
    return rec


def _cell_key(arch, shape_name, multi_pod, tag=""):
    m = "multi" if multi_pod else "single"
    t = f"_{tag}" if tag else ""
    return f"{arch}__{shape_name}__{m}{t}".replace("/", "_")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--rules", default="",
                    help="logical=axis1+axis2|none,... sharding-rule overrides")
    ap.add_argument("--set", action="append", default=[],
                    help="arch-config overrides key=value (perf variants)")
    args = ap.parse_args()
    place_compile_cache()

    extra_rules = {}
    for kv in args.rules.split(","):
        if not kv:
            continue
        k, v = kv.split("=")
        extra_rules[k] = None if v == "none" else tuple(v.split("+"))
    overrides = {}
    for kv in args.set:
        k, v = kv.split("=")
        overrides[k] = (int(v) if v.lstrip("-").isdigit()
                        else True if v == "true"
                        else False if v == "false" else v)

    os.makedirs(ART_DIR, exist_ok=True)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    todo = []
    if args.arch == "clusd-msmarco":
        # the paper's own system — extra cells beyond the 40 assigned
        for shape in shapes_for("retrieval").values():
            if not args.shape or shape.name == args.shape:
                todo.append((args.arch, shape, None))
    else:
        for arch, shape, skip in cells():
            if args.arch and arch != args.arch:
                continue
            if args.shape and shape.name != args.shape:
                continue
            todo.append((arch, shape, skip))

    summary = {"ok": 0, "skip": 0, "fail": 0}
    for arch, shape, skip in todo:
        for multi_pod in meshes:
            key = _cell_key(arch, shape.name, multi_pod, args.tag)
            path = os.path.join(ART_DIR, key + ".json")
            if os.path.exists(path) and not args.force:
                print(f"[cached] {key}", flush=True)
                continue
            if skip:
                rec = {"arch": arch, "shape": shape.name,
                       "mesh": "2x16x16" if multi_pod else "16x16",
                       "status": "skip", "reason": skip}
                summary["skip"] += 1
            else:
                print(f"[run] {key} ...", flush=True)
                try:
                    rec = run_cell(arch, shape, multi_pod, extra_rules,
                                   args.save_hlo, overrides)
                    summary["ok"] += 1
                    r = rec["roofline"]
                    print(f"  ok compile={rec['compile_s']}s "
                          f"peak={rec['memory']['peak_gb']:.2f}GiB "
                          f"dom={r['dominant']} "
                          f"t=({r['compute_s']:.2e},{r['memory_s']:.2e},"
                          f"{r['collective_s']:.2e})s "
                          f"useful={rec['useful_flops_ratio']:.3f}", flush=True)
                except Exception as e:
                    rec = {"arch": arch, "shape": shape.name,
                           "mesh": "2x16x16" if multi_pod else "16x16",
                           "status": "fail", "error": str(e)[-2000:],
                           "traceback": traceback.format_exc()[-4000:]}
                    summary["fail"] += 1
                    print(f"  FAIL {type(e).__name__}: {str(e)[:300]}", flush=True)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
    print("summary:", summary, flush=True)
    return 0 if summary["fail"] == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
