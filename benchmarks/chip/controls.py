"""Readings that the limit of `correct` is set from, for one cell:

    python3 benchmarks/chip/controls.py --workload <name> \
        --seeds 1,2,3 --seconds 4

One set-up, then for each seed a short window of the cell's own traffic
and the comparison of a seeded sample of its answers with the plain
reference, twice: as served by the program (the lower reading: sound
runs) and rescored by the configuration's control, the reference in the
precision below the one the configuration states (the upper reading).
Prints one JSON line per seed and a summary line. The benchmark's own
runs never run the control."""

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

import deploy  # noqa: E402
import harness  # noqa: E402
import loadgen  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402


def readings(cell, seeds, seconds):
    """[{seed, program, control, ...}] with the largest gap of each, and
    how far the program's selector logits lie from the reference's on
    the candidates where both put the same clusters first."""
    from repro.obs import Tracer
    conf, traffic = cell.config, cell.traffic
    batch = int(traffic["batch"])
    n = (len(loadgen.arrivals(0, traffic["rate_qps"], seconds)) * batch
         if traffic["loop"] == "open" else int(traffic["pool"]))
    pools = {f"w{s}": (s, max(n, batch), deploy.WINDOW) for s in seeds}
    pools["warm"] = (conf["data_seed"], run.WARM_QUERIES, deploy.WARM)
    mod = harness.load_module(cell.deployment_file)
    ref = harness.load_module(cell.reference_file)
    dep = mod.build(conf, tracer=Tracer(), pools=pools)
    run.warm_up(dep, mod, batch, run.CompileClock())
    out = []
    try:
        for s in seeds:
            qd, qt, qw, _ = dep.pools[f"w{s}"]
            window = loadgen.run(
                traffic, lambda f, k: dep.serve(qd[f:f + k], qt[f:f + k],
                                                qw[f:f + k]),
                len(qd), seconds, s)
            picks = run.sample_requests(window, s, run.CHECK_QUERIES)
            rows = np.concatenate([np.arange(r.first, r.first + r.n)
                                   for r in picks])
            answers = [(r.ids[j], r.scores[j]) for r in picks
                       for j in range(r.n)]
            q = (qd[rows], qt[rows], qw[rows])
            prog = reference.check(answers, q, dep.data, ref.dense, conf)
            ctrl = reference.check(answers, q, dep.data, ref.dense, conf,
                                   control_fn=ref.control)
            out.append({"seed": s, "program": prog.gap,
                        "control": ctrl.gap, "malformed": prog.malformed,
                        "unresolved": prog.unresolved, "left": prog.left,
                        "answers": len(answers),
                        "selected_mean": float(np.mean(prog.selected))
                        if prog.selected else None,
                        **_logit_gap(dep, q, prog, batch)})
    finally:
        dep.close()
    return out


def _logit_gap(dep, q, rep, batch):
    """The program's Stage I and selector against the reference's: how
    many queries differ in candidates where no near-tie leaves Stage I
    to rounding; where they agree, the largest gap of the selector
    logits, of the query-centroid similarity (over |q| * the largest
    centroid norm) and of the sparse top-k scores (over the top score,
    where the top-k ids agree); and the reference logit nearest theta.
    These set reference.TIE and reference.LOGIT_MARGIN."""
    st = [dep.stages(*(x[i:i + batch] for x in q))
          for i in range(0, len(q[0]) - batch + 1, batch)]
    st = {k: np.concatenate([s[k] for s in st]) for k in st[0]}
    m = len(st["cand"])
    k = st["sparse_ids"].shape[1]
    resolved = np.array([t is None for t in rep.ties[:m]])
    same = (st["cand"] == rep.cand[:m]).all(1)
    ok = same & resolved
    top_ids, top_s = rep.top
    ids_same = (st["sparse_ids"] == top_ids[:m, :k]).all(1)
    p = st["probs"].astype(np.float64)
    z = np.log(p / (1 - p))
    theta = dep.cfg.theta
    sim = np.abs(st["feats"][..., 0] - rep.feats[:m, :, 0]) \
        / rep.sim_scale[:m, None]
    sparse = np.abs(st["sparse_scores"] - top_s[:m, :k]) / top_s[:m, :1]
    return {"candidates_differ": int((~same & resolved).sum()),
            "logit_gap_max": float(np.abs(z - rep.logits[:m])[ok].max(
                initial=0.0)),
            "sim_rel_gap_max": float(sim[ok].max(initial=0.0)),
            "sparse_rel_gap_max": float(sparse[ids_same].max(initial=0.0)),
            "sparse_ids_differ": int((~ids_same).sum()),
            "logit_nearest_theta": float(np.abs(
                rep.logits - np.log(theta / (1 - theta))).min())}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)
    cell = harness.resolve(args.workload)
    sys.path.insert(0, str(harness.ROOT / "src"))
    import jax
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    from repro.common.compile_cache import place_compile_cache
    place_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    run.run_as_stated()
    t0 = time.perf_counter()
    rows = readings(cell, [int(s) for s in args.seeds.split(",")],
                    args.seconds)
    for r in rows:
        print(json.dumps(r))
    print(json.dumps({
        "workload": cell.name, "seeds": len(rows),
        "program_max": max(r["program"] for r in rows),
        "control_min": min(r["control"] for r in rows),
        "logit_gap_max": max(r["logit_gap_max"] for r in rows),
        "sim_rel_gap_max": max(r["sim_rel_gap_max"] for r in rows),
        "sparse_rel_gap_max": max(r["sparse_rel_gap_max"] for r in rows),
        "candidates_differ": sum(r["candidates_differ"] for r in rows),
        "unresolved": sum(r["unresolved"] for r in rows),
        "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
