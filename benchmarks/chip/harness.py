"""Finds everything one cell needs by the names in BENCHMARK.json, so a
later cell, configuration, traffic mix or per-layer metric is added as
files and entries, never as an edit:

  configuration   the `file` its BENCHMARK.json entry names (a JSON file
                  of sizes under configs/), its plain reference beside it
                  (`<file stem>.reference.py`) and the deployment module
                  its `deployment` key names (deployments/<name>.py)
  traffic mix     traffic/<traffic>.json, read by loadgen.py
  per-layer       metrics/<metric name>.py, a reader with
  metric          `read(ctx) -> number or None`

The result line is built here too, with the numbers compared for
`correct` under the last key.
"""

import dataclasses
import importlib.util
import json
import re
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load_module(path):
    """Import a file of the benchmark by path (names may hold `.` and
    `-`, which `import` cannot)."""
    path = Path(path)
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file missing: {path}")
    name = "chipbench_" + re.sub(r"\W", "_", path.stem)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(metric, workload):
    return "workloads" not in metric or workload in metric["workloads"]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list            # BENCHMARK.json metric entries
    per_layer: list
    reference_file: Path
    deployment_file: Path
    reader_files: dict          # per-layer metric name -> reader path


def load_benchmark(root=ROOT):
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def resolve(workload, bench=None, *, root=ROOT, here=HERE):
    """The Cell called `workload`, with every file it needs found by name
    (FileNotFoundError names the first one missing)."""
    bench = bench if bench is not None else load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise ValueError(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    w = cells[workload]
    centry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config_file = Path(root) / centry["file"]
    config = json.loads(config_file.read_text())
    traffic_file = Path(here) / "traffic" / f"{w['traffic']}.json"
    traffic = json.loads(traffic_file.read_text())
    per_layer = [m for m in bench["per_layer"] if applies(m, workload)]
    cell = Cell(
        name=workload, chips=int(w["chips"]), config_name=w["config"],
        config=config, traffic_name=w["traffic"], traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if applies(m, workload)],
        per_layer=per_layer,
        reference_file=config_file.with_name(
            config_file.stem + ".reference.py"),
        deployment_file=Path(here) / "deployments"
        / f"{config['deployment']}.py",
        reader_files={m["name"]: Path(here) / "metrics" / f"{m['name']}.py"
                      for m in per_layer})
    for p in [cell.reference_file, cell.deployment_file,
              *cell.reader_files.values()]:
        if not p.is_file():
            raise FileNotFoundError(f"{workload}: benchmark file missing: "
                                    f"{p}")
    return cell


def result_line(*, correct, attempted, failed, metrics, device, checks,
                breakdown=None):
    """The last line of standard output. `metrics` maps name -> (value,
    unit, extra keys); `checks` maps name -> (value, limit) and comes
    last."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed),
           "metrics": {k: {"value": v, "unit": u, **extra}
                       for k, (v, u, extra) in metrics.items()},
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return json.dumps(out)
