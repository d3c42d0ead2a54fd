"""Finding a cell's files by name.

``BENCHMARK.json`` at the checkout's root names each cell's configuration
and traffic; the harness reads

* ``bench/configs/<config>.json``: the model as it is run, in the keys of
  its published ``config.json``, plus the repo architecture it is built
  from (``arch``) and the fields of that architecture it replaces
  (``program``);
* ``bench/traffic/<traffic>.json``: the training job: batch, sequence
  length, integrator and optimizer settings, warm-up steps;
* ``bench/limits/<cell>.json``: the limit of each compared number;
* ``bench/metrics/<metric>.py``: one reader per metric, ``read(ctx)``
  returning a number, or None where it finds nothing to read.

Adding a cell or a metric adds files; no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Dict, List

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict:
    return _json(os.path.join(ROOT, "BENCHMARK.json"))


def workload(bm: Dict, name: str) -> Dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json; "
                     f"known: {[w['name'] for w in bm['workloads']]}")


def config(bm: Dict, name: str) -> Dict:
    """A configuration's file, as BENCHMARK.json names it."""
    for c in bm["configs"]:
        if c["name"] == name:
            return _json(os.path.join(ROOT, c["file"]))
    raise SystemExit(f"bench: no configuration {name!r} in BENCHMARK.json")


def traffic(name: str) -> Dict:
    return _json(os.path.join(BENCH, "traffic", f"{name}.json"))


def limits(cell: str) -> Dict[str, float]:
    return _json(os.path.join(BENCH, "limits", f"{cell}.json"))["limits"]


def metrics(bm: Dict, cell: str, trace: bool) -> List[Dict]:
    """The metrics a run of ``cell`` reports: end-to-end ones untraced,
    per-layer ones traced; each where its ``workloads`` list names the
    cell, or everywhere without the list."""
    group = bm["per_layer"] if trace else bm["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def reader(name: str):
    """``read`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
