"""Finding a cell's files by name.

``BENCHMARK.json`` at the checkout's root names each cell's configuration
and traffic; the harness reads

* ``bench/configs/<config>.json``: the model as it is run, in the keys of
  its published ``config.json``, plus the repo architecture it is built
  from (``arch``), the fields of that architecture it replaces
  (``program``) and, optionally, the reference module of its block
  (``reference``, a module of ``bench/reference/``; ``lm`` without it);
* ``bench/traffic/<traffic>.json``: the training job: batch, sequence
  length, integrator and optimizer settings, warm-up steps;
* ``bench/limits/<cell>.json``: the limit of each compared number;
* ``bench/metrics/<metric>.py``: one reader per metric, ``read(ctx)``
  returning a number, or None where it finds nothing to read.

A reference module (``bench/reference/lm.py`` is one) holds everything
the harness knows of one architecture; nothing of it imports the
program. It has these functions (``REFERENCE_API``):

* ``sizes(published)``: the model's sizes, hashable, from the published
  keys (``ctx["model"]``);
* ``job(traffic)``: integrator and optimizer settings, hashable
  (``ctx["job"]``);
* ``program_fields(published)``: ``{ModelConfig field: value}`` that the
  program's model must have;
* ``layer_leaf(group, index, path)``: the reference's name of the
  program's parameter leaf at ``blocks/<group>/...``: ``group``
  ``"prelude"`` with ``index`` the prelude layer, or ``"period"`` with
  ``index`` the ``k`` of ``sub<k>``, and ``path`` the keys inside that
  layer; None where the block has no such leaf, which stops the run.
  The harness numbers the layers (prelude layers first, then each
  period's sublayers in order) and names a layer's leaf ``L<i>.<name>``;
* ``top_leaf(path)``: the same for a leaf outside ``blocks``;
* ``required_flops_per_token(sizes, job, seq_len)``: what a training
  step requires, for ``train_step_mfu``;
* ``run(seed, sizes, job, batches, matmul, devices)``: the first steps
  from its own weights over the observed ``(tokens, labels)`` batches, in
  float32, spread over the cell's ``devices`` where there are several
  (``bench/reference/placement.py``): ``{"loss": [...], "grad": {leaf:
  norm}, "change": {leaf: norm}}``;
* ``init_params(seed, sizes)``: its weights as ``{"embed", ..., "layers":
  [one dict per layer]}``; ``init_layer(key, sizes)``, ``layer_vjp``
  and ``head_vjp``: one layer's weights and the two jitted blocks whose
  memory ``bench/aot_fit.py`` reckons.

Adding a cell or a metric adds files; so does adding a configuration of a
new architecture: the configuration, its reference module where none
fits, its limits. No file here changes.
"""
from __future__ import annotations

import importlib
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


REFERENCE_API = ("sizes", "job", "program_fields", "layer_leaf", "top_leaf",
                 "required_flops_per_token", "run", "init_params",
                 "init_layer", "layer_vjp", "head_vjp")


def reference(cfile: Dict):
    """The reference module a configuration file names (``lm`` where it
    names none), checked for every function of ``REFERENCE_API``."""
    name = cfile.get("reference", "lm")
    mod = importlib.import_module(f"bench.reference.{name}")
    missing = [f for f in REFERENCE_API if not callable(getattr(mod, f, None))]
    if missing:
        raise SystemExit(f"bench: reference module {name!r} lacks "
                         f"{', '.join(missing)}")
    return mod


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
