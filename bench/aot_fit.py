"""Rehearse a cell's memory without the chip: compile its training step,
and the reference's largest blocks, for a described TPU v5e and print
``memory_analysis()``; reckon the reference's bytes on each chip.

    JAX_PLATFORMS=cpu python3 bench/aot_fit.py --workload <cell> \
        [--batch N] [--reference [--chips C] [--layers L]]

Nothing runs; the numbers are the compiler's for one program at a time
(donated arguments and temporaries counted as if both were live). The
step is the one ``Trainer.train`` jits (``jitted_train_step``) at the
cell's sizes, on one chip of a described ``v5e:2x2``. The reference's
blocks are those of the module the cell's configuration names.

``--reference`` also reckons the reference's bytes on each of ``--chips``
chips (default: the cell's), placed as ``bench/reference/placement.py``
places them: each chip's leaves once as weights and once per reference
step as gradients (the accumulator and the history of the steps before),
a tied head's copy and its gradient on the last chip and their sum into
the embedding's gradient on the first, one sequence's layer inputs, and
the larger of the temporaries and new outputs of the blocks that run on
the chip (the optimizer's update compiles to none). ``--layers`` reckons
the reference of the configuration at another depth; the step is then
not compiled.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from bench import cell, spec  # noqa: E402
from bench.reference.placement import layer_chip  # noqa: E402

GB = 1e9


def _mem(compiled) -> dict:
    ma = compiled.memory_analysis()
    out = {k: getattr(ma, k) / GB for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "alias_size_in_bytes", "temp_size_in_bytes")}
    out["total_gb"] = (out["argument_size_in_bytes"]
                       + out["output_size_in_bytes"]
                       - out["alias_size_in_bytes"]
                       + out["temp_size_in_bytes"])
    return out


def _shaped(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def step_memory(cfile, traffic, batch: int, one) -> dict:
    from repro.models import init_lm
    from repro.optim.optimizer import init_opt_state
    from repro.train.loop import jitted_train_step
    from repro.train.trainer import build
    tc = cell.trainer_config(cfile, traffic, seed=0)
    cfg = cell.model_config(cfile).with_ode(tc.ode_settings()).validate()
    _, _, opt_cfg = build(tc)
    params = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg))
    opt = jax.eval_shape(lambda p: init_opt_state(opt_cfg, p), params)
    s = tc.seq_len
    b = {"tokens": jax.ShapeDtypeStruct((batch, s), jnp.int32, sharding=one),
         "labels": jax.ShapeDtypeStruct((batch, s), jnp.int32, sharding=one)}
    t = time.time()
    compiled = jitted_train_step.lower(
        _shaped(params, one), _shaped(opt, one), None, b, cfg=cfg,
        opt_cfg=opt_cfg, microbatches=1, compress=False,
        zero1=False).compile()
    out = _mem(compiled)
    out["compile_s"] = time.time() - t
    out["tpu_custom_call"] = compiled.as_text().count("tpu_custom_call")
    return out


def _bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree))


def _transient(mem: dict) -> float:
    """GB a block adds to what is already resident: its temporaries and
    the outputs that do not reuse a donated argument."""
    return (mem["temp_size_in_bytes"] + mem["output_size_in_bytes"]
            - mem["alias_size_in_bytes"])


def reference_memory(reference, m, traffic, one) -> dict:
    """The reference's per-row layer VJP and head VJP for one sequence."""
    job = reference.job(traffic)
    s, d = traffic["seq_len"], m.d_model
    lp = jax.eval_shape(lambda: reference.init_layer(
        jax.random.PRNGKey(0), m))
    lp = _shaped(lp, one)
    x = jax.ShapeDtypeStruct((s, d), jnp.float32, sharding=one)
    out = {"layer_vjp": _mem(reference.layer_vjp.lower(
        lp, lp, x, x, m, job, None).compile())}
    head = jax.ShapeDtypeStruct((d, m.vocab_size), jnp.float32, sharding=one)
    scale = jax.ShapeDtypeStruct((d,), jnp.float32, sharding=one)
    lab = jax.ShapeDtypeStruct((s,), jnp.int32, sharding=one)
    f32 = jax.ShapeDtypeStruct((), jnp.float32, sharding=one)
    out["head_vjp"] = _mem(reference.head_vjp.lower(
        head, scale, head, x, lab, f32, None, m.param_dtype).compile())
    return out


def reference_chips(reference, m, traffic, chips: int, blocks: dict
                    ) -> list:
    """The reference's reckoned GB on each of ``chips`` chips."""
    shapes = jax.eval_shape(lambda: reference.init_params(0, m))
    steps = int(traffic["reference_steps"])
    row = traffic["seq_len"] * m.d_model * 4      # one float32 layer input
    out = [{"chip": c, "layers": [], "top": [], "leaf_gb": 0.0,
            "other_gb": 0.0, "blocks": []} for c in range(chips)]
    for i, lp in enumerate(shapes["layers"]):
        c = out[layer_chip(i, len(shapes["layers"]), chips)]
        c["layers"].append(i)
        c["leaf_gb"] += _bytes(lp) / GB
        c["other_gb"] += row / GB
    for k, x in shapes.items():
        if k != "layers":
            c = out[0] if k == "embed" else out[-1]
            c["top"].append(k)
            c["leaf_gb"] += _bytes(x) / GB
    out[-1]["other_gb"] += row / GB
    if "head" not in shapes:                      # a tied head
        out[0]["other_gb"] += 2 * _bytes(shapes["embed"]) / GB
        out[-1]["other_gb"] += 2 * _bytes(shapes["embed"]) / GB
    for c in out:
        if c["layers"]:
            c["blocks"].append("layer_vjp")
        if c is out[-1]:
            c["blocks"].append("head_vjp")
        peak = max([_transient(blocks[b]) for b in c["blocks"]] + [0.0])
        c["total_gb"] = (1 + steps) * c["leaf_gb"] + c["other_gb"] + peak
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batch", type=int, default=0,
                    help="global batch (default: the traffic file's)")
    ap.add_argument("--reference", action="store_true",
                    help="also compile the reference's blocks and reckon "
                         "its bytes on each chip")
    ap.add_argument("--chips", type=int, default=0,
                    help="chips the reference spreads over (default: the "
                         "cell's)")
    ap.add_argument("--layers", type=int, default=0,
                    help="reckon the reference at this depth (default: "
                         "the configuration's); skips the step")
    a = ap.parse_args(argv)
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    bm = spec.benchmark()
    w = spec.workload(bm, a.workload)
    cfile, traffic = spec.config(bm, w["config"]), spec.traffic(w["traffic"])
    batch = a.batch or int(traffic["global_batch"])
    res = {"workload": a.workload, "global_batch": batch}
    if not a.layers:
        res["step"] = step_memory(cfile, traffic, batch, one)
    if a.reference or a.layers:
        reference = spec.reference(cfile)
        published = dict(cfile["published"])
        if a.layers:
            published["num_hidden_layers"] = a.layers
        m = reference.sizes(published)
        blocks = reference_memory(reference, m, traffic, one)
        chips = a.chips or int(w["chips"])
        res["reference"] = dict(
            blocks, n_layers=m.n_layers,
            chips=reference_chips(reference, m, traffic, chips, blocks))
    print(json.dumps(res, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
