"""Rehearse a cell's memory without the chip: compile its training step,
and the reference's largest blocks, for a described TPU v5e and print
``memory_analysis()``.

    JAX_PLATFORMS=cpu python3 bench/aot_fit.py --workload <cell> [--batch N]

Nothing runs; the numbers are the compiler's for one program at a time
(donated arguments and temporaries counted as if both were live). The
step is the one ``Trainer.train`` jits (``jitted_train_step``) at the
cell's sizes, on one chip of a described ``v5e:2x2``.
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
from bench.reference import lm as reference  # noqa: E402

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


def reference_memory(cfile, traffic, one) -> dict:
    """The reference's per-row layer VJP and head VJP for one sequence."""
    m = reference.sizes(cfile["published"])
    job = reference.job(traffic)
    s, d = traffic["seq_len"], m.d_model
    lp = jax.eval_shape(lambda: reference._init_layer(
        jax.random.PRNGKey(0), m))
    lp = _shaped(lp, one)
    x = jax.ShapeDtypeStruct((s, d), jnp.float32, sharding=one)
    out = {"layer_vjp": _mem(reference._layer_vjp.lower(
        lp, lp, x, x, m, job, None).compile())}
    head = jax.ShapeDtypeStruct((d, m.vocab_size), jnp.float32, sharding=one)
    scale = jax.ShapeDtypeStruct((d,), jnp.float32, sharding=one)
    lab = jax.ShapeDtypeStruct((s,), jnp.int32, sharding=one)
    f32 = jax.ShapeDtypeStruct((), jnp.float32, sharding=one)
    out["head_vjp"] = _mem(reference._head_vjp.lower(
        head, scale, head, x, lab, f32, None, m.param_dtype).compile())
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batch", type=int, default=0,
                    help="global batch (default: the traffic file's)")
    ap.add_argument("--reference", action="store_true",
                    help="also compile the reference's blocks")
    a = ap.parse_args(argv)
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    bm = spec.benchmark()
    w = spec.workload(bm, a.workload)
    cfile, traffic = spec.config(bm, w["config"]), spec.traffic(w["traffic"])
    batch = a.batch or int(traffic["global_batch"])
    res = {"workload": a.workload, "global_batch": batch,
           "step": step_memory(cfile, traffic, batch, one)}
    if a.reference:
        res["reference"] = reference_memory(cfile, traffic, one)
    print(json.dumps(res, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
