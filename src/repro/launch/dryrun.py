"""Multi-pod dry-run: AOT lower + compile every (arch x shape x mesh) cell.

MUST be the very first two lines (before any other import, including
repro.*, since jax locks the device count on first init):
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse       # noqa: E402
import dataclasses    # noqa: E402
import json           # noqa: E402
import time           # noqa: E402
import traceback      # noqa: E402
from typing import Optional  # noqa: E402

import jax            # noqa: E402

from repro.configs import (ARCHS, DEFAULT_ODE, get_config,  # noqa: E402
                           get_shape_cell)
from repro.configs.base import SHAPE_CELLS, cell_applicable  # noqa: E402
from repro.core.ode_block import OdeSettings  # noqa: E402
from repro.distributed.sharding import (batch_shardings,  # noqa: E402
                                        cache_shardings, opt_state_shardings,
                                        param_shardings, replicated)
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.roofline import analyze  # noqa: E402
from repro.launch.specs import (batch_specs, decode_token_specs,  # noqa: E402
                                param_specs, serve_state_specs)
from repro.launch.steps import (make_decode_step, make_prefill_step,  # noqa: E402
                                make_train_step)
from repro.models.lm import ServeState  # noqa: E402
from repro.optim.optimizer import (OptimizerConfig, OptState,  # noqa: E402
                                   init_opt_state)


def _active_params(cfg, params_like) -> float:
    """Active (per-token) parameter count: MoE routed experts scaled by
    top_k/E; embedding table excluded (gather, not matmul)."""
    total = 0.0
    moe_frac = (cfg.moe_top_k / cfg.moe_experts) if cfg.moe_experts else 1.0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params_like)[0]:
        names = [getattr(p, "key", getattr(p, "name", getattr(p, "idx", "")))
                 for p in path]
        names = [str(n) for n in names]
        size = 1
        for s in leaf.shape:
            size *= s
        if names[-1] == "embed":
            continue
        if names[-1] in ("w_gate", "w_up", "w_down") and len(leaf.shape) >= 3 \
                and "mlp" in names:
            size *= moe_frac
        total += size
    return total


def _ode_units(cfg, kind: str) -> float:
    """f-eval flop multiplier per block vs a single discrete fwd pass (=2N).

    MALI fixed-step with n steps: fwd = (n+1) evals; train bwd = per-step
    (inverse 1 + vjp 3) + v0-vjp 3 evals (bwd eval ~ 2x fwd)."""
    if cfg.ode.mode == "off":
        return 6.0 if kind == "train" else 2.0
    n = cfg.ode.n_steps
    fwd = 2.0 * (n + 1)
    if kind != "train":
        return fwd
    bwd = 8.0 * n + 6.0
    return fwd + bwd


def _model_flops(cfg, cell, params_like) -> float:
    n_active = _active_params(cfg, params_like)
    tokens = cell.global_batch * (cell.seq_len if cell.kind != "decode" else 1)
    return _ode_units(cfg, cell.kind) / 2.0 * 2.0 * n_active * tokens


def _opt_sharding_tree(cfg, p_sh, mesh, params_like):
    rep = replicated(mesh)
    z = opt_state_shardings(cfg, mesh, p_sh, params_like)
    return OptState(rep, z, z, z)


def _mem_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    if ma is None:
        return {}
    out = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        val = getattr(ma, attr, None)
        if val is not None:
            out[attr] = int(val)
    if not out:
        out["repr"] = str(ma)
    return out


def run_cell(arch: str, shape: str, multi_pod: bool,
             ode: Optional[OdeSettings] = DEFAULT_ODE,
             microbatches: int = 1, out_dir: str = "reports/dryrun",
             save_hlo: bool = False, variant: str = "",
             attn_bwd: str = "") -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    ode_tag = "ode" if (ode and ode.mode != "off") else "discrete"
    tag = f"{arch}__{shape}__{mesh_name}__{ode_tag}"
    if variant:
        tag += f"__{variant}"
    cell = get_shape_cell(shape)
    cfg = get_config(arch, ode=ode)
    if attn_bwd:
        cfg = dataclasses.replace(cfg, attn_bwd=attn_bwd)
    ok, reason = cell_applicable(cfg, cell)
    record = {"arch": arch, "shape": shape, "mesh": mesh_name,
              "ode": ode_tag, "microbatches": microbatches,
              "variant": variant}
    if not ok:
        record.update(status="skipped", reason=reason)
        return record

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    params_like = param_specs(cfg)
    p_sh = param_shardings(cfg, mesh, params_like)
    model_flops = _model_flops(cfg, cell, params_like)
    n_active = _active_params(cfg, params_like)
    record["active_params"] = n_active
    record["model_flops"] = model_flops

    with mesh:
        if cell.kind == "train":
            opt_cfg = OptimizerConfig()
            opt_like = jax.eval_shape(
                lambda p: init_opt_state(opt_cfg, p), params_like)
            o_sh = _opt_sharding_tree(cfg, p_sh, mesh, params_like)
            b_like = batch_specs(cfg, cell)
            b_sh = batch_shardings(cfg, mesh, b_like)
            # pin grads to their params' sharding (replicated for 'dp')
            # right after backward — blocks the ZeRO-1 opt-state sharding
            # from propagating into the loss graph (measured 10x flop blowup
            # otherwise; see EXPERIMENTS.md §Perf)
            step = make_train_step(cfg, opt_cfg, microbatches=microbatches,
                                   grad_shardings=p_sh)
            rep = replicated(mesh)
            metrics_sh = {"lr": rep, "grad_norm": rep, "loss": rep,
                          "ode_accepted": rep, "ode_rejected": rep,
                          "ode_fevals": rep, "moe_routed": rep,
                          "moe_dropped": rep}
            jitted = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                             out_shardings=(p_sh, o_sh, metrics_sh),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params_like, opt_like, b_like)
        elif cell.kind == "prefill":
            b_like = batch_specs(cfg, cell)
            b_sh = batch_shardings(cfg, mesh, b_like)
            st_like = serve_state_specs(cfg, cell)
            st_sh = ServeState(
                cache_shardings(cfg, mesh, st_like.cache, cell.global_batch),
                replicated(mesh))
            step = make_prefill_step(cfg)
            jitted = jax.jit(step, in_shardings=(p_sh, b_sh, st_sh),
                             out_shardings=(replicated(mesh), st_sh),
                             donate_argnums=(2,))
            lowered = jitted.lower(params_like, b_like, st_like)
        else:  # decode
            tok_like = decode_token_specs(cfg, cell)
            tok_sh = batch_shardings(cfg, mesh, {"t": tok_like})["t"]
            st_like = serve_state_specs(cfg, cell)
            st_sh = ServeState(
                cache_shardings(cfg, mesh, st_like.cache, cell.global_batch),
                replicated(mesh))
            step = make_decode_step(cfg)
            jitted = jax.jit(step, in_shardings=(p_sh, tok_sh, st_sh),
                             out_shardings=(replicated(mesh), st_sh),
                             donate_argnums=(2,))
            lowered = jitted.lower(params_like, tok_like, st_like)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = _mem_dict(compiled)
    roof = analyze(compiled, chips=chips, model_flops=model_flops,
                   default_group=16)
    record.update(
        status="ok", chips=chips, lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1), memory=mem,
        roofline=roof.to_dict())

    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, tag + ".json"), "w") as f:
        json.dump(record, f, indent=2, default=float)
    if save_hlo:
        with open(os.path.join(out_dir, tag + ".hlo.txt"), "w") as f:
            f.write(compiled.as_text())
    print(compiled.memory_analysis())
    try:
        print({k: v for k, v in (compiled.cost_analysis() or {}).items()
               if k in ("flops", "bytes accessed")})
    except Exception:
        pass
    return record


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--ode", default="on", choices=["on", "off"])
    ap.add_argument("--ode-steps", type=int, default=2)
    ap.add_argument("--fused-bwd", default="on", choices=["on", "off"])
    ap.add_argument("--attn-bwd", default="flash", choices=["flash", "autodiff"])
    ap.add_argument("--variant", default="", help="tag for A/B records")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--out", default="reports/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    args = ap.parse_args()

    archs = sorted(ARCHS) if args.arch == "all" else [args.arch]
    shapes = ([c.name for c in SHAPE_CELLS] if args.shape == "all"
              else [args.shape])
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    ode = (dataclasses.replace(DEFAULT_ODE, n_steps=args.ode_steps,
                               fused_bwd=args.fused_bwd == "on")
           if args.ode == "on" else OdeSettings(mode="off"))

    summary_path = os.path.join(args.out, "summary.jsonl")
    os.makedirs(args.out, exist_ok=True)
    n_ok = n_skip = n_fail = 0
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                label = f"{arch} x {shape} x {'multi' if multi else 'single'}"
                print(f"=== {label} ===", flush=True)
                try:
                    rec = run_cell(arch, shape, multi, ode,
                                   microbatches=args.microbatches,
                                   out_dir=args.out, save_hlo=args.save_hlo,
                                   variant=args.variant,
                                   attn_bwd=args.attn_bwd)
                except Exception as e:
                    traceback.print_exc()
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "pod2x16x16" if multi else "pod16x16",
                           "status": "error", "error": f"{type(e).__name__}: {e}"}
                with open(summary_path, "a") as f:
                    f.write(json.dumps(rec, default=float) + "\n")
                st = rec.get("status")
                n_ok += st == "ok"
                n_skip += st == "skipped"
                n_fail += st == "error"
                print(f"--- {label}: {st}", flush=True)
    print(f"dry-run complete: {n_ok} ok, {n_skip} skipped (per assignment "
          f"rule), {n_fail} failed")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
