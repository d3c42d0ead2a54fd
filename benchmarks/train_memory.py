"""Memory-vs-depth proof on the FULL train step (paper Table 1, taken
end-to-end): AOT-compile ``repro.train.loop.train_step`` for a smoke LM at
growing ODE step budgets and read the backward temp footprint from the
compiled artifact (``memory_analysis().temp_size_in_bytes``).

MALI reconstructs states via psi^-1, so its temp bytes must stay flat
(growth ~1.0x, acceptance <= 1.05x) across a 64x step spread while
Naive/ACA checkpoint per-step residuals and grow linearly. Everything is
lowered from ShapeDtypeStructs — no parameters are materialized, so the
sweep is trace+compile only.
"""
from __future__ import annotations

import dataclasses
from typing import List

import jax
import jax.numpy as jnp

from repro.configs import smoke_config
from repro.core.ode_block import OdeSettings
from repro.launch.specs import param_specs
from repro.optim.optimizer import OptimizerConfig, init_opt_state
from repro.train.loop import train_step

from .common import Row

ARCH = "qwen3-1.7b"
STEPS = (8, 32, 128, 512)
METHODS = (("mali", "alf"), ("naive", "alf"), ("aca", "heun_euler"))
B, S = 2, 16


def _cfg(method: str, solver: str, n_steps: int):
    ode = OdeSettings(mode="per_block", method=method, solver=solver,
                      n_steps=n_steps)
    base = smoke_config(ARCH, ode)
    # one period, no prelude: depth enough for the ODE branches to dominate
    # temps, small enough that 12 AOT compiles stay cheap
    return dataclasses.replace(base, prelude=(), n_periods=1).validate()


def _temp_bytes(method: str, solver: str, n_steps: int) -> int:
    cfg = _cfg(method, solver, n_steps)
    opt_cfg = OptimizerConfig()
    p_spec = param_specs(cfg)
    o_spec = jax.eval_shape(lambda p: init_opt_state(opt_cfg, p), p_spec)
    b_spec = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
              "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}

    def step(p, o, b):
        p2, o2, _, metrics = train_step(p, o, None, b, cfg=cfg,
                                        opt_cfg=opt_cfg)
        return p2, o2, metrics["loss"]

    c = jax.jit(step).lower(p_spec, o_spec, b_spec).compile()
    ma = c.memory_analysis()
    return int(ma.temp_size_in_bytes) if ma else -1


def run() -> List[Row]:
    rows: List[Row] = []
    for method, solver in METHODS:
        series = []
        for n in STEPS:
            b = _temp_bytes(method, solver, n)
            series.append(b)
            rows.append((f"train_memory/temp_bytes/{method}/n={n}", b,
                         f"{ARCH} smoke 1-period B={B} S={S}"))
        growth = series[-1] / max(series[0], 1)
        rows.append((f"train_memory/growth_{STEPS[0]}to{STEPS[-1]}/{method}",
                     growth,
                     "flat~1 (<=1.05) expected for mali; "
                     "~N_t for naive/aca"))
    return rows
