"""Dense gated-MLP (SwiGLU) feed-forward."""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from .common import dense_init, silu

Pytree = Any


def init_mlp(key: jax.Array, cfg: ModelConfig, d_ff: int) -> Pytree:
    dt = jnp.dtype(cfg.param_dtype)
    kg, ku, kd = jax.random.split(key, 3)
    d = cfg.d_model
    return {
        "w_gate": dense_init(kg, (d, d_ff), dt),
        "w_up": dense_init(ku, (d, d_ff), dt),
        "w_down": dense_init(kd, (d_ff, d), dt, fan_in=d_ff),
    }


@jax.named_scope("mlp")
def apply_mlp(params: Pytree, x: jax.Array) -> jax.Array:
    return (silu(x @ params["w_gate"]) * (x @ params["w_up"])) @ params["w_down"]
