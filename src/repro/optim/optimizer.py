"""Optimizers: AdamW (low-precision moments + f32 master weights) and SGD,
with warmup+cosine schedule and global-norm clipping.

Memory posture for the large archs (DESIGN.md §6): params live in bf16; the
optimizer carries an f32 master copy plus bf16 m/v by default (8 bytes/param
of state). All optimizer state is sharded exactly like the parameters (and
additionally over 'data' for fsdp_tp archs) — ZeRO-1 falls out of the
sharding spec, not the math.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp

Pytree = Any
_tm = jax.tree_util.tree_map


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    name: str = "adamw"            # 'adamw' | 'sgd'
    peak_lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10000
    min_lr_ratio: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    momentum_dtype: str = "bfloat16"   # m/v storage dtype
    master_dtype: str = "float32"      # master weight copy ('' = none)
    momentum: float = 0.9              # sgd


class OptState(NamedTuple):
    step: jax.Array
    m: Pytree
    v: Pytree          # sgd: zeros-like placeholder (empty leaves)
    master: Pytree     # f32 master copy ('' master_dtype -> params alias)


def lr_schedule(cfg: OptimizerConfig, step: jax.Array) -> jax.Array:
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    t = jnp.clip((step - cfg.warmup_steps)
                 / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1), 0.0, 1.0)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * t))
    decay = cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * cos
    return cfg.peak_lr * warm * decay


def global_norm(tree: Pytree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(l.astype(jnp.float32)))
              for l in jax.tree_util.tree_leaves(tree)]
    return jnp.sqrt(sum(leaves))


def clip_by_global_norm(grads: Pytree, max_norm: float) -> Tuple[Pytree, jax.Array]:
    """max_norm <= 0 disables clipping (norm still computed for metrics)."""
    norm = global_norm(grads)
    if max_norm <= 0:
        return grads, norm
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return _tm(lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype),
               grads), norm


def init_opt_state(cfg: OptimizerConfig, params: Pytree) -> OptState:
    mdt = jnp.dtype(cfg.momentum_dtype)
    m = _tm(lambda p: jnp.zeros(p.shape, mdt), params)
    if cfg.name == "adamw":
        v = _tm(lambda p: jnp.zeros(p.shape, mdt), params)
    else:
        v = _tm(lambda p: jnp.zeros((0,), jnp.float32), params)
    if cfg.master_dtype:
        # a copy even where the dtypes match: the train step donates params
        # and optimizer state, and one buffer cannot be donated twice
        master = _tm(lambda p: jnp.array(p, jnp.dtype(cfg.master_dtype),
                                         copy=True), params)
    else:
        master = _tm(lambda p: jnp.zeros((0,), jnp.float32), params)
    return OptState(jnp.zeros((), jnp.int32), m, v, master)


@jax.named_scope("optimizer")
def apply_updates(cfg: OptimizerConfig, params: Pytree, grads: Pytree,
                  state: OptState) -> Tuple[Pytree, OptState, dict]:
    """One optimizer step; returns (new_params, new_state, metrics)."""
    grads, gnorm = clip_by_global_norm(grads, cfg.clip_norm)
    step = state.step + 1
    lr = lr_schedule(cfg, step)
    mdt = jnp.dtype(cfg.momentum_dtype)

    def current_master(p, mw):
        return mw.astype(jnp.float32) if cfg.master_dtype else p.astype(jnp.float32)

    if cfg.name == "adamw":
        bc1 = 1 - cfg.b1 ** step.astype(jnp.float32)
        bc2 = 1 - cfg.b2 ** step.astype(jnp.float32)

        def upd(p, g, m, v, mw):
            gf = g.astype(jnp.float32)
            mf = cfg.b1 * m.astype(jnp.float32) + (1 - cfg.b1) * gf
            vf = cfg.b2 * v.astype(jnp.float32) + (1 - cfg.b2) * gf * gf
            mhat = mf / bc1
            vhat = vf / bc2
            w = current_master(p, mw)
            w = w - lr * (mhat / (jnp.sqrt(vhat) + cfg.eps)
                          + cfg.weight_decay * w)
            new_master = w.astype(jnp.dtype(cfg.master_dtype)) if cfg.master_dtype else mw
            return w.astype(p.dtype), mf.astype(mdt), vf.astype(mdt), new_master

        out = _tm(upd, params, grads, state.m, state.v, state.master)
        new_params = _tm(lambda o: o[0], out, is_leaf=lambda o: isinstance(o, tuple))
        new_m = _tm(lambda o: o[1], out, is_leaf=lambda o: isinstance(o, tuple))
        new_v = _tm(lambda o: o[2], out, is_leaf=lambda o: isinstance(o, tuple))
        new_master = _tm(lambda o: o[3], out, is_leaf=lambda o: isinstance(o, tuple))
        return (new_params, OptState(step, new_m, new_v, new_master),
                {"lr": lr, "grad_norm": gnorm})

    # SGD + momentum (the paper's Cifar/ImageNet optimizer)
    def upd_sgd(p, g, m, mw):
        gf = g.astype(jnp.float32)
        w = current_master(p, mw)
        gf = gf + cfg.weight_decay * w
        mf = cfg.momentum * m.astype(jnp.float32) + gf
        w = w - lr * mf
        new_master = w.astype(jnp.dtype(cfg.master_dtype)) if cfg.master_dtype else mw
        return w.astype(p.dtype), mf.astype(mdt), new_master

    out = _tm(upd_sgd, params, grads, state.m, state.master)
    new_params = _tm(lambda o: o[0], out, is_leaf=lambda o: isinstance(o, tuple))
    new_m = _tm(lambda o: o[1], out, is_leaf=lambda o: isinstance(o, tuple))
    new_master = _tm(lambda o: o[2], out, is_leaf=lambda o: isinstance(o, tuple))
    return (new_params, OptState(step, new_m, state.v, new_master),
            {"lr": lr, "grad_norm": gnorm})
