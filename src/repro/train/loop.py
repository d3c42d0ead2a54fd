"""Train-step construction and the TrainLoop registry.

The step is a *pure module-level function* jitted once with value-hashed
static configs (``ModelConfig`` / ``OptimizerConfig`` are frozen dataclasses
hashing by value), so fresh-but-equal config instances reuse one trace —
the retrace contract ``analysis.trace_audit.run_train_audit`` checks.

Gradients come from ``jax.value_and_grad(lm_loss_and_stats, has_aux=True)``:
the continuous-depth model's residual branches are native
``solve(..., gradient=MALI(...))`` calls, and the aux
:class:`~repro.models.transformer.TrainStats` threads the per-step
integration accounting (f-evals, accepted/rejected trials) and the MoE
routing counts out of the jitted step — the counters are laundered inside
the model (R002c), so summing them over a microbatch scan here is
float0-safe.

:class:`TrainLoop` is the registered driver axis (R004 lint: every
registered loop overrides every abstract member and appears in tests):
:class:`StandardLoop` carries no extra state, :class:`CompressedLoop`
threads int8 error-feedback compression state through the step.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.distributed.sharding import ambient_mesh, param_shardings
from repro.models.lm import lm_loss_and_stats
from repro.models.transformer import (TrainStats, add_train_stats,
                                      zero_train_stats)
from repro.optim.compression import EFState, compress_grads, init_ef_state
from repro.optim.optimizer import OptimizerConfig, OptState, apply_updates

Pytree = Any
_tm = jax.tree_util.tree_map


def _split_microbatches(batch: Pytree, n: int) -> Pytree:
    return _tm(lambda a: a.reshape((n, a.shape[0] // n) + a.shape[1:]), batch)


def loss_and_grads(params: Pytree, batch: Pytree, *, cfg: ModelConfig,
                   microbatches: int = 1
                   ) -> Tuple[jax.Array, TrainStats, Pytree]:
    """(mean loss, summed TrainStats, mean grads) for one global batch.

    With ``microbatches > 1`` the global batch is split on its leading axis
    and accumulated through a ``lax.scan`` (sequential — peak memory is one
    microbatch's activations). Loss and grads are averaged over
    microbatches; the integration counters are *summed* (they count work
    actually done, so the total must not shrink with the split).
    """
    vg = jax.value_and_grad(lm_loss_and_stats, has_aux=True)

    def one(p, b):
        (loss, stats), grads = vg(p, cfg, b)
        return loss, stats, grads

    if microbatches <= 1:
        return one(params, batch)
    mbs = _split_microbatches(batch, microbatches)

    def acc(carry, mb):
        loss_acc, stats_acc, g_acc = carry
        loss, stats, g = one(params, mb)
        return (loss_acc + loss, add_train_stats(stats_acc, stats),
                _tm(jnp.add, g_acc, g)), None

    zeros = _tm(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (loss, stats, grads), _ = lax.scan(
        acc, (jnp.float32(0.0), zero_train_stats(), zeros), mbs)
    inv = 1.0 / microbatches
    return loss * inv, stats, _tm(lambda g: g * inv, grads)


def train_step(params: Pytree, opt_state: OptState, ef: Optional[EFState],
               batch: Pytree, *, cfg: ModelConfig, opt_cfg: OptimizerConfig,
               microbatches: int = 1, compress: bool = False,
               zero1: bool = False
               ) -> Tuple[Pytree, OptState, Optional[EFState], Dict]:
    """One full training step as a pure function.

    ``zero1=True`` constrains the gradients to the parameter shardings of
    the ambient mesh before the optimizer: with ZeRO-1-sharded optimizer
    state this turns the DP gradient all-reduce into a reduce-scatter.
    ``compress=True`` routes the (constrained) gradients through int8
    error-feedback compression, threading ``ef``.
    """
    loss, stats, grads = loss_and_grads(params, batch, cfg=cfg,
                                        microbatches=microbatches)
    if zero1:
        mesh = ambient_mesh()
        if mesh is not None and mesh.size > 1:
            grads = jax.lax.with_sharding_constraint(
                grads, param_shardings(cfg, mesh, grads))
    if compress:
        grads, ef = compress_grads(grads, ef)
    params, opt_state, metrics = apply_updates(opt_cfg, params, grads,
                                               opt_state)
    metrics["loss"] = loss
    metrics["ode_accepted"] = stats.n_accepted
    metrics["ode_rejected"] = stats.n_rejected
    metrics["ode_fevals"] = stats.n_fevals
    metrics["moe_routed"] = stats.moe_routed
    metrics["moe_dropped"] = stats.moe_dropped
    return params, opt_state, ef, metrics


# One module-level jit: every Trainer instance (and every fresh-but-equal
# config) shares this cache. cfg/opt_cfg hash by value, so a restored run
# rebuilds its configs from the checkpoint manifest without retracing.
# params and opt_state are donated: without it the step holds the old and
# the new training state at once, twice the ~10 bytes/param of state.
jitted_train_step = jax.jit(
    train_step, static_argnames=("cfg", "opt_cfg", "microbatches",
                                 "compress", "zero1"),
    donate_argnums=(0, 1))


class TrainLoop:
    """Base of the training-loop axis: how one optimizer step is driven.

    A loop owns the step's *extra state* (``carry`` — e.g. error-feedback
    compression state) and maps ``(params, opt_state, carry, batch)`` to
    their successors plus a metrics dict. Subclasses are frozen dataclasses
    registered in :data:`TRAIN_LOOPS`.
    """

    name: str = "?"

    def init_carry(self, params: Pytree) -> Pytree:
        """Initial extra state for this loop (None when stateless)."""
        raise NotImplementedError

    def step(self, params: Pytree, opt_state: OptState, carry: Pytree,
             batch: Pytree, *, cfg: ModelConfig, opt_cfg: OptimizerConfig,
             microbatches: int = 1, zero1: bool = False
             ) -> Tuple[Pytree, OptState, Pytree, Dict]:
        """One optimizer step; returns (params, opt_state, carry, metrics)."""
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class StandardLoop(TrainLoop):
    """Plain AdamW step (no gradient compression; carry is None)."""

    name = "standard"

    def init_carry(self, params: Pytree) -> None:
        return None

    def step(self, params, opt_state, carry, batch, *, cfg, opt_cfg,
             microbatches=1, zero1=False):
        params, opt_state, _, metrics = jitted_train_step(
            params, opt_state, None, batch, cfg=cfg, opt_cfg=opt_cfg,
            microbatches=microbatches, compress=False, zero1=zero1)
        return params, opt_state, None, metrics


@dataclasses.dataclass(frozen=True)
class CompressedLoop(TrainLoop):
    """int8 error-feedback gradient compression; carry is the EF residual
    (part of the resumable state — dropping it on restore silently changes
    the gradient stream)."""

    name = "compressed"

    def init_carry(self, params: Pytree) -> EFState:
        return init_ef_state(params)

    def step(self, params, opt_state, carry, batch, *, cfg, opt_cfg,
             microbatches=1, zero1=False):
        params, opt_state, carry, metrics = jitted_train_step(
            params, opt_state, carry, batch, cfg=cfg, opt_cfg=opt_cfg,
            microbatches=microbatches, compress=True, zero1=zero1)
        return params, opt_state, carry, metrics


TRAIN_LOOPS: Dict[str, TrainLoop] = {
    "standard": StandardLoop(),
    "compressed": CompressedLoop(),
}


def get_train_loop(name: str) -> TrainLoop:
    try:
        return TRAIN_LOOPS[name]
    except KeyError:
        raise ValueError(f"unknown train loop {name!r}; "
                         f"choose from {sorted(TRAIN_LOOPS)}") from None
