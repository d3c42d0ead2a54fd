"""Step-function builders: the jit targets for training and serving.

``make_train_step`` returns the full production step — loss, grads
(optionally micro-batched accumulation, optionally int8 error-feedback
gradient compression), clip, AdamW/SGD update — as a pure function
(params, opt_state[, ef_state], batch) -> (params, opt_state[, ef], metrics).

The gradient computation itself lives in :mod:`repro.train.loop`
(``loss_and_grads``) — this module keeps the legacy closure-style builder
interface on top of it for callers that pass explicit ``grad_shardings``.
The metrics dict includes the step's integration accounting
(``ode_accepted`` / ``ode_rejected`` / ``ode_fevals``), threaded out of
the jitted step as the loss function's RunStats aux (float0-safe: the
counters are laundered inside the model per R002c).
"""
from __future__ import annotations

from typing import Any, Callable

import jax

from repro.configs.base import ModelConfig
from repro.models import decode_step, lm_loss, prefill
from repro.models.lm import ServeState
from repro.optim.compression import EFState, compress_grads
from repro.optim.optimizer import OptimizerConfig, OptState, apply_updates
from repro.train.loop import loss_and_grads

Pytree = Any
_tm = jax.tree_util.tree_map


def make_loss_fn(cfg: ModelConfig) -> Callable[[Pytree, Pytree], jax.Array]:
    def loss_fn(params, batch):
        return lm_loss(params, cfg, batch)
    return loss_fn


def make_train_step(cfg: ModelConfig, opt_cfg: OptimizerConfig, *,
                    microbatches: int = 1, compress: bool = False,
                    grad_shardings=None):
    """grad_shardings: optional NamedSharding tree applied to the gradients
    before the optimizer — with ZeRO-1-sharded optimizer state this turns
    the DP gradient all-reduce into a reduce-scatter (the update then runs
    sharded and the new params are all-gathered by out_shardings)."""

    def constrain(grads):
        if grad_shardings is None:
            return grads
        return jax.lax.with_sharding_constraint(grads, grad_shardings)

    def finish(params, opt_state, loss, stats, grads):
        params, opt_state, metrics = apply_updates(
            opt_cfg, params, grads, opt_state)
        metrics["loss"] = loss
        metrics["ode_accepted"] = stats.n_accepted
        metrics["ode_rejected"] = stats.n_rejected
        metrics["ode_fevals"] = stats.n_fevals
        metrics["moe_routed"] = stats.moe_routed
        metrics["moe_dropped"] = stats.moe_dropped
        return params, opt_state, metrics

    if compress:
        def train_step(params, opt_state: OptState, ef: EFState, batch):
            loss, stats, grads = loss_and_grads(params, batch, cfg=cfg,
                                                microbatches=microbatches)
            grads, ef = compress_grads(constrain(grads), ef)
            params, opt_state, metrics = finish(params, opt_state, loss,
                                                stats, grads)
            return params, opt_state, ef, metrics
        return train_step

    def train_step(params, opt_state: OptState, batch):
        loss, stats, grads = loss_and_grads(params, batch, cfg=cfg,
                                            microbatches=microbatches)
        params, opt_state, metrics = finish(params, opt_state, loss, stats,
                                            constrain(grads))
        return params, opt_state, metrics

    return train_step


def make_prefill_step(cfg: ModelConfig):
    def prefill_step(params, batch, state: ServeState):
        return prefill(params, cfg, batch, state)
    return prefill_step


def make_decode_step(cfg: ModelConfig):
    def serve_step(params, tokens, state: ServeState):
        return decode_step(params, cfg, tokens, state)
    return serve_step
