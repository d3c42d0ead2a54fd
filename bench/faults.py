"""Faults a training cell can have, planted under the Trainer's train loop.

Each wraps the loop the Trainer runs (``Trainer.loop``) so that a whole
benchmark run, window and comparison included, drives the broken step:

* :class:`Unchanged`: a step that returns its state unchanged;
* :class:`HalfBatch`: half of the batch left out, the mean taken over
  the rest;
* :class:`FrozenNorms`: the norms' scales (q/k-norm, the attention, MLP
  and final norms) get no gradient and no update: the small leaves that
  a comparison scaled by the median leaf cannot see.

``bench/calibrate.py`` reads them on the chip; the CPU tests drive them
through the harness at smoke size.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.train.loop import TrainLoop


def half_batch(batch):
    """Half of the batch: half of its rows, or, for a batch of one
    sequence, the first half of its positions (a causal model's loss over
    them is the mean over those tokens)."""
    rows = next(iter(batch.values())).shape[0]
    if rows > 1:
        return {k: v[: rows // 2] for k, v in batch.items()}
    return {k: v[:, : v.shape[1] // 2] for k, v in batch.items()}


def _copy(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)


class Unchanged(TrainLoop):
    """A step that returns its state unchanged."""

    def __init__(self, inner: TrainLoop):
        self.inner, self.name = inner, inner.name

    def init_carry(self, params):
        return self.inner.init_carry(params)

    def step(self, params, opt_state, carry, batch, **kw):
        _, _, carry, metrics = self.inner.step(_copy(params), _copy(opt_state),
                                               carry, batch, **kw)
        return params, opt_state, carry, metrics


class HalfBatch(Unchanged):
    """Half of the batch left out, the mean taken over the rest."""

    def step(self, params, opt_state, carry, batch, **kw):
        return self.inner.step(params, opt_state, carry, half_batch(batch),
                               **kw)


def _is_scale(path) -> bool:
    return str(getattr(path[-1], "key", "")) == "scale"


def _keep_scales(new, old):
    """``new`` with every norm's scale taken from ``old``."""
    return jax.tree_util.tree_map_with_path(
        lambda p, n, o: o if _is_scale(p) else n, new, old)


class FrozenNorms(Unchanged):
    """The norms' scales keep their weights, moments and master copy: as
    if they got no gradient and no update."""

    def step(self, params, opt_state, carry, batch, **kw):
        keep = jax.tree_util.tree_map_with_path(
            lambda p, x: jnp.copy(x) if _is_scale(p) else None,
            (params, opt_state.m, opt_state.v, opt_state.master))
        params, opt_state, carry, metrics = self.inner.step(
            params, opt_state, carry, batch, **kw)
        p, m, v, w = keep
        return (_keep_scales(params, p),
                opt_state._replace(m=_keep_scales(opt_state.m, m),
                                   v=_keep_scales(opt_state.v, v),
                                   master=_keep_scales(opt_state.master, w)),
                carry, metrics)


FAULTS = {"unchanged": Unchanged, "half_batch": HalfBatch,
          "frozen_norms": FrozenNorms}
