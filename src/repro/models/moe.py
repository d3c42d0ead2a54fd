"""Mixture-of-Experts FFN: a top-k softmax router over every expert, this
layer's share of the experts computed under a device budget, optional
shared experts (DeepSeekMoE-style fine-grained + shared).

Expert parallelism divides a layer's ``moe_experts`` over the chips of a
group. A layer is told which experts it holds (``moe_first_expert``, and
``moe_experts_held`` of them; all by default), routes every token over all
experts, and computes its held experts' part of the result. Without the
group's exchange, what the absent experts would add is left out; the
shares' parts, with the shared experts counted once, add up to the whole
layer.

The held experts compute under a device-level budget (DeepSeek-V2 §3.2.3,
token dropping): ``B = ceil(N k held / E * factor)`` rows per call, the
factor being ``moe_capacity_factor`` (``moe_eval_capacity_factor`` in
eval mode). Over it the held assignments of lowest affinity (routing
probability) drop, ties broken by (token, choice) order, and contribute
0. A layer that holds every expert never drops at a factor of 1 or more.
``B`` is static, so the layer's work does not depend on the routing: rows
that the held assignments leave empty compute a token with gate 0 (the
token of an assignment held elsewhere, so that no one token collects
them in the scatter back).

The kept rows are sorted by expert and the SwiGLU experts run as grouped
products over them (``jax.lax.ragged_dot``); the results are scattered
back, weighted by the gate values (renormalised over the top k only with
``moe_norm_topk``). No ``[E, capacity, D]`` buffer is padded.

Routing is a deterministic function of (z, t) ⇒ the ALF inverse re-derives
identical routing decisions during MALI's backward reconstruction (DESIGN.md
§Arch-applicability).
"""
from __future__ import annotations

import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from .common import dense_init, silu
from .mlp import apply_mlp, init_mlp

Pytree = Any


class MoeCounts(NamedTuple):
    """Assignments of one MoE call's tokens to the held experts, and how
    many of them the budget dropped."""
    routed: jax.Array
    dropped: jax.Array


def init_moe(key: jax.Array, cfg: ModelConfig) -> Pytree:
    """The router over all ``moe_experts``; the held experts' weights."""
    dt = jnp.dtype(cfg.param_dtype)
    d, dff = cfg.d_model, cfg.moe_d_ff or cfg.d_ff
    e, held = cfg.moe_experts, cfg.n_experts_held
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    params = {
        "router": dense_init(kr, (d, e), jnp.float32),  # router kept f32
        "w_gate": dense_init(kg, (held, d, dff), dt, fan_in=d),
        "w_up": dense_init(ku, (held, d, dff), dt, fan_in=d),
        "w_down": dense_init(kd, (held, dff, d), dt, fan_in=dff),
    }
    if cfg.moe_shared_experts > 0:
        params["shared"] = init_mlp(ks, cfg, dff * cfg.moe_shared_experts)
    return params


def budget(n_tokens: int, cfg: ModelConfig, factor: float) -> int:
    """Rows the held experts compute for ``n_tokens`` tokens."""
    k = cfg.moe_top_k
    rows = math.ceil(n_tokens * k * cfg.n_experts_held / cfg.moe_experts
                     * factor)
    return max(1, min(rows, n_tokens * k))


def _route(params: Pytree, cfg: ModelConfig, xt: jax.Array):
    """Top-k routing probabilities [N, k], the chosen experts' ids less
    ``moe_first_expert`` [N*k], and which of those are held [N*k]."""
    logits = xt.astype(jnp.float32) @ params["router"]          # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    vals, idx = lax.top_k(probs, cfg.moe_top_k)                 # [N, k]
    local = idx.reshape(-1) - cfg.moe_first_expert
    return vals, local, (local >= 0) & (local < cfg.n_experts_held)


def route_counts(params: Pytree, cfg: ModelConfig, x: jax.Array
                 ) -> MoeCounts:
    """The routing counts of ``apply_moe`` (training) on ``x`` [B, S, D],
    int32."""
    xt = x.reshape(-1, x.shape[-1])
    _, _, held = _route(params, cfg, xt)
    routed = held.sum(dtype=jnp.int32)
    rows = budget(xt.shape[0], cfg, cfg.moe_capacity_factor)
    return MoeCounts(routed, jnp.maximum(routed - rows, 0))


def dispatch(vals: jax.Array, local: jax.Array, is_held: jax.Array,
             rows: int, held: int):
    """The budget's rows, grouped by held expert: (assignment index [B],
    kept [B], group sizes [held]). ``vals`` [N, k] are the top-k
    probabilities (the affinities), ``local`` and ``is_held`` [N*k] as
    ``_route`` gives them. Over budget, the highest-affinity held
    assignments are kept (``top_k`` puts the lower index first among
    equals: (token, choice) order); rows left empty go to the last group
    and are not kept. Within a group, rows are in (token, choice) order."""
    n_assign = local.shape[0]
    if rows < n_assign:
        _, pick = lax.top_k(jnp.where(is_held, vals.reshape(-1), -1.0), rows)
    else:
        pick = jnp.arange(n_assign, dtype=jnp.int32)
    keep = is_held[pick]
    group = jnp.where(keep, local[pick], held - 1)
    order = jnp.argsort(group * n_assign + pick)
    sizes = jnp.bincount(group, length=held).astype(jnp.int32)
    return pick[order], keep[order], sizes


@jax.named_scope("moe")
def apply_moe(params: Pytree, cfg: ModelConfig, x: jax.Array,
              eval_mode: bool = False) -> jax.Array:
    """x: [B, S, D] -> [B, S, D]. eval_mode uses the (laxer) serve-time
    budget factor — inference should be (near-)dropless."""
    b, s, d = x.shape
    n, k = b * s, cfg.moe_top_k
    cdt = jnp.dtype(cfg.compute_dtype)
    factor = (cfg.moe_eval_capacity_factor if eval_mode
              else cfg.moe_capacity_factor)
    xt = x.reshape(n, d)
    vals, local, is_held = _route(params, cfg, xt)
    gates = vals
    if cfg.moe_norm_topk:
        gates = vals / jnp.maximum(vals.sum(-1, keepdims=True), 1e-9)

    with jax.named_scope("moe_dispatch"):
        pick, keep, sizes = dispatch(
            vals, local, is_held, budget(n, cfg, factor),
            cfg.n_experts_held)
        tok = pick // k
        w = jnp.where(keep, gates.reshape(-1)[pick], 0.0)
        xs = xt[tok].astype(cdt)                          # [B, D]

    with jax.named_scope("moe_experts"):
        h = lax.ragged_dot(xs, params["w_gate"], sizes)
        u = lax.ragged_dot(xs, params["w_up"], sizes)
        y = lax.ragged_dot(silu(h) * u, params["w_down"], sizes)

    with jax.named_scope("moe_dispatch"):
        out = jnp.zeros((n, d), jnp.float32).at[tok].add(
            y.astype(jnp.float32) * w[:, None])
        out = out.astype(cdt)

    if cfg.moe_shared_experts > 0:
        out = out + apply_mlp(params["shared"], xt)
    return out.reshape(b, s, d)
