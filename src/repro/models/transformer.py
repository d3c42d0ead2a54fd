"""Block assembly: (prelude, scanned periods) x (mixer, mlp) residual
branches, with the paper's continuous-depth mode as a first-class feature.

Train path: each residual branch is either the discrete ``x + f(norm(x))``
(ode.mode='off' — the "ResNet" baseline of paper Sec 4.2) or the Neural-ODE
``x <- z(T), dz/dt = f_branch(z)`` integrated by the configured gradient
method (MALI by default) — paper Sec 4.2's ResNet->Neural-ODE conversion
applied per residual branch, parameter count unchanged.

Serve path (prefill/decode): forward-only, so the ALF steps are unrolled
explicitly with the KV/SSM cache threaded through every f-eval; each eval
index is a cache "virtual layer" slot (DESIGN.md §3).
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import LayerSpec, ModelConfig
from repro.core import solve
from repro.core.interface import RunStats
from .attention import (KVCache, attention_decode, attention_prefill,
                        attention_train, init_attention)
from .common import rmsnorm, rmsnorm_init
from .mla import init_mla, mla_train
from .mlp import apply_mlp, init_mlp
from .moe import apply_moe, init_moe, route_counts
from .ssm import MambaCache, apply_mamba_decode, apply_mamba_train, init_mamba
from .xlstm import (LstmCache, apply_mlstm_decode, apply_mlstm_train,
                    apply_slstm_decode, apply_slstm_train, init_mlstm,
                    init_slstm)

Pytree = Any


def n_cache_slots(cfg: ModelConfig) -> int:
    """Virtual-layer count per block: v0-init + one per ALF step."""
    if cfg.ode.mode == "off":
        return 1
    return cfg.ode.n_steps + 1


# ---------------------------------------------------------------------------
# Per-layer init
# ---------------------------------------------------------------------------

_MIXER_INIT = {
    "attn": init_attention,
    "mla": init_mla,
    "mamba": init_mamba,
    "mlstm": init_mlstm,
    "slstm": init_slstm,
}


def init_layer(key: jax.Array, cfg: ModelConfig, spec: LayerSpec,
               dense_d_ff: Optional[int] = None) -> Pytree:
    dt = jnp.dtype(cfg.param_dtype)
    k1, k2 = jax.random.split(key)
    params = {
        "mixer_norm": rmsnorm_init(cfg.d_model, dt),
        "mixer": _MIXER_INIT[spec.mixer](k1, cfg),
    }
    if spec.mlp == "dense":
        params["mlp_norm"] = rmsnorm_init(cfg.d_model, dt)
        params["mlp"] = init_mlp(k2, cfg, dense_d_ff or cfg.d_ff)
    elif spec.mlp == "moe":
        params["mlp_norm"] = rmsnorm_init(cfg.d_model, dt)
        params["mlp"] = init_moe(k2, cfg)
    return params


# ---------------------------------------------------------------------------
# Train path
# ---------------------------------------------------------------------------

def _mixer_train_fn(cfg: ModelConfig, spec: LayerSpec, positions=None):
    # NOTE: positions must be None under the ODE path (tracer capture would
    # break custom_vjp's static f); attention computes its own arange.
    if spec.mixer == "attn":
        return lambda p, z: attention_train(p, cfg, spec, z, positions)
    if spec.mixer == "mla":
        return lambda p, z: mla_train(p, cfg, spec, z, positions)
    if spec.mixer == "mamba":
        return lambda p, z: apply_mamba_train(p, cfg, z)
    if spec.mixer == "mlstm":
        return lambda p, z: apply_mlstm_train(p, cfg, z)
    return lambda p, z: apply_slstm_train(p, cfg, z)


def _mlp_train_fn(cfg: ModelConfig, spec: LayerSpec, eval_mode: bool = False):
    if spec.mlp == "moe":
        return lambda p, z: apply_moe(p, cfg, z, eval_mode=eval_mode)
    return lambda p, z: apply_mlp(p, z)


def zero_run_stats() -> RunStats:
    z = jnp.zeros((), jnp.int32)
    return RunStats(z, z, z)


def _detach_counter(c: jax.Array) -> jax.Array:
    # lax.stop_gradient is a no-op on integer dtypes, so a custom_vjp's
    # instantiated float0 tangent (R002c) rides through it and crashes the
    # first arithmetic op under a jvp trace (grad-of-scan, vmap-of-grad).
    # The int -> f32 conversion has no tangent space, so its jvp emits a
    # real float32 zero; stop_gradient then binds for real.
    return lax.stop_gradient(c.astype(jnp.float32)).astype(jnp.int32)


class TrainStats(NamedTuple):
    """A train step's counters, summed over its residual branches: the ODE
    solves' :class:`RunStats` fields, and the MoE branches' routing of
    their entry state (``moe.route_counts``): assignments to held experts
    and those of them over the device budget. Detached int32 scalars."""
    n_accepted: jax.Array
    n_rejected: jax.Array
    n_fevals: jax.Array
    moe_routed: jax.Array
    moe_dropped: jax.Array


def zero_train_stats() -> TrainStats:
    z = jnp.zeros((), jnp.int32)
    return TrainStats(z, z, z, z, z)


def add_train_stats(a: TrainStats, b: TrainStats) -> TrainStats:
    return TrainStats(*(x + y for x, y in zip(a, b)))


def _moe_entry_counts(params: Pytree, cfg: ModelConfig,
                      x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Routing counts of a MoE branch at its entry state z0 (what its first
    f-eval sees), outside the gradient."""
    cdt = jnp.dtype(cfg.compute_dtype)
    p, z = lax.stop_gradient((params, x))
    counts = route_counts(p["mlp"], cfg, rmsnorm(p["mlp_norm"], z.astype(cdt)))
    return tuple(_detach_counter(c) for c in counts)


def _residual_branch(cfg: ModelConfig, branch_params: Pytree, x: jax.Array,
                     inner) -> Tuple[jax.Array, RunStats]:
    """Apply one residual branch discretely or as a Neural ODE.

    The ODE state (z, v) is kept in f32 — ALF's exact reversibility is a
    float-rounding property, and bf16 state would visibly degrade the
    backward reconstruction; ``f`` itself still computes in the model dtype
    (cast at the norm boundary). The discrete path is untouched.

    Returns the branch output and the solve's :class:`RunStats`. The raw
    counters are custom_vjp primal outputs — instantiated float0 tangents
    under a jvp trace (R002c) — so they are laundered through
    ``lax.stop_gradient`` before any cross-branch arithmetic, and only
    float0-tolerant ops (add, scan carry) ever touch them: a ``jnp.sum``
    here would hit ``reduce_sum`` on the instantiated float0 tangent and
    crash under grad-of-scan. ``solve`` already returns scalar totals, so
    no reduction is needed.
    """
    cdt = jnp.dtype(cfg.compute_dtype)

    def dynamics(p, z, t):
        out = inner(p["inner"], rmsnorm(p["norm"], z.astype(cdt)))
        return out.astype(jnp.float32)

    p = {"norm": branch_params["norm"], "inner": branch_params["inner"]}
    ode = cfg.ode
    if ode.mode == "off":
        return x + inner(p["inner"], rmsnorm(p["norm"], x)), zero_run_stats()
    solver, controller, gradient, saveat = ode.as_objects()
    sol = solve(dynamics, p, x.astype(jnp.float32), 0.0, ode.t1,
                solver=solver, controller=controller, gradient=gradient,
                saveat=saveat, batching=ode.batching())
    stats = RunStats(*(_detach_counter(c)
                       for c in (sol.stats.n_accepted, sol.stats.n_rejected,
                                 sol.stats.n_fevals)))
    return sol.ys.astype(x.dtype), stats


def layer_train(params: Pytree, cfg: ModelConfig, spec: LayerSpec,
                x: jax.Array, positions: jax.Array = None
                ) -> Tuple[jax.Array, TrainStats]:
    mixer = _mixer_train_fn(cfg, spec, None)
    x, ode = _residual_branch(
        cfg, {"norm": params["mixer_norm"], "inner": params["mixer"]}, x,
        mixer)
    stats = TrainStats(*ode, *zero_train_stats()[3:])
    if spec.mlp != "none":
        moe = (_moe_entry_counts(params, cfg, x) if spec.mlp == "moe"
               else stats[3:])
        mlp = _mlp_train_fn(cfg, spec)
        x, s2 = _residual_branch(
            cfg, {"norm": params["mlp_norm"], "inner": params["mlp"]}, x, mlp)
        stats = add_train_stats(stats, TrainStats(*s2, *moe))
    return x, stats


def init_blocks(key: jax.Array, cfg: ModelConfig) -> Pytree:
    params: Pytree = {}
    keys = jax.random.split(key, max(len(cfg.prelude), 1) + 1)
    if cfg.prelude:
        params["prelude"] = [
            init_layer(keys[i], cfg, spec, dense_d_ff=cfg.prelude_d_ff or None)
            for i, spec in enumerate(cfg.prelude)]
    if cfg.period:
        def init_period(k):
            sub = {}
            ks = jax.random.split(k, len(cfg.period))
            for j, spec in enumerate(cfg.period):
                sub[f"sub{j}"] = init_layer(ks[j], cfg, spec)
            return sub

        pkeys = jax.random.split(keys[-1], cfg.n_periods)
        params["period"] = jax.vmap(init_period)(pkeys)
    return params


def blocks_train(params: Pytree, cfg: ModelConfig, x: jax.Array,
                 positions: jax.Array) -> Tuple[jax.Array, TrainStats]:
    """Returns (activations, :class:`TrainStats` summed over every residual
    branch).

    Stats counters are detached int32 scalars (see ``_residual_branch``), so
    carrying their sum through the period scan is float0-safe.
    """
    stats = zero_train_stats()
    for i, spec in enumerate(cfg.prelude):
        x, s = layer_train(params["prelude"][i], cfg, spec, x, positions)
        stats = add_train_stats(stats, s)

    if cfg.period:
        def period_fn(carry, pp):
            xc, sc = carry
            for j, spec in enumerate(cfg.period):
                xc, s = layer_train(pp[f"sub{j}"], cfg, spec, xc, positions)
                sc = add_train_stats(sc, s)
            return (xc, sc), None

        (x, stats), _ = lax.scan(period_fn, (x, stats), params["period"])
    return x, stats


# ---------------------------------------------------------------------------
# Serve path (prefill / decode) — explicit ALF unroll with cache threading
# ---------------------------------------------------------------------------

def _refuse_mla(spec: LayerSpec) -> None:
    if spec.mixer == "mla":
        raise NotImplementedError(
            "serving an 'mla' layer needs a latent KV cache (c_kv, k_pe per "
            "slot), which the serve path does not have; MLA runs on the "
            "train path only")


def init_layer_cache(cfg: ModelConfig, spec: LayerSpec, batch: int,
                     s_max: int) -> Pytree:
    _refuse_mla(spec)
    slots = n_cache_slots(cfg)
    if spec.mixer == "attn":
        return KVCache.init(cfg, slots, batch, s_max)
    if spec.mixer == "mamba":
        return MambaCache.init(cfg, slots, batch)
    if spec.mixer == "mlstm":
        return LstmCache.init_mlstm(cfg, slots, batch)
    return LstmCache.init_slstm(cfg, slots, batch)


def init_cache(cfg: ModelConfig, batch: int, s_max: int) -> Pytree:
    cache: Pytree = {}
    if cfg.prelude:
        cache["prelude"] = [init_layer_cache(cfg, spec, batch, s_max)
                            for spec in cfg.prelude]
    if cfg.period:
        def one_period():
            return {f"sub{j}": init_layer_cache(cfg, spec, batch, s_max)
                    for j, spec in enumerate(cfg.period)}

        proto = one_period()
        # tile (not zeros) to preserve non-zero inits (the -inf mLSTM stabilizer)
        cache["period"] = jax.tree_util.tree_map(
            lambda small: jnp.tile(small[None],
                                   (cfg.n_periods,) + (1,) * small.ndim),
            proto)
    return cache


def _write_slot(buf: jax.Array, val: jax.Array, slot) -> jax.Array:
    return lax.dynamic_update_slice(
        buf, val[None].astype(buf.dtype), (slot,) + (0,) * val.ndim)


def _mixer_serve(params, cfg, spec, z, cache, slot, pos_info, kind):
    """Dispatch one mixer f-eval with cache read/write at `slot`."""
    if spec.mixer == "attn":
        if kind == "prefill":
            return attention_prefill(params, cfg, spec, z, pos_info, cache, slot)
        return attention_decode(params, cfg, spec, z, pos_info, cache, slot)
    if spec.mixer == "mamba":
        if kind == "prefill":
            y, (conv_state, ssm_state) = apply_mamba_train(
                params, cfg, z, return_state=True)
            cache = MambaCache(_write_slot(cache.conv, conv_state, slot),
                               _write_slot(cache.ssm, ssm_state, slot))
            return y, cache
        return apply_mamba_decode(params, cfg, z, cache, slot)
    if spec.mixer == "mlstm":
        if kind == "prefill":
            y, (c_m, n_m, m_m) = apply_mlstm_train(params, cfg, z,
                                                   return_state=True)
            cache = LstmCache(_write_slot(cache.c, c_m, slot),
                              _write_slot(cache.n, n_m, slot),
                              _write_slot(cache.m, m_m, slot), cache.h)
            return y, cache
        return apply_mlstm_decode(params, cfg, z, cache, slot)
    if kind == "prefill":
        y, (c_m, n_m, m_m, h_m) = apply_slstm_train(params, cfg, z,
                                                    return_state=True)
        cache = LstmCache(_write_slot(cache.c, c_m, slot),
                          _write_slot(cache.n, n_m, slot),
                          _write_slot(cache.m, m_m, slot),
                          _write_slot(cache.h, h_m, slot))
        return y, cache
    return apply_slstm_decode(params, cfg, z, cache, slot)


def layer_serve(params: Pytree, cfg: ModelConfig, spec: LayerSpec,
                x: jax.Array, cache: Pytree, pos_info, kind: str
                ) -> Tuple[jax.Array, Pytree]:
    """One layer, serve mode. pos_info: positions [B,S] (prefill) or scalar
    pos (decode)."""
    _refuse_mla(spec)
    ode = cfg.ode
    cdt = jnp.dtype(cfg.compute_dtype)

    def mixer_eval(z, slot, c):
        zn = rmsnorm(params["mixer_norm"], z.astype(cdt))
        y, c = _mixer_serve(params["mixer"], cfg, spec, zn, c, slot,
                            pos_info, kind)
        return y.astype(jnp.float32), c

    if ode.mode == "off":
        y, cache = mixer_eval(x, 0, cache)
        x = x + y.astype(x.dtype)
    else:
        n, eta = ode.n_steps, ode.eta
        h = ode.t1 / n
        v, cache = mixer_eval(x, 0, cache)          # v0 (slot 0)
        z = x.astype(jnp.float32)
        for i in range(n):
            k1 = z + v * (h / 2)
            u1, cache = mixer_eval(k1, i + 1, cache)  # slot i+1
            v = v + 2.0 * eta * (u1 - v)
            z = k1 + v * (h / 2)
        x = z.astype(x.dtype)

    if spec.mlp != "none":
        mlp = _mlp_train_fn(cfg, spec, eval_mode=True)

        def mlp_f(z):
            return mlp(params["mlp"],
                       rmsnorm(params["mlp_norm"], z.astype(cdt))
                       ).astype(jnp.float32)

        if ode.mode == "off":
            x = x + mlp_f(x).astype(x.dtype)
        else:
            n, eta = ode.n_steps, ode.eta
            h = ode.t1 / n
            v = mlp_f(x)
            z = x.astype(jnp.float32)
            for _ in range(n):
                k1 = z + v * (h / 2)
                u1 = mlp_f(k1)
                v = v + 2.0 * eta * (u1 - v)
                z = k1 + v * (h / 2)
            x = z.astype(x.dtype)
    return x, cache


def blocks_serve(params: Pytree, cfg: ModelConfig, x: jax.Array,
                 cache: Pytree, pos_info, kind: str
                 ) -> Tuple[jax.Array, Pytree]:
    new_cache: Pytree = {}
    if cfg.prelude:
        entries = []
        for i, spec in enumerate(cfg.prelude):
            x, ce = layer_serve(params["prelude"][i], cfg, spec, x,
                                cache["prelude"][i], pos_info, kind)
            entries.append(ce)
        new_cache["prelude"] = entries

    if cfg.period:
        def period_fn(xc, inp):
            pp, cc = inp
            outs = {}
            for j, spec in enumerate(cfg.period):
                xc, ce = layer_serve(pp[f"sub{j}"], cfg, spec, xc,
                                     cc[f"sub{j}"], pos_info, kind)
                outs[f"sub{j}"] = ce
            return xc, outs

        x, period_cache = lax.scan(period_fn, x,
                                   (params["period"], cache["period"]))
        new_cache["period"] = period_cache
    return x, new_cache
