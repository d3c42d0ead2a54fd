"""Multi-head latent attention (MLA, DeepSeek-V2 §2.1) on the train path.

Without a query latent (``q_lora_rank`` null, as in DeepSeek-V2-Lite)::

    q            = x W_q                 -> H x (nope ‖ rope)
    [c_kv ‖ k_pe] = x W_kva              -> kv_rank ‖ rope
    c_kv         = RMSNorm(c_kv)
    [k_nope ‖ v] = c_kv W_kvb            -> H x (nope ‖ v)
    k            = k_nope ‖ RoPE(k_pe)   (one rotary key shared by all heads)
    out          = softmax(q k^T · scale) v W_o

with YaRN rotary where the config sets it, and the softmax scale
``(nope + rope)^-1/2`` times YaRN's ``mscale(factor, mscale_all_dim)^2``.
The rotary columns are rotated half against half (the repo's ``apply_rope``);
HF's checkpoint stores them interleaved and permutes them first, which
random weights make immaterial. The attention core is the train path's
(``attention.sdpa_train``) with value heads narrower than q/k.

Serving MLA needs a cache of the latent (``c_kv``, ``k_pe``), which the
serve path does not have: ``transformer.layer_serve`` refuses the mixer.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from .attention import sdpa_train
from .common import (apply_rope, dense_init, rmsnorm, rmsnorm_init,
                     rope_frequencies, yarn_mscale)

Pytree = Any


def init_mla(key: jax.Array, cfg: ModelConfig) -> Pytree:
    dt = jnp.dtype(cfg.param_dtype)
    kq, ka, kb, ko = jax.random.split(key, 4)
    d, h, r = cfg.d_model, cfg.n_heads, cfg.mla_kv_rank
    dn, dr, dv = cfg.mla_nope_dim, cfg.mla_rope_dim, cfg.mla_v_dim
    return {
        "wq": dense_init(kq, (d, h * (dn + dr)), dt),
        "wkv_a": dense_init(ka, (d, r + dr), dt),
        "kv_norm": rmsnorm_init(r, dt),
        "wkv_b": dense_init(kb, (r, h * (dn + dv)), dt),
        "wo": dense_init(ko, (h * dv, d), dt, fan_in=h * dv),
    }


def mla_softmax_scale(cfg: ModelConfig) -> float:
    scale = (cfg.mla_nope_dim + cfg.mla_rope_dim) ** -0.5
    if cfg.rope_yarn_factor and cfg.rope_yarn_mscale_all_dim:
        m = yarn_mscale(cfg.rope_yarn_factor, cfg.rope_yarn_mscale_all_dim)
        scale *= m * m
    return scale


def _rope(cfg: ModelConfig, x: jax.Array, positions: jax.Array) -> jax.Array:
    if not cfg.rope_yarn_factor:
        return apply_rope(x, positions, cfg.rope_theta)
    f = cfg.rope_yarn_factor
    freqs = rope_frequencies(
        x.shape[-1], cfg.rope_theta,
        (f, cfg.rope_yarn_original_len, cfg.rope_yarn_beta_fast,
         cfg.rope_yarn_beta_slow))
    scale = (yarn_mscale(f, cfg.rope_yarn_mscale)
             / yarn_mscale(f, cfg.rope_yarn_mscale_all_dim))
    return apply_rope(x, positions, cfg.rope_theta, freqs=freqs, scale=scale)


@jax.named_scope("mla")
def mla_train(params: Pytree, cfg: ModelConfig, spec: LayerSpec,
              x: jax.Array, positions: jax.Array = None) -> jax.Array:
    b, s, _ = x.shape
    if positions is None:
        # computed here (not closed over): see attention_train
        positions = jnp.tile(jnp.arange(s, dtype=jnp.int32)[None], (b, 1))
    h, r = cfg.n_heads, cfg.mla_kv_rank
    dn, dr, dv = cfg.mla_nope_dim, cfg.mla_rope_dim, cfg.mla_v_dim
    q = (x @ params["wq"]).reshape(b, s, h, dn + dr)
    kva = x @ params["wkv_a"]
    c_kv = rmsnorm(params["kv_norm"], kva[..., :r])
    kv = (c_kv @ params["wkv_b"]).reshape(b, s, h, dn + dv)
    q_pe = _rope(cfg, q[..., dn:], positions)
    k_pe = _rope(cfg, kva[:, :, None, r:], positions)      # [B, S, 1, dr]
    q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe, (b, s, h, dr))], axis=-1)
    window = cfg.sliding_window if spec.attn_kind == "local" else 0
    out = sdpa_train(cfg, q, k, kv[..., dn:], positions, window,
                     mla_softmax_scale(cfg))
    return out.reshape(b, s, h * dv) @ params["wo"]
