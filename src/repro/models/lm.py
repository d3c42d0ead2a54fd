"""Full language model: embeddings -> blocks -> head, plus the train / prefill
/ decode entry points the launcher and dry-run lower.

Loss is next-token cross-entropy computed in sequence chunks under
``jax.checkpoint`` so the full [B, S, vocab] logits tensor is never alive
(vocab up to 256k makes the dense tensor tens of GB at the assigned shapes).

``input_mode='embeds'`` is the stub modality frontend of the [audio]/[vlm]
archs: the model consumes precomputed frame/patch embeddings from
``input_specs()`` instead of token ids (the backbone — the part under test —
is identical).
"""
from __future__ import annotations

from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from .common import embed_init, rmsnorm, rmsnorm_init, softcap
from .transformer import (TrainStats, blocks_serve, blocks_train,
                          init_blocks, init_cache)

Pytree = Any

_LOSS_CHUNK = 512


def init_lm(key: jax.Array, cfg: ModelConfig) -> Pytree:
    ke, kb, kh = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.param_dtype)
    params = {
        "embed": embed_init(ke, (cfg.vocab_size, cfg.d_model), dt),
        "blocks": init_blocks(kb, cfg),
        "final_norm": rmsnorm_init(cfg.d_model, dt),
    }
    if not cfg.tie_embeddings:
        params["head"] = embed_init(kh, (cfg.d_model, cfg.vocab_size), dt)
    return params


def _head_matrix(params: Pytree, cfg: ModelConfig) -> jax.Array:
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["head"]


@jax.named_scope("embed")
def _embed(params: Pytree, cfg: ModelConfig, batch: Pytree) -> jax.Array:
    if cfg.input_mode == "embeds":
        return batch["embeds"].astype(jnp.dtype(cfg.compute_dtype))
    x = params["embed"][batch["tokens"]]
    return x.astype(jnp.dtype(cfg.compute_dtype))


def backbone_train(params: Pytree, cfg: ModelConfig, batch: Pytree
                   ) -> Tuple[jax.Array, TrainStats]:
    """Returns (final hidden states, summed TrainStats — detached int32
    counters from every residual branch; the ODE ones zero with
    ode.mode='off')."""
    x = _embed(params, cfg, batch)
    x, stats = blocks_train(params["blocks"], cfg, x, None)
    return rmsnorm(params["final_norm"], x), stats


def chunked_ce_loss(h: jax.Array, head: jax.Array, labels: jax.Array,
                    cfg: ModelConfig, chunk: int = _LOSS_CHUNK) -> jax.Array:
    """Mean next-token CE without materializing [B, S, vocab]."""
    b, s, d = h.shape
    n_chunks = -(-s // chunk)
    pad = n_chunks * chunk - s
    h_p = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
    l_p = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    h_p = jnp.moveaxis(h_p.reshape(b, n_chunks, chunk, d), 1, 0)
    l_p = jnp.moveaxis(l_p.reshape(b, n_chunks, chunk), 1, 0)

    @jax.checkpoint
    def chunk_loss(carry, inp):
        hc, lc = inp
        logits = (hc @ head).astype(jnp.float32)
        logits = softcap(logits, cfg.final_softcap)
        lse = jax.nn.logsumexp(logits, axis=-1)
        valid = lc >= 0
        tgt = jnp.take_along_axis(
            logits, jnp.maximum(lc, 0)[..., None], axis=-1)[..., 0]
        nll = jnp.where(valid, lse - tgt, 0.0)
        return (carry[0] + nll.sum(), carry[1] + valid.sum()), None

    (total, count), _ = lax.scan(
        chunk_loss, (jnp.float32(0.0), jnp.int32(0)), (h_p, l_p))
    return total / jnp.maximum(count, 1)


def lm_loss_and_stats(params: Pytree, cfg: ModelConfig, batch: Pytree
                      ) -> Tuple[jax.Array, TrainStats]:
    """Like :func:`lm_loss` but also returns the step's counters.

    The stats are the ``has_aux`` side of the train step's value_and_grad:
    already stop_gradient-detached inside the backbone, so they thread out
    of a jitted (and microbatch-scanned) step without touching the float0
    tangent machinery (R002c).
    """
    h, stats = backbone_train(params, cfg, batch)
    with jax.named_scope("head_loss"):
        loss = chunked_ce_loss(h, _head_matrix(params, cfg), batch["labels"],
                               cfg)
    return loss, stats


def lm_loss(params: Pytree, cfg: ModelConfig, batch: Pytree) -> jax.Array:
    """batch: {'tokens' | 'embeds', 'labels'} with labels already shifted."""
    return lm_loss_and_stats(params, cfg, batch)[0]


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

class ServeState(NamedTuple):
    cache: Pytree
    pos: jax.Array   # next write position, int32


def init_serve_state(cfg: ModelConfig, batch: int, s_max: int) -> ServeState:
    return ServeState(init_cache(cfg, batch, s_max), jnp.int32(0))


def prefill(params: Pytree, cfg: ModelConfig, batch: Pytree,
            state: ServeState) -> Tuple[jax.Array, ServeState]:
    """Process the prompt; returns last-position logits + filled cache."""
    x = _embed(params, cfg, batch)
    b, s, _ = x.shape
    positions = jnp.tile(jnp.arange(s, dtype=jnp.int32)[None], (b, 1))
    x, cache = blocks_serve(params["blocks"], cfg, x, state.cache,
                            positions, "prefill")
    h_last = rmsnorm(params["final_norm"], x[:, -1:])
    logits = (h_last @ _head_matrix(params, cfg)).astype(jnp.float32)
    logits = softcap(logits, cfg.final_softcap)
    return logits, ServeState(cache, jnp.int32(s))


def decode_step(params: Pytree, cfg: ModelConfig, tokens_or_embeds: jax.Array,
                state: ServeState) -> Tuple[jax.Array, ServeState]:
    """One decode step. tokens [B, 1] int32 (or [B, 1, D] embeds)."""
    if cfg.input_mode == "embeds" and tokens_or_embeds.ndim == 3:
        x = tokens_or_embeds.astype(jnp.dtype(cfg.compute_dtype))
    else:
        x = params["embed"][tokens_or_embeds].astype(
            jnp.dtype(cfg.compute_dtype))
    x, cache = blocks_serve(params["blocks"], cfg, x, state.cache,
                            state.pos, "decode")
    h = rmsnorm(params["final_norm"], x)
    logits = (h @ _head_matrix(params, cfg)).astype(jnp.float32)
    logits = softcap(logits, cfg.final_softcap)
    return logits, ServeState(cache, state.pos + 1)
