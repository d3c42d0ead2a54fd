"""GQA attention: RoPE, qk-norm, logit softcap, sliding window, KV cache.

Three execution paths:
  * ``attention_train`` — full-sequence causal attention. Short sequences use
    the direct einsum; long sequences use a flash-style chunked online-softmax
    (pure-jnp loops over query/KV blocks: O(S * block) memory, lowers on any
    backend) that visits, for each query block, only the KV blocks the
    causal / sliding-window mask leaves live. The Pallas TPU kernel
    (repro.kernels.flash_attention) implements the same contraction.
  * ``attention_prefill`` — train path + writes K/V into the cache slot.
  * ``attention_decode`` — single-token query against the cache.

The ``slot`` axis of the cache is the *virtual layer* index of continuous-
depth mode: every ALF f-eval inside a block gets its own KV slot (see
DESIGN.md §3); slot 0 is used when ode.mode == 'off'.

Shapes: activations [B, S, D]; q/k/v [B, S, H|K, d_head]; caches
k/v: [n_slots, B, S_max, K, d_head].
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import LayerSpec, ModelConfig
from repro.distributed.sharding import hint, model_axis_size
from .common import apply_rope, dense_init, rmsnorm, rmsnorm_init, softcap

Pytree = Any

NEG_INF = -2.0 ** 30  # large-but-finite: keeps softmax NaN-free on fully-masked rows

# Direct-einsum threshold; above this the flash-style chunked path is used
# (keeps attention scores VMEM/loop-local instead of materializing
# [B, H, S, S] f32 in HBM — on TPU this is the Pallas kernel's contraction).
_DIRECT_SEQ_LIMIT = 2048
_BLOCK_Q = 512
_BLOCK_KV = 1024


def init_attention(key: jax.Array, cfg: ModelConfig) -> Pytree:
    dt = jnp.dtype(cfg.param_dtype)
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, h, k_, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    params = {
        "wq": dense_init(kq, (d, h * dh), dt),
        "wk": dense_init(kk, (d, k_ * dh), dt),
        "wv": dense_init(kv, (d, k_ * dh), dt),
        "wo": dense_init(ko, (h * dh, d), dt, fan_in=h * dh),
    }
    if cfg.qk_norm:
        params["q_norm"] = rmsnorm_init(dh, dt)
        params["k_norm"] = rmsnorm_init(dh, dt)
    return params


def _project_qkv(params: Pytree, cfg: ModelConfig, x: jax.Array,
                 positions: jax.Array):
    b, s, _ = x.shape
    h, k_, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = (x @ params["wq"]).reshape(b, s, h, dh)
    k = (x @ params["wk"]).reshape(b, s, k_, dh)
    v = (x @ params["wv"]).reshape(b, s, k_, dh)
    # pin: q sharded on whole heads, K/V replicated over 'model' when the
    # kv-head count doesn't divide it — otherwise GSPMD splits d_head and
    # every attention tile (and the qk-norm variance) needs a psum
    # (measured: 172k ARs / 21.6 TB wire on qwen3 prefill_32k; §Perf)
    q = hint(q, "batch", None, "model", None)
    k = hint(k, "batch", None, "model", None)
    v = hint(v, "batch", None, "model", None)
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q)
        k = rmsnorm(params["k_norm"], k)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _mask_bias(q_pos: jax.Array, k_pos: jax.Array, window: int) -> jax.Array:
    """[Sq, Sk] additive bias: causal (+ sliding window if window > 0)."""
    keep = k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        keep &= k_pos[None, :] > q_pos[:, None] - window
    return jnp.where(keep, 0.0, NEG_INF).astype(jnp.float32)


def _sdpa_direct(cfg: ModelConfig, q, k, v, bias, scale=None) -> jax.Array:
    """[B,Sq,H,dh] x [B,Sk,K,dh] grouped attention, f32 accumulation; v may
    be narrower than q/k ([B,Sk,K,dv]). ``scale`` defaults to dh^-1/2."""
    b, sq, h, dh = q.shape
    k_heads = k.shape[2]
    g = h // k_heads
    qg = q.reshape(b, sq, k_heads, g, dh)
    scale = dh ** -0.5 if scale is None else scale
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    scores = softcap(scores, cfg.attn_softcap)
    scores = scores + bias[None, None, None, :, :]
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, h, v.shape[-1]).astype(q.dtype)


def _sdpa_chunked(cfg: ModelConfig, q, k, v, q_pos, k_pos, window,
                  block_q: int = _BLOCK_Q, block_kv: int = _BLOCK_KV,
                  scale=None):
    """Flash-style online-softmax over (Q-block x KV-block) tiles in pure jnp.

    Memory is O(block_q * block_kv) per tile instead of O(Sq * Sk); this is
    the backend-portable twin of the Pallas kernel. v may be narrower than
    q/k; ``scale`` defaults to dh^-1/2.
    """
    b, sq, h, dh = q.shape
    dv = v.shape[-1]
    (qp, kp_x, vp_x, qpos, kpos, nq, nkv, pad_q, pad_kv, g) = _chunk_arrays(
        cfg, q, k, v, q_pos, k_pos, block_q, block_kv, ctx_parallel=True)
    k_heads = h  # _chunk_arrays repeats KV to full head count (g == 1)
    scale = dh ** -0.5 if scale is None else scale

    # Both loops consume their tiles as scan xs (dynamic-sliced per
    # iteration) rather than closures, so the loop state never carries the
    # full K/V arrays — keeps the while-carry (and real HBM traffic) at
    # O(tile) like the Pallas kernel.

    def q_block(carry, xs):
        qb, qpb = xs                              # [B, bq, K, G, dh]
        qb = qb.astype(jnp.float32)

        def kv_step(c, kxs):
            m, l, acc = c
            kb, vb, kposb = kxs
            kb = kb.astype(jnp.float32)           # [B, bk, K, dh]
            vb = vb.astype(jnp.float32)
            s = jnp.einsum("bqkgd,bskd->bkgqs", qb, kb) * scale
            s = softcap(s, cfg.attn_softcap)
            s = s + _mask_bias(qpb, kposb, window)[None, None, None]
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p, vb)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, k_heads, g, block_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, k_heads, g, block_q), jnp.float32)
        a0 = jnp.zeros((b, k_heads, g, block_q, dv), jnp.float32)
        (m, l, acc), _ = lax.scan(kv_step, (m0, l0, a0), (kp_x, vp_x, kpos))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return carry, out  # [B, K, G, bq, dv]

    _, outs = lax.scan(q_block, 0, (qp, qpos))    # [nq, B, K, G, bq, dv]
    out = jnp.moveaxis(outs, 0, 3)                # [B, K, G, nq, bq, dv]
    out = out.reshape(b, k_heads, g, nq * block_q, dv)[:, :, :, :sq]
    out = jnp.moveaxis(out.reshape(b, h, sq, dv), 1, 2)
    return out.astype(q.dtype)


def _chunk_arrays(cfg, q, k, v, q_pos, k_pos, block_q, block_kv,
                  ctx_parallel: bool = False):
    """Pad + tile q/k/v for the blocked paths. Returns grouped layouts.

    K/V are pre-repeated to the full head count (GQA -> MHA layout): the
    tiled (K, G) head split is not expressible as a single-axis GSPMD
    sharding, so GSPMD shards the KV tile stack along the kv-block axis and
    all-gathers one tile per loop iteration (measured 172k AGs / 1.35 TB on
    qwen3 prefill_32k; §Perf). With H fused the head dim shards cleanly and
    attention runs collective-free.
    """
    b, sq, h, dh = q.shape
    if k.shape[2] != h:
        rep = h // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    k_heads = h
    g = 1
    sk = k.shape[1]
    nq = -(-sq // block_q)
    nkv = -(-sk // block_kv)
    pad_q = nq * block_q - sq
    pad_kv = nkv * block_kv - sk
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    qpos = jnp.pad(q_pos, (0, pad_q), constant_values=-1)
    kp = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    kpos = jnp.pad(k_pos, (0, pad_kv), constant_values=2 ** 30)
    qp = jnp.moveaxis(qp.reshape(b, nq, block_q, k_heads, g, dh), 1, 0)
    kp = jnp.moveaxis(kp.reshape(b, nkv, block_kv, k_heads, dh), 1, 0)
    vp = jnp.moveaxis(vp.reshape(b, nkv, block_kv, k_heads, v.shape[-1]),
                      1, 0)
    # tile stacks: batch on dp, scan axes replicated, heads on model when
    # they divide it; otherwise shard the per-tile q ROWS over 'model'
    # (context-parallel fallback for few-head archs like gemma2's 8 heads
    # on a 16-way axis — replicated-q attention costs 16x redundant
    # compute+memory; §Perf)
    if h % max(model_axis_size(), 1) == 0:
        qp = hint(qp, None, "batch", None, "model", None, None)
    elif ctx_parallel:
        # serve path only: the train path measures better with q left to
        # GSPMD when heads don't divide (gemma2 train 9.4 s vs 33.4 s; §Perf)
        qp = hint(qp, None, "batch", "model", None, None, None)
    kp = hint(kp, None, "batch", None, "model", None)
    vp = hint(vp, None, "batch", None, "model", None)
    return (qp, kp, vp, qpos.reshape(nq, block_q),
            kpos.reshape(nkv, block_kv), nq, nkv, pad_q, pad_kv, g)


def _live_kv_range(qpos: jax.Array, kpos: jax.Array, window: int):
    """Per q-block, the kv-block range ``[lo, hi]`` that holds every live tile.

    Tile (i, j) can hold an unmasked pair only if ``min(kpos[j]) <=
    max(qpos[i])`` (causal) and, with ``window > 0``, ``max(kpos[j]) >
    min(qpos[i]) - window``; every tile outside the range is masked for every
    row of the q-block. Read from the tiled positions (``qpos [nq, bq]``,
    ``kpos [nkv, bk]``, padding included), so it holds for any positions.
    A q-block with no live tile gets ``lo > hi``. Returns int32 ``[nq]`` each.
    """
    nkv = kpos.shape[0]
    live = kpos.min(axis=1)[None, :] <= qpos.max(axis=1)[:, None]
    if window > 0:
        live &= (kpos.max(axis=1)[None, :]
                 > qpos.min(axis=1)[:, None] - window)
    j = jnp.arange(nkv, dtype=jnp.int32)
    lo = jnp.min(jnp.where(live, j, nkv), axis=1)
    hi = jnp.max(jnp.where(live, j, -1), axis=1)
    return lo, hi


@functools.lru_cache(maxsize=None)
def _make_flash_sdpa(softcap_val: float, window: int, scale: float,
                     block_q: int, block_kv: int):
    """FlashAttention-2-style custom_vjp over pre-tiled inputs.

    Each q-block visits only the kv-blocks of its live range
    (``_live_kv_range``), in ascending order, forward and backward. A
    skipped tile is masked for every row of the q-block: after a row's first
    live tile it would add ``p = 0``, and before it what it added is wiped by
    ``alpha = 0``. So every row with a live key (padding rows are cut off
    by the caller) gets what the sweep over every tile gives.

    Forward: online softmax, residuals = (tiles, out, lse) — O(S*d), no
    O(S^2) tiles survive to the backward (the vanilla AD-of-scan backward
    stacks the per-tile f32 probabilities: measured 2.1 GB/layer residual at
    stablelm train_4k; EXPERIMENTS.md §Perf iteration 2).
    Backward: recompute each live (q-block, kv-block) tile from
    (q,k,v,lse), accumulate dq and add dk/dv into kv-block j of the carried
    dk/dv in place — standard FA2, incl. the softcap chain rule.

    Tiled layouts: q [nq, B, bq, K, G, dh]; k [nkv, B, bk, K, dh]; v [nkv,
    B, bk, K, dv]; qpos [nq, bq]; kpos [nkv, bk]. Returns out [nq, B, K, G,
    bq, dv].
    """

    def _bias(qpb, kposb):
        return _mask_bias(qpb, kposb, window)[None, None, None]

    def _scores(qb, kb, qpb, kposb):
        s = jnp.einsum("bqkgd,bskd->bkgqs", qb, kb) * scale
        s = softcap(s, softcap_val)
        return s + _bias(qpb, kposb)

    def _tile(x, j):
        return lax.dynamic_index_in_dim(x, j, 0, keepdims=False)

    def forward(qp, kp, vp, qpos, kpos):
        lo, hi = _live_kv_range(qpos, kpos, window)

        def q_block(carry, xs):
            qb, qpb, lo_i, hi_i = xs
            qb = qb.astype(jnp.float32)

            def kv_step(j, c):
                m, l, acc = c
                s = _scores(qb, _tile(kp, j).astype(jnp.float32), qpb,
                            _tile(kpos, j))
                m_new = jnp.maximum(m, s.max(axis=-1))
                p = jnp.exp(s - m_new[..., None])
                alpha = jnp.exp(m - m_new)
                l_new = l * alpha + p.sum(axis=-1)
                acc_new = acc * alpha[..., None] + jnp.einsum(
                    "bkgqs,bskd->bkgqd", p, _tile(vp, j).astype(jnp.float32))
                return m_new, l_new, acc_new

            b, bq, kh, g, _ = qb.shape
            m0 = jnp.full((b, kh, g, bq), NEG_INF, jnp.float32)
            l0 = jnp.zeros((b, kh, g, bq), jnp.float32)
            a0 = jnp.zeros((b, kh, g, bq, vp.shape[-1]), jnp.float32)
            m, l, acc = lax.fori_loop(lo_i, hi_i + 1, kv_step, (m0, l0, a0))
            out = acc / jnp.maximum(l, 1e-30)[..., None]
            # +inf lse for fully-masked (padding) rows => p == 0 in bwd
            lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)),
                            jnp.inf)
            return carry, (out, lse)

        _, (outs, lses) = lax.scan(q_block, 0, (qp, qpos, lo, hi))
        return outs, lses  # [nq,B,K,G,bq,dv], [nq,B,K,G,bq]

    @jax.custom_vjp
    def flash(qp, kp, vp, qpos, kpos):
        return forward(qp, kp, vp, qpos, kpos)[0]

    def flash_fwd(qp, kp, vp, qpos, kpos):
        outs, lses = forward(qp, kp, vp, qpos, kpos)
        return outs, (qp, kp, vp, qpos, kpos, outs, lses)

    def flash_bwd(res, g_out):
        qp, kp, vp, qpos, kpos, outs, lses = res
        lo, hi = _live_kv_range(qpos, kpos, window)
        # delta_i = sum_d dO_i * O_i  (FA2)
        delta = jnp.sum(g_out.astype(jnp.float32)
                        * outs.astype(jnp.float32), axis=-1)  # [nq,B,K,G,bq]

        def q_block(carry, xs):
            qb, dob, lseb, deltab, qpb, lo_i, hi_i = xs
            qb = qb.astype(jnp.float32)
            dob = dob.astype(jnp.float32)

            def kv_step(j, c):
                dq_b, dk, dv = c
                kb = _tile(kp, j).astype(jnp.float32)
                vb = _tile(vp, j).astype(jnp.float32)
                s_raw = jnp.einsum("bqkgd,bskd->bkgqs", qb, kb) * scale
                s_c = softcap(s_raw, softcap_val)
                s_b = s_c + _bias(qpb, _tile(kpos, j))
                p = jnp.exp(s_b - lseb[..., None])           # [b,k,g,bq,bk]
                dv_t = jnp.einsum("bkgqs,bkgqd->bskd", p, dob)
                dp = jnp.einsum("bkgqd,bskd->bkgqs", dob, vb)
                ds_c = p * (dp - deltab[..., None])
                if softcap_val > 0:
                    ds = ds_c * (1.0 - (s_c / softcap_val) ** 2)
                else:
                    ds = ds_c
                ds = ds * scale
                dq_b = dq_b + jnp.einsum("bkgqs,bskd->bqkgd", ds, kb)
                dk_t = jnp.einsum("bkgqs,bqkgd->bskd", ds, qb)
                dk = lax.dynamic_update_index_in_dim(
                    dk, _tile(dk, j) + dk_t, j, 0)
                dv = lax.dynamic_update_index_in_dim(
                    dv, _tile(dv, j) + dv_t, j, 0)
                return dq_b, dk, dv

            dq0 = jnp.zeros(qb.shape, jnp.float32)
            dq_b, dk, dv = lax.fori_loop(lo_i, hi_i + 1, kv_step,
                                         (dq0,) + carry)
            return (dk, dv), dq_b

        dk0 = jnp.zeros(kp.shape, jnp.float32)
        dv0 = jnp.zeros(vp.shape, jnp.float32)
        (dk, dv), dqs = lax.scan(q_block, (dk0, dv0),
                                 (qp, g_out, lses, delta, qpos, lo, hi))
        return (dqs.astype(qp.dtype), dk.astype(kp.dtype),
                dv.astype(vp.dtype), None, None)

    flash.defvjp(flash_fwd, flash_bwd)
    return flash


def _sdpa_chunked_flash(cfg: ModelConfig, q, k, v, q_pos, k_pos, window,
                        block_q: int = _BLOCK_Q, block_kv: int = _BLOCK_KV,
                        scale=None):
    """Differentiable chunked attention with the FA2-style backward; v may
    be narrower than q/k; ``scale`` defaults to dh^-1/2."""
    b, sq, h, dh = q.shape
    dv = v.shape[-1]
    (qp, kp, vp, qpos, kpos, nq, nkv, pad_q, pad_kv, g) = _chunk_arrays(
        cfg, q, k, v, q_pos, k_pos, block_q, block_kv)
    k_heads = h  # _chunk_arrays repeats KV to full head count (g == 1)
    flash = _make_flash_sdpa(float(cfg.attn_softcap), int(window),
                             float(dh ** -0.5 if scale is None else scale),
                             block_q, block_kv)
    outs = flash(qp, kp, vp, qpos, kpos)      # [nq, B, K, G, bq, dv]
    out = jnp.moveaxis(outs, 0, 3)            # [B, K, G, nq, bq, dv]
    out = out.reshape(b, k_heads, g, nq * block_q, dv)[:, :, :, :sq]
    out = jnp.moveaxis(out.reshape(b, h, sq, dv), 1, 2)
    return out.astype(q.dtype)


def _finish(params, b, s, out):
    return out.reshape(b, s, -1) @ params["wo"]


@jax.named_scope("attention")
def attention_train(params: Pytree, cfg: ModelConfig, spec: LayerSpec,
                    x: jax.Array, positions: jax.Array = None) -> jax.Array:
    b, s, _ = x.shape
    if positions is None:
        # computed here (not closed over) so the ODE dynamics closure stays
        # tracer-free for custom_vjp's nondiff f argument
        positions = jnp.tile(jnp.arange(s, dtype=jnp.int32)[None], (b, 1))
    window = cfg.sliding_window if spec.attn_kind == "local" else 0
    q, k, v = _project_qkv(params, cfg, x, positions)
    return _finish(params, b, s, sdpa_train(cfg, q, k, v, positions, window))


def sdpa_train(cfg: ModelConfig, q, k, v, positions: jax.Array, window: int,
               scale=None) -> jax.Array:
    """Causal (windowed) attention of the train path: the direct einsum up
    to ``_DIRECT_SEQ_LIMIT``, the chunked flash ``custom_vjp`` (or, with
    ``attn_bwd='autodiff'``, AD through the chunked scan) above it. v may
    be narrower than q/k; ``scale`` defaults to dh^-1/2."""
    s = q.shape[1]
    if s <= _DIRECT_SEQ_LIMIT:
        bias = _mask_bias(positions[0], positions[0], window)
        return _sdpa_direct(cfg, q, k, v, bias, scale)
    if getattr(cfg, "attn_bwd", "flash") == "flash":
        return _sdpa_chunked_flash(cfg, q, k, v, positions[0], positions[0],
                                   window, scale=scale)
    return _sdpa_chunked(cfg, q, k, v, positions[0], positions[0], window,
                         scale=scale)


class KVCache(NamedTuple):
    k: jax.Array  # [n_slots, B, S_max, K, dh]
    v: jax.Array

    @staticmethod
    def init(cfg: ModelConfig, n_slots: int, batch: int, s_max: int):
        dt = jnp.dtype(cfg.compute_dtype)
        shape = (n_slots, batch, s_max, cfg.n_kv_heads, cfg.d_head)
        return KVCache(jnp.zeros(shape, dt), jnp.zeros(shape, dt))


@jax.named_scope("attention")
def attention_prefill(params: Pytree, cfg: ModelConfig, spec: LayerSpec,
                      x: jax.Array, positions: jax.Array, cache: KVCache,
                      slot) -> Tuple[jax.Array, KVCache]:
    b, s, _ = x.shape
    q, k, v = _project_qkv(params, cfg, x, positions)
    cache = KVCache(
        k=lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype)[None],
                                   (slot, 0, 0, 0, 0)),
        v=lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype)[None],
                                   (slot, 0, 0, 0, 0)))
    window = cfg.sliding_window if spec.attn_kind == "local" else 0
    if s <= _DIRECT_SEQ_LIMIT:
        bias = _mask_bias(positions[0], positions[0], window)
        out = _sdpa_direct(cfg, q, k, v, bias)
    else:
        out = _sdpa_chunked(cfg, q, k, v, positions[0], positions[0], window)
    return _finish(params, b, s, out), cache


@jax.named_scope("attention")
def attention_decode(params: Pytree, cfg: ModelConfig, spec: LayerSpec,
                     x: jax.Array, pos: jax.Array, cache: KVCache,
                     slot) -> Tuple[jax.Array, KVCache]:
    """One-token decode: x [B, 1, D]; pos scalar int32 (current position)."""
    b = x.shape[0]
    positions = jnp.full((b, 1), pos, jnp.int32)
    q, k, v = _project_qkv(params, cfg, x, positions)
    cache = KVCache(
        k=lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype)[None],
                                   (slot, 0, pos, 0, 0)),
        v=lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype)[None],
                                   (slot, 0, pos, 0, 0)))
    k_all = lax.dynamic_index_in_dim(cache.k, slot, 0, keepdims=False)
    v_all = lax.dynamic_index_in_dim(cache.v, slot, 0, keepdims=False)
    s_max = k_all.shape[1]
    k_pos = jnp.arange(s_max, dtype=jnp.int32)
    window = cfg.sliding_window if spec.attn_kind == "local" else 0
    keep = k_pos <= pos
    if window > 0:
        keep &= k_pos > pos - window
    bias = jnp.where(keep, 0.0, NEG_INF).astype(jnp.float32)[None, :]  # [1,S]
    out = _sdpa_direct(cfg, q, k_all, v_all, bias)
    return _finish(params, b, 1, out), cache
