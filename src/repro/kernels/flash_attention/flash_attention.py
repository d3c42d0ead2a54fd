"""Pallas TPU flash attention (forward): causal GQA with online softmax.

Grid: (batch, kv_head, q_group, num_q_blocks). Each program holds one
(block_q x d) query tile in VMEM and streams (block_kv x d) K/V tiles with a
``fori_loop``, maintaining the online-softmax (m, l, acc) accumulators in
f32 — the FlashAttention-2 schedule adapted to TPU: block sizes are MXU-
aligned (q 256 x kv 512 x d 128 by default), K/V tiles are read through VMEM
(HBM->VMEM DMA is generated by Mosaic from the BlockSpec), and the causal
structure skips fully-masked KV blocks via the loop upper bound.

Supports: GQA head grouping, attn-logit softcap (gemma2), sliding window.
Forward-only: MALI's backward replays f under jax.vjp, so XLA differentiates
through the jnp fallback path during training; the Pallas kernel is the
serve/prefill hot path. (A custom bwd kernel is a further hillclimb step —
see EXPERIMENTS.md §Perf.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels.dispatch import pallas_call

NEG_INF = -2.0 ** 30
BLOCK_Q = 256
BLOCK_KV = 512


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, block_q: int, block_kv: int,
                  sm_scale: float, causal: bool, window: int, softcap: float,
                  seq_kv: int):
    qb = pl.program_id(3)
    q = q_ref[0, 0, 0].astype(jnp.float32) * sm_scale      # [bq, d]
    d = q.shape[-1]
    q_start = qb * block_q
    q_pos = q_start + lax.iota(jnp.int32, block_q)

    n_kv = -(-seq_kv // block_kv)   # padded-KV blocks (tail masked above)
    if causal:
        # last kv block that intersects the causal triangle of this q block
        hi = (q_start + block_q + block_kv - 1) // block_kv
        n_kv = min(n_kv, hi) if isinstance(hi, int) else n_kv

    def body(j, carry):
        m, l, acc = carry
        k = pl.load(k_ref, (0, 0, pl.ds(j * block_kv, block_kv),
                            slice(None))).astype(jnp.float32)
        v = pl.load(v_ref, (0, 0, pl.ds(j * block_kv, block_kv),
                            slice(None))).astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [bq, bkv]
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = j * block_kv + lax.iota(jnp.int32, block_kv)
        # mask KV padding (ops wrapper pads Sk up to a block multiple)
        keep = (k_pos[None, :] < seq_kv) & jnp.ones(
            (block_q, block_kv), jnp.bool_)
        if causal:
            keep &= k_pos[None, :] <= q_pos[:, None]
        if window > 0:
            keep &= k_pos[None, :] > q_pos[:, None] - window
        s = jnp.where(keep, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        return m_new, l_new, acc_new

    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m, l, acc = lax.fori_loop(0, n_kv, body, (m0, l0, acc0))
    o_ref[0, 0, 0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_call(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = True, window: int = 0,
                         softcap: float = 0.0, block_q: int = BLOCK_Q,
                         block_kv: int = BLOCK_KV):
    """q: [B, KV, G, Sq, d]; k/v: [B, KV, Sk, d]. Sq % block_q == 0,
    Sk % block_kv == 0 (ops wrapper pads)."""
    b, kh, g, sq, d = q.shape
    sk_real = k.shape[2]
    block_q = min(block_q, sq)
    block_kv = min(block_kv, sk_real)
    # pad both sequence dims up to block multiples; the kernel masks the KV
    # tail (k_pos < seq_kv) and the Q tail rows are sliced off below.
    pad_q = (-sq) % block_q
    pad_kv = (-sk_real) % block_kv
    if pad_q:
        q = jnp.pad(q, ((0, 0),) * 3 + ((0, pad_q), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
    sq_p = sq + pad_q
    sk = sk_real + pad_kv
    grid = (b, kh, g, sq_p // block_q)

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_kv=block_kv,
        sm_scale=d ** -0.5, causal=causal, window=window, softcap=softcap,
        seq_kv=sk_real)
    out = pallas_call(
        kernel,
        name="flash_attention",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, block_q, d),
                         lambda b_, k_, g_, q_: (b_, k_, g_, q_, 0)),
            pl.BlockSpec((1, 1, sk, d), lambda b_, k_, g_, q_: (b_, k_, 0, 0)),
            pl.BlockSpec((1, 1, sk, d), lambda b_, k_, g_, q_: (b_, k_, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, block_q, d),
                               lambda b_, k_, g_, q_: (b_, k_, g_, q_, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
    )(q, k, v)
    return out[:, :, :, :sq] if pad_q else out
