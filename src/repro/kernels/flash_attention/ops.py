"""jit'd wrapper: [B,S,H,d]/[B,S,K,d] layout -> flash kernel (or jnp oracle)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .flash_attention import flash_attention_call


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "use_pallas"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    softcap: float = 0.0,
                    use_pallas: bool = False) -> jax.Array:
    """q: [B, Sq, H, d]; k/v: [B, Sk, K, d] -> [B, Sq, H, d]."""
    if not use_pallas:
        return ref.attention_ref(q, k, v, causal=causal, window=window,
                                 softcap=softcap)
    b, sq, h, d = q.shape
    kh = k.shape[2]
    g = h // kh
    qg = jnp.moveaxis(q.reshape(b, sq, kh, g, d), 1, 3)   # [B,KV,G,Sq,d]
    kg = jnp.moveaxis(k, 1, 2)                            # [B,KV,Sk,d]
    vg = jnp.moveaxis(v, 1, 2)
    o = flash_attention_call(qg, kg, vg, causal=causal, window=window,
                             softcap=softcap)
    return jnp.moveaxis(o, 3, 1).reshape(b, sq, h, d)
