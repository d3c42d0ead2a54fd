"""Shared model components: norms, rotary embeddings, init, dtype policy."""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

Pytree = Any


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------

def dense_init(key: jax.Array, shape: Tuple[int, ...], dtype,
               fan_in: Optional[int] = None) -> jax.Array:
    """Truncated-normal with 1/sqrt(fan_in) scale (standard LM init)."""
    fan_in = fan_in if fan_in is not None else shape[0]
    scale = fan_in ** -0.5
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * scale).astype(dtype)


def embed_init(key: jax.Array, shape: Tuple[int, ...], dtype) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms (RMSNorm is the backbone default; Pallas kernel available in
# repro.kernels.rmsnorm — models call through `rmsnorm` so the kernel can be
# swapped in by the ops layer)
# ---------------------------------------------------------------------------

def rmsnorm_init(d: int, dtype) -> Pytree:
    return {"scale": jnp.ones((d,), dtype)}


@jax.named_scope("norm")
def rmsnorm(params: Pytree, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dtype)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    """Gemma-2 logit soft-capping: cap * tanh(x / cap)."""
    if cap <= 0.0:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(d_head: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., seq, n_heads, d_head]; positions: [..., seq] (int)."""
    d_head = x.shape[-1]
    freqs = rope_frequencies(d_head, theta)          # [d_head/2]
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, d/2]
    cos = jnp.cos(angles)[..., :, None, :]            # [..., S, 1, d/2]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rx1 = x1 * cos - x2 * sin
    rx2 = x2 * cos + x1 * sin
    return jnp.concatenate([rx1, rx2], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------

def silu(x):
    return x * jax.nn.sigmoid(x)


def gelu(x):
    return jax.nn.gelu(x, approximate=True)


ACTIVATIONS = {"silu": silu, "gelu": gelu, "relu": jax.nn.relu}
