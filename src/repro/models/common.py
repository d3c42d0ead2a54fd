"""Shared model components: norms, rotary embeddings, init, dtype policy."""
from __future__ import annotations

import math
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

def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature correction ``0.1 m ln(factor) + 1``."""
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(dim: int, theta: float, original_len: int,
                          beta_fast: float, beta_slow: float
                          ) -> Tuple[int, int]:
    """The rotary dims ``[low, high]`` over which YaRN ramps from the
    extrapolated to the interpolated frequencies: where ``beta_fast`` and
    ``beta_slow`` rotations fit in ``original_len`` positions."""
    def dim_of(rotations):
        return (dim * math.log(original_len / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    return (max(math.floor(dim_of(beta_fast)), 0),
            min(math.ceil(dim_of(beta_slow)), dim - 1))


def rope_frequencies(d_head: int, theta: float,
                     yarn: Optional[Tuple[float, int, float, float]] = None
                     ) -> jax.Array:
    """Rotary frequencies; with ``yarn = (factor, original_len, beta_fast,
    beta_slow)`` those of YaRN (HF ``DeepseekV2YarnRotaryEmbedding``): the
    frequencies divided by ``factor`` below the correction range, kept
    above it, blended by a linear ramp inside."""
    freqs = 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head))
    if yarn is None:
        return freqs
    factor, original_len, beta_fast, beta_slow = yarn
    low, high = yarn_correction_range(d_head, theta, original_len, beta_fast,
                                      beta_slow)
    ramp = jnp.clip((jnp.arange(d_head // 2, dtype=jnp.float32) - low)
                    / (high - low if high > low else 0.001), 0.0, 1.0)
    return freqs / factor * ramp + freqs * (1.0 - ramp)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               freqs: Optional[jax.Array] = None,
               scale: float = 1.0) -> jax.Array:
    """x: [..., seq, n_heads, d_head]; positions: [..., seq] (int).
    ``freqs`` replaces the plain frequencies (YaRN's), ``scale``
    multiplies cos and sin (YaRN's mscale ratio)."""
    d_head = x.shape[-1]
    if freqs is None:
        freqs = rope_frequencies(d_head, theta)      # [d_head/2]
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, d/2]
    cos = jnp.cos(angles)[..., :, None, :]            # [..., S, 1, d/2]
    sin = jnp.sin(angles)[..., :, None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
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
