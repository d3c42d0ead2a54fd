"""jit'd wrapper for the fused selective-scan kernel (jnp oracle on CPU)."""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import ref
from .mamba_scan import BLOCK_DI, selective_scan_call


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def selective_scan(delta: jax.Array, u: jax.Array, A: jax.Array,
                   B: jax.Array, C: jax.Array,
                   h0: Optional[jax.Array] = None, *,
                   use_pallas: bool = False
                   ) -> Tuple[jax.Array, jax.Array]:
    """delta/u: [Bt, S, DI]; A: [DI, ST]; B/C: [Bt, S, ST].
    Returns (y [Bt, S, DI] f32, h_final [Bt, DI, ST] f32)."""
    bt, s, di = delta.shape
    st = A.shape[1]
    if h0 is None:
        h0 = jnp.zeros((bt, di, st), jnp.float32)
    if not use_pallas:
        return ref.selective_scan_ref(delta, u, A, B, C, h0)
    # pad DI up to a block multiple (A rows padded with zeros -> dA=1,
    # dBu=0: padded state stays 0 and is sliced off)
    pad = (-di) % min(BLOCK_DI, max(di, 1))
    if pad:
        delta = jnp.pad(delta, ((0, 0), (0, 0), (0, pad)))
        u = jnp.pad(u, ((0, 0), (0, 0), (0, pad)))
        A = jnp.pad(A, ((0, pad), (0, 0)))
        h0 = jnp.pad(h0, ((0, 0), (0, pad), (0, 0)))
    y, h = selective_scan_call(delta, u, A, B, C, h0)
    if pad:
        y = y[..., :di]
        h = h[:, :di]
    return y, h
