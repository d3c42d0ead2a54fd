"""Pallas TPU kernel: fused RMSNorm over [rows, d] with (block_rows, d)
VMEM tiles — one HBM read + one write per element, reduction in f32.

d must be lane-aligned (multiple of 128) for the VPU; the ops wrapper pads
otherwise (all assigned archs have d_model % 128 == 0 except gemma2's 2304
which is 18*128 — fine).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dispatch import pallas_call

BLOCK_ROWS = 256


def _rmsnorm_kernel(x_ref, scale_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)
                  * scale_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm_call(x: jax.Array, scale: jax.Array, eps: float = 1e-6,
                 block_rows: int = BLOCK_ROWS):
    rows, d = x.shape
    bs = min(block_rows, rows)
    assert rows % bs == 0
    return pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        name="rmsnorm",
        grid=(rows // bs,),
        in_specs=[pl.BlockSpec((bs, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((bs, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
    )(x, scale)
