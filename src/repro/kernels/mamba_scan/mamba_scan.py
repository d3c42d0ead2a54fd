"""Pallas TPU kernel: fused selective scan (Mamba recurrence).

The core insight of the Mamba CUDA kernel, adapted to TPU: the discretized
state tensors dA, dBu of shape [B, S, DI, ST] must NEVER hit HBM. The
kernel reads only the factors (delta, u: [B, S, DI]; B, C: [B, S, ST];
A: [DI, ST]) and keeps the running state h [block_di, ST] in VMEM/VREGs
across the sequence loop, emitting y [B, S, DI] — HBM traffic drops from
O(S*DI*ST) to O(S*(DI+ST)), a ~ST/2 = 8x reduction at Jamba's ST=16 before
counting the elementwise-chain savings.

Tiling: grid (B, DI/block_di). Per program the VMEM working set is
delta/u/y tiles [S, block_di] f32 (3 x 4 MB at S=4096, block_di=256),
B/C [S, ST] (2 x 256 KB) and h [block_di, ST] (16 KB) — comfortably inside
the ~16 MB VMEM budget; longer sequences are handled by the caller chunking
S (models/ssm.py already scans over chunks).

GPU->TPU adaptation notes (DESIGN.md §8): the CUDA kernel's warp-parallel
prefix scan becomes a sequential fori_loop over S here — on TPU the VPU
processes the [block_di, ST] state as full vector registers per step, and
the win comes from VMEM residency, not intra-step parallelism. The
matmul-free recurrence never touches the MXU; y's contraction over ST is a
VPU reduction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels.dispatch import pallas_call

BLOCK_DI = 256


def _scan_kernel(delta_ref, u_ref, a_ref, b_ref, c_ref, h0_ref,
                 y_ref, hout_ref, *, seq_len: int):
    a = a_ref[0].astype(jnp.float32)                 # [bdi, ST]
    h = h0_ref[0].astype(jnp.float32)                # [bdi, ST]

    def step(t, h):
        dt = delta_ref[0, t].astype(jnp.float32)     # [bdi]
        ut = u_ref[0, t].astype(jnp.float32)         # [bdi]
        bt = b_ref[0, t].astype(jnp.float32)         # [ST]
        ct = c_ref[0, t].astype(jnp.float32)         # [ST]
        dA = jnp.exp(dt[:, None] * a)                # [bdi, ST]
        h = dA * h + (dt * ut)[:, None] * bt[None, :]
        y_ref[0, t] = (h * ct[None, :]).sum(-1).astype(y_ref.dtype)
        return h

    h = lax.fori_loop(0, seq_len, step, h)
    hout_ref[0] = h.astype(hout_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_di",))
def selective_scan_call(delta: jax.Array, u: jax.Array, A: jax.Array,
                        B: jax.Array, C: jax.Array, h0: jax.Array,
                        block_di: int = BLOCK_DI):
    """delta/u: [Bt, S, DI]; A: [DI, ST]; B/C: [Bt, S, ST];
    h0: [Bt, DI, ST]. Returns (y [Bt, S, DI] f32, h_final [Bt, DI, ST] f32).
    DI % block_di == 0 (ops wrapper pads)."""
    bt, s, di = delta.shape
    st = A.shape[1]
    block_di = min(block_di, di)
    assert di % block_di == 0
    grid = (bt, di // block_di)

    kernel = functools.partial(_scan_kernel, seq_len=s)
    y, h_out = pallas_call(
        kernel,
        name="mamba_scan",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, s, block_di), lambda b, i: (b, 0, i)),   # delta
            pl.BlockSpec((1, s, block_di), lambda b, i: (b, 0, i)),   # u
            pl.BlockSpec((1, block_di, st), lambda b, i: (0, i, 0)),  # A
            pl.BlockSpec((1, s, st), lambda b, i: (b, 0, 0)),         # B
            pl.BlockSpec((1, s, st), lambda b, i: (b, 0, 0)),         # C
            pl.BlockSpec((1, block_di, st), lambda b, i: (b, i, 0)),  # h0
        ],
        out_specs=[
            pl.BlockSpec((1, s, block_di), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, block_di, st), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bt, s, di), jnp.float32),
            jax.ShapeDtypeStruct((bt, di, st), jnp.float32),
        ],
    )(delta, u, A[None], B, C, h0)
    return y, h_out
