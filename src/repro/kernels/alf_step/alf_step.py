"""Pallas TPU kernels for the fused ALF state updates — forward AND backward.

Tiling: the state is flattened to [rows, 128] (lane-aligned) and tiled in
(block_rows, 128) VMEM blocks — elementwise, so any tiling is valid; 128
lanes match the VPU, block_rows sized so in+out blocks fit comfortably in
VMEM (default 1024 rows -> 5 x 512KB f32 blocks per program).

The step size ``h`` rides as a (1, 1) array in SMEM (a runtime scalar, not
a compile-time constant), so one compiled kernel serves every step of an
adaptive integration. Two dimensions, because Mosaic tiles the last two
dims of every block: under ``vmap`` (per-sample step sizes) h becomes
(B, 1, 1) and each program's (1, 1) block still spans them whole.

Interpret mode follows the lowering platform (``repro.kernels.dispatch``):
compiled Mosaic on TPU, the Pallas interpreter on CPU.

Kernel inventory (the jnp oracle for each lives in ref.py):

  forward step        _midpoint_kernel, _update_kernel
  psi^-1              _inverse_update_kernel (tail, given k1),
                      _inverse_kernel (full, re-derives k1)
  direct backprop     _midpoint_vjp_kernel, _update_vjp_kernel — the
                      closed-form custom_vjp rules of the forward ops
  MALI backward       _bwd_pre_kernel (inverse midpoint + f-cotangent),
                      _bwd_post_kernel (inverse tail + adjoint propagation)
                      — ONE launch on each side of the step's f-eval VJP

Compute dtype: blocks arrive in the storage dtype; ``_acc`` promotes to at
least f32 for the arithmetic (f64 blocks stay f64 under x64) and every
write casts back via ``.astype(ref.dtype)`` (odelint R003d).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import ambient_mesh
from repro.kernels.dispatch import pallas_call

LANES = 128
BLOCK_ROWS = 1024


def _acc(x):
    """Storage dtype -> compute dtype (>= f32; f64 preserved)."""
    return x.astype(jnp.promote_types(x.dtype, jnp.float32))


def _midpoint_kernel(h_ref, z_ref, v_ref, k1_ref, *, sign: float):
    h = h_ref[0, 0]
    z = _acc(z_ref[...])
    v = _acc(v_ref[...])
    k1_ref[...] = (z + sign * v * (h * 0.5)).astype(k1_ref.dtype)


def _update_kernel(h_ref, k1_ref, v_ref, u1_ref, z_out_ref, v_out_ref, *,
                   eta: float):
    h = h_ref[0, 0]
    k1 = _acc(k1_ref[...])
    v = _acc(v_ref[...])
    u1 = _acc(u1_ref[...])
    v_out = v + 2.0 * eta * (u1 - v)
    v_out_ref[...] = v_out.astype(v_out_ref.dtype)
    z_out_ref[...] = (k1 + v_out * (h * 0.5)).astype(z_out_ref.dtype)


def _inverse_update_kernel(h_ref, k1_ref, vo_ref, u1_ref, z_in_ref, v_in_ref,
                           *, eta: float):
    h = h_ref[0, 0]
    k1 = _acc(k1_ref[...])
    vo = _acc(vo_ref[...])
    u1 = _acc(u1_ref[...])
    if eta == 1.0:
        v_in = 2.0 * u1 - vo
    else:
        v_in = (vo - 2.0 * eta * u1) * (1.0 / (1.0 - 2.0 * eta))
    v_in_ref[...] = v_in.astype(v_in_ref.dtype)
    z_in_ref[...] = (k1 - v_in * (h * 0.5)).astype(z_in_ref.dtype)


def _inverse_kernel(h_ref, zo_ref, vo_ref, u1_ref, z_in_ref, v_in_ref, *,
                    eta: float):
    """Full psi^-1: midpoint recovery + inverse tail in one pass."""
    h = h_ref[0, 0]
    zo = _acc(zo_ref[...])
    vo = _acc(vo_ref[...])
    u1 = _acc(u1_ref[...])
    k1 = zo - vo * (h * 0.5)
    if eta == 1.0:
        v_in = 2.0 * u1 - vo
    else:
        v_in = (vo - 2.0 * eta * u1) * (1.0 / (1.0 - 2.0 * eta))
    v_in_ref[...] = v_in.astype(v_in_ref.dtype)
    z_in_ref[...] = (k1 - v_in * (h * 0.5)).astype(z_in_ref.dtype)


def _midpoint_vjp_kernel(h_ref, g_ref, vbar_ref, *, sign: float):
    h = h_ref[0, 0]
    g = _acc(g_ref[...])
    vbar_ref[...] = (sign * g * (h * 0.5)).astype(vbar_ref.dtype)


def _update_vjp_kernel(h_ref, gz_ref, gv_ref, vbar_ref, ubar_ref, *,
                       eta: float):
    h = h_ref[0, 0]
    gz = _acc(gz_ref[...])
    gv = _acc(gv_ref[...])
    cot_vout = gv + gz * (h * 0.5)
    vbar_ref[...] = ((1.0 - 2.0 * eta) * cot_vout).astype(vbar_ref.dtype)
    ubar_ref[...] = (2.0 * eta * cot_vout).astype(ubar_ref.dtype)


def _bwd_pre_kernel(h_ref, z_ref, v_ref, az_ref, av_ref, k1_ref, cu_ref, *,
                    eta: float):
    h = h_ref[0, 0]
    z = _acc(z_ref[...])
    v = _acc(v_ref[...])
    az = _acc(az_ref[...])
    av = _acc(av_ref[...])
    k1_ref[...] = (z - v * (h * 0.5)).astype(k1_ref.dtype)
    cu_ref[...] = (2.0 * eta * (av + az * (h * 0.5))).astype(cu_ref.dtype)


def _bwd_post_kernel(h_ref, k1_ref, vo_ref, u1_ref, az_ref, av_ref, dk1_ref,
                     zp_ref, vp_ref, dz_ref, dv_ref, *, eta: float):
    h = h_ref[0, 0]
    k1 = _acc(k1_ref[...])
    vo = _acc(vo_ref[...])
    u1 = _acc(u1_ref[...])
    az = _acc(az_ref[...])
    av = _acc(av_ref[...])
    dk1 = _acc(dk1_ref[...])
    if eta == 1.0:
        v_prev = 2.0 * u1 - vo
    else:
        v_prev = (vo - 2.0 * eta * u1) * (1.0 / (1.0 - 2.0 * eta))
    vp_ref[...] = v_prev.astype(vp_ref.dtype)
    zp_ref[...] = (k1 - v_prev * (h * 0.5)).astype(zp_ref.dtype)
    cot_k1 = az + dk1
    dz_ref[...] = cot_k1.astype(dz_ref.dtype)
    cot_vout = av + az * (h * 0.5)
    dv_ref[...] = (cot_k1 * (h * 0.5)
                   + (1.0 - 2.0 * eta) * cot_vout).astype(dv_ref.dtype)


def _local_call(kernel, name, h, arrays, n_out, block_rows):
    """One launch of the kernel ``name`` over [rows, LANES] arrays on one
    device."""
    rows = arrays[0].shape[0]
    bs = min(block_rows, rows)
    # Pad rows to a block multiple: an unguarded `rows // bs` grid covers
    # only (rows // bs) * bs rows and the tail is silently never written
    # (odelint R003). The ops are elementwise, so zero-padding is exact.
    pad = (-rows) % bs
    if pad:
        arrays = [jnp.pad(a, ((0, pad), (0, 0))) for a in arrays]
    rows_p = rows + pad
    assert rows_p % bs == 0
    grid = (rows_p // bs,)
    spec = pl.BlockSpec((bs, LANES), lambda i: (i, 0))
    out_shape = tuple(
        jax.ShapeDtypeStruct((rows_p, LANES), a.dtype)
        for a in arrays[:n_out])
    fn = pallas_call(
        kernel,
        name=name,
        grid=grid,
        in_specs=([pl.BlockSpec(memory_space=pltpu.SMEM)]
                  + [spec] * len(arrays)),
        out_specs=(spec,) * n_out if n_out > 1 else spec,
        out_shape=out_shape if n_out > 1 else out_shape[0],
    )
    return _unpad(fn(h, *arrays), rows, pad, n_out)


def _unpad(out, rows, pad, n_out):
    if not pad:
        return out
    if n_out > 1:
        return tuple(o[:rows] for o in out)
    return out[:rows]


def _row_mesh():
    """The mesh to split rows over, or None: the ambient multi-device mesh
    of a ``with mesh:`` context, unless the caller is already inside a
    shard_map body (manual axes), where the arrays are device-local."""
    mesh = ambient_mesh()
    if mesh is None or mesh.size == 1 or \
            jax.sharding.get_abstract_mesh().manual_axes:
        return None
    return mesh


@jax.named_scope("alf_kernel")
def _tiled_call(kernel, name, args, n_out, block_rows=BLOCK_ROWS):
    """args: (h_scalar, *arrays) with arrays pre-shaped [rows, LANES].

    Under a multi-device mesh the launch is shard-local: GSPMD cannot
    partition a Mosaic kernel, and every op here is elementwise, so each
    device runs the kernel on its own slice of rows (a shard_map over all
    mesh axes, rows zero-padded to a multiple of the device count).
    Every call, its padding included, runs under the ``alf_kernel`` scope.
    """
    h, *arrays = args
    # h rides at >= f32 whatever the block storage dtype (a bf16 h would
    # quantize small adaptive steps); f64 blocks get an f64 h under x64.
    h = jnp.asarray(h, jnp.promote_types(arrays[0].dtype, jnp.float32))
    h = h.reshape(1, 1)
    mesh = _row_mesh()
    if mesh is None:
        return _local_call(kernel, name, h, arrays, n_out, block_rows)
    rows = arrays[0].shape[0]
    pad = (-rows) % mesh.size
    if pad:
        arrays = [jnp.pad(a, ((0, pad), (0, 0))) for a in arrays]
    rows_spec = P(tuple(mesh.axis_names))
    out = jax.shard_map(
        lambda h_, *a: _local_call(kernel, name, h_, list(a), n_out,
                                   block_rows),
        mesh=mesh, in_specs=(P(),) + (rows_spec,) * len(arrays),
        out_specs=(rows_spec,) * n_out if n_out > 1 else rows_spec,
        check_vma=False)(h, *arrays)
    return _unpad(out, rows, pad, n_out)


def midpoint_call(z, v, h, *, sign=1.0, block_rows=BLOCK_ROWS):
    return _tiled_call(functools.partial(_midpoint_kernel, sign=sign),
                       "alf_midpoint", (h, z, v), 1, block_rows)


def update_call(k1, v, u1, h, *, eta=1.0, block_rows=BLOCK_ROWS):
    return _tiled_call(functools.partial(_update_kernel, eta=eta),
                       "alf_update", (h, k1, v, u1), 2, block_rows)


def inverse_update_call(k1, v_out, u1, h, *, eta=1.0, block_rows=BLOCK_ROWS):
    return _tiled_call(functools.partial(_inverse_update_kernel, eta=eta),
                       "alf_inverse_update", (h, k1, v_out, u1), 2,
                       block_rows)


def inverse_call(z_out, v_out, u1, h, *, eta=1.0, block_rows=BLOCK_ROWS):
    return _tiled_call(functools.partial(_inverse_kernel, eta=eta),
                       "alf_inverse", (h, z_out, v_out, u1), 2, block_rows)


def midpoint_vjp_call(g, h, *, sign=1.0, block_rows=BLOCK_ROWS):
    return _tiled_call(functools.partial(_midpoint_vjp_kernel, sign=sign),
                       "alf_midpoint_vjp", (h, g), 1, block_rows)


def update_vjp_call(g_z, g_v, h, *, eta=1.0, block_rows=BLOCK_ROWS):
    return _tiled_call(functools.partial(_update_vjp_kernel, eta=eta),
                       "alf_update_vjp", (h, g_z, g_v), 2, block_rows)


def bwd_pre_call(z, v, a_z, a_v, h, *, eta=1.0, block_rows=BLOCK_ROWS):
    return _tiled_call(functools.partial(_bwd_pre_kernel, eta=eta),
                       "alf_bwd_pre", (h, z, v, a_z, a_v), 2, block_rows)


def bwd_post_call(k1, v_out, u1, a_z, a_v, dk1, h, *, eta=1.0,
                  block_rows=BLOCK_ROWS):
    return _tiled_call(functools.partial(_bwd_post_kernel, eta=eta),
                       "alf_bwd_post", (h, k1, v_out, u1, a_z, a_v, dk1), 4,
                       block_rows)
