"""jit'd public wrappers for the fused ALF update kernels.

Pytree-generic: leaves are flattened/concatenated to a lane-aligned [rows,
128] buffer in a common storage dtype derived from the leaves
(``jnp.result_type`` — a bf16 tree stays bf16 in HBM, float64 states under
x64 stay f64), processed by one kernel launch, and split back with every
leaf's original dtype restored — so the whole model state is one fused
elementwise pass regardless of parameter structure.

``use_pallas=False`` routes to the jnp oracle — identical math, XLA-fused.
``use_pallas=True`` launches the Pallas kernels: compiled (Mosaic) when the
program is lowered for a TPU, interpreted only when it is lowered for the
CPU (``repro.kernels.dispatch``); tests check it against the oracle.

Reverse rules: the ops a *forward* integration launches (``alf_midpoint``,
``alf_update``) carry closed-form ``jax.custom_vjp`` rules — the step is
elementwise in state, so each cotangent rule is just a second fused kernel
(``midpoint_vjp_call`` / ``update_vjp_call``) plus an identity and a scalar
h-cotangent reduction. Direct backprop (``Naive()``, ``SaveAt(steps=True)``,
dense output) therefore works through the launch. The backward-sweep ops
(``alf_inverse``, ``alf_inverse_update``, ``alf_bwd_pre``, ``alf_bwd_post``)
only ever run inside MALI's own custom_vjp backward and stay forward-only
by design — see ``repro.kernels.registry.NO_REVERSE_RULE``.
"""
from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from . import ref
from .alf_step import (LANES, bwd_post_call, bwd_pre_call, inverse_call,
                       inverse_update_call, midpoint_call, midpoint_vjp_call,
                       update_call, update_vjp_call)

Pytree = Any

_tm = jax.tree_util.tree_map


def _common_dtype(*trees):
    """The jnp.result_type of every leaf across the argument trees — the
    shared storage dtype of one fused launch (mixed trees promote once at
    the flatten, not silently to f32)."""
    leaves = [l for t in trees for l in jax.tree_util.tree_leaves(t)]
    return jnp.result_type(*leaves)


def _as_h(h, cdtype):
    """Normalize the step size to a strong scalar of at least f32 (f64 for
    f64 states) — the fixed aval the custom_vjp h-cotangent reproduces."""
    return jax.lax.convert_element_type(
        jnp.asarray(h), jnp.promote_types(cdtype, jnp.float32))


def _flatten(tree: Pytree, dtype) -> Tuple[jax.Array, Any, Any, int]:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    flat = jnp.concatenate([l.reshape(-1).astype(dtype) for l in leaves])
    n = flat.shape[0]
    pad = (-n) % LANES
    flat = jnp.pad(flat, (0, pad)).reshape(-1, LANES)
    shapes = [(l.shape, l.dtype) for l in leaves]
    return flat, treedef, shapes, n


def _meta(tree: Pytree) -> Tuple[Any, Any, int]:
    """(treedef, shapes, n) of a tree without building its flat buffer —
    for unflattening a kernel output against a *different* tree's leaf
    dtypes (cotangents must reproduce the primal avals exactly)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes = [(l.shape, l.dtype) for l in leaves]
    n = 0
    for shape, _ in shapes:
        size = 1
        for s in shape:
            size *= s
        n += size
    return treedef, shapes, n


def _unflatten(flat: jax.Array, treedef, shapes, n: int) -> Pytree:
    flat = flat.reshape(-1)[:n]
    leaves = []
    off = 0
    for shape, dtype in shapes:
        size = 1
        for s in shape:
            size *= s
        leaves.append(flat[off:off + size].reshape(shape).astype(dtype))
        off += size
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _pick(pairs: Pytree, i: int) -> Pytree:
    """Select component i from a tree whose leaves are tuples."""
    return _tm(lambda p: p[i], pairs, is_leaf=lambda p: isinstance(p, tuple))


def _dtype_tree(tree: Pytree) -> Pytree:
    """Scalar-zero carriers of a tree's leaf dtypes — a residual that
    records the primal avals' dtypes without keeping the arrays alive."""
    return _tm(lambda x: jnp.zeros((), x.dtype), tree)


def _cast_like(tree: Pytree, dt: Pytree) -> Pytree:
    return _tm(lambda x, d: x.astype(d.dtype), tree, dt)


def _meta_like(shaped: Pytree, dt: Pytree) -> Tuple[Any, Any, int]:
    """_meta with shapes from ``shaped`` and dtypes from ``dt``."""
    leaves, treedef = jax.tree_util.tree_flatten(shaped)
    dts = jax.tree_util.tree_leaves(dt)
    shapes = [(l.shape, d.dtype) for l, d in zip(leaves, dts)]
    n = 0
    for shape, _ in shapes:
        size = 1
        for s in shape:
            size *= s
        n += size
    return treedef, shapes, n


def _h_cotangent(h, coeff: float, a: Pytree, g: Pytree):
    """h_bar = coeff * sum over leaves of <a, g>, reduced at h's dtype."""
    tot = jnp.zeros((), h.dtype)
    for ai, gi in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(g)):
        tot = tot + jnp.sum(ai.astype(h.dtype) * gi.astype(h.dtype))
    return tot * coeff


# ---------------------------------------------------------------------------
# alf_midpoint: k1 = z + sign*v*h/2, with a closed-form VJP
#   z_bar = g;  v_bar = sign*(h/2)*g;  h_bar = sum <sign*v/2, g>
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _midpoint(sign, use_pallas, z, v, h):
    if not use_pallas:
        return _tm(lambda zi, vi: ref.midpoint_ref(zi, vi, h, sign), z, v)
    cd = _common_dtype(z, v)
    zf, td, sh, n = _flatten(z, cd)
    vf, _, _, _ = _flatten(v, cd)
    return _unflatten(midpoint_call(zf, vf, h, sign=sign), td, sh, n)


def _midpoint_fwd(sign, use_pallas, z, v, h):
    return _midpoint(sign, use_pallas, z, v, h), (v, h)


def _midpoint_bwd(sign, use_pallas, res, g):
    v, h = res
    if use_pallas:
        gf, _, _, _ = _flatten(g, _common_dtype(g))
        v_bar = _unflatten(midpoint_vjp_call(gf, h, sign=sign), *_meta(v))
    else:
        v_bar = _tm(lambda vi, gi:
                    ref.midpoint_vjp_ref(gi, h, sign).astype(vi.dtype), v, g)
    h_bar = _h_cotangent(h, 0.5 * sign, v, g)
    return (g, v_bar, h_bar)


_midpoint.defvjp(_midpoint_fwd, _midpoint_bwd)


@functools.partial(jax.jit, static_argnames=("sign", "use_pallas"))
def alf_midpoint(z: Pytree, v: Pytree, h, *, sign: float = 1.0,
                 use_pallas: bool = False) -> Pytree:
    """k1 = z + sign*v*h/2 over an arbitrary pytree state. Differentiable:
    the cotangent rule is closed-form (itself one fused kernel on the
    pallas path), so direct backprop works through the launch."""
    return _midpoint(float(sign), bool(use_pallas), z, v,
                     _as_h(h, _common_dtype(z, v)))


# ---------------------------------------------------------------------------
# alf_update: the forward tail, with a closed-form VJP
#   cot_vout = g_v + (h/2)*g_z
#   k1_bar = g_z;  v_bar = (1-2*eta)*cot_vout;  u1_bar = 2*eta*cot_vout
#   h_bar = sum <v_out/2, g_z>
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _update(eta, use_pallas, k1, v, u1, h):
    if not use_pallas:
        pairs = _tm(lambda a, b, c: ref.update_ref(a, b, c, h, eta),
                    k1, v, u1)
        return _pick(pairs, 0), _pick(pairs, 1)
    cd = _common_dtype(k1, v, u1)
    kf, td, sh, n = _flatten(k1, cd)
    vf, _, _, _ = _flatten(v, cd)
    uf, _, _, _ = _flatten(u1, cd)
    zo, vo = update_call(kf, vf, uf, h, eta=eta)
    return _unflatten(zo, td, sh, n), _unflatten(vo, *_meta(v))


def _update_fwd(eta, use_pallas, k1, v, u1, h):
    out = _update(eta, use_pallas, k1, v, u1, h)
    # v_out is the only array the bwd needs numerically (the h-cotangent);
    # the scalar dtype carriers pin the cotangent avals of v and u1.
    return out, (_dtype_tree(v), _dtype_tree(u1), out[1], h)


def _update_bwd(eta, use_pallas, res, g):
    v_dt, u1_dt, v_out, h = res
    g_z, g_v = g
    if use_pallas:
        cd = _common_dtype(g_z, g_v)
        gzf, _, _, _ = _flatten(g_z, cd)
        gvf, _, _, _ = _flatten(g_v, cd)
        vb, ub = update_vjp_call(gzf, gvf, h, eta=eta)
        v_bar = _unflatten(vb, *_meta_like(g_v, v_dt))
        u1_bar = _unflatten(ub, *_meta_like(g_v, u1_dt))
    else:
        pairs = _tm(lambda a, b: ref.update_vjp_ref(a, b, h, eta), g_z, g_v)
        v_bar = _cast_like(_pick(pairs, 0), v_dt)
        u1_bar = _cast_like(_pick(pairs, 1), u1_dt)
    h_bar = _h_cotangent(h, 0.5, v_out, g_z)
    return (g_z, v_bar, u1_bar, h_bar)


_update.defvjp(_update_fwd, _update_bwd)


@functools.partial(jax.jit, static_argnames=("eta", "use_pallas"))
def alf_update(k1: Pytree, v: Pytree, u1: Pytree, h, *, eta: float = 1.0,
               use_pallas: bool = False) -> Tuple[Pytree, Pytree]:
    """Forward tail (z_out, v_out). Differentiable: the step is linear in
    (k1, v, u1), so the VJP is closed-form — one fused kernel on the
    pallas path."""
    return _update(float(eta), bool(use_pallas), k1, v, u1,
                   _as_h(h, _common_dtype(k1, v, u1)))


# ---------------------------------------------------------------------------
# Forward-only backward-sweep ops (NO_REVERSE_RULE — only ever launched
# inside MALI's custom_vjp backward, which is itself never differentiated)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("eta", "use_pallas"))
def alf_inverse_update(k1: Pytree, v_out: Pytree, u1: Pytree, h, *,
                       eta: float = 1.0, use_pallas: bool = False
                       ) -> Tuple[Pytree, Pytree]:
    """psi^-1 tail given the (already recovered) midpoint k1."""
    if not use_pallas:
        pairs = _tm(lambda a, b, c: ref.inverse_update_ref(a, b, c, h, eta),
                    k1, v_out, u1)
        return _pick(pairs, 0), _pick(pairs, 1)
    cd = _common_dtype(k1, v_out, u1)
    kf, td, sh, n = _flatten(k1, cd)
    vf, _, _, _ = _flatten(v_out, cd)
    uf, _, _, _ = _flatten(u1, cd)
    zi, vi = inverse_update_call(kf, vf, uf, h, eta=eta)
    return _unflatten(zi, td, sh, n), _unflatten(vi, *_meta(v_out))


@functools.partial(jax.jit, static_argnames=("eta", "use_pallas"))
def alf_inverse(z_out: Pytree, v_out: Pytree, u1: Pytree, h, *,
                eta: float = 1.0, use_pallas: bool = False
                ) -> Tuple[Pytree, Pytree]:
    """Full psi^-1 state reconstruction in ONE elementwise pass: recover
    (z_in, v_in) from the step output (z_{i+1}, v_{i+1}), given
    u1 = f(k1, s1); the midpoint k1 = z_out - v_out*h/2 is re-derived
    inside the kernel instead of being read back from HBM."""
    if not use_pallas:
        pairs = _tm(lambda a, b, c: ref.inverse_ref(a, b, c, h, eta),
                    z_out, v_out, u1)
        return _pick(pairs, 0), _pick(pairs, 1)
    cd = _common_dtype(z_out, v_out, u1)
    zf, td, sh, n = _flatten(z_out, cd)
    vf, _, _, _ = _flatten(v_out, cd)
    uf, _, _, _ = _flatten(u1, cd)
    zi, vi = inverse_call(zf, vf, uf, h, eta=eta)
    return _unflatten(zi, td, sh, n), _unflatten(vi, *_meta(v_out))


@functools.partial(jax.jit, static_argnames=("eta", "use_pallas"))
def alf_bwd_pre(z_i: Pytree, v_i: Pytree, a_z: Pytree, a_v: Pytree, h, *,
                eta: float = 1.0, use_pallas: bool = False
                ) -> Tuple[Pytree, Pytree]:
    """Fused head of one MALI backward step: the inverse's midpoint
    k1 = z_i - v_i*h/2 plus the f-eval cotangent
    cot_u1 = 2*eta*(a_v + (h/2)*a_z) — which depends only on the adjoints,
    so the WHOLE elementwise algebra before the step's f linearization is
    this single launch."""
    if not use_pallas:
        pairs = _tm(lambda a, b, c, d: ref.bwd_pre_ref(a, b, c, d, h, eta),
                    z_i, v_i, a_z, a_v)
        return _pick(pairs, 0), _pick(pairs, 1)
    cd = _common_dtype(z_i, v_i, a_z, a_v)
    zf, td, sh, n = _flatten(z_i, cd)
    vf, _, _, _ = _flatten(v_i, cd)
    azf, _, _, _ = _flatten(a_z, cd)
    avf, _, _, _ = _flatten(a_v, cd)
    k1, cu = bwd_pre_call(zf, vf, azf, avf, h, eta=eta)
    return _unflatten(k1, td, sh, n), _unflatten(cu, *_meta(a_z))


@functools.partial(jax.jit, static_argnames=("eta", "use_pallas"))
def alf_bwd_post(k1: Pytree, v_out: Pytree, u1: Pytree, a_z: Pytree,
                 a_v: Pytree, dk1: Pytree, h, *, eta: float = 1.0,
                 use_pallas: bool = False
                 ) -> Tuple[Pytree, Pytree, Pytree, Pytree]:
    """Fused tail of one MALI backward step, given dk1 = vjp_f(cot_u1)
    from the shared f linearization: the psi^-1 reconstruction
    (z_prev, v_prev) plus the propagated adjoints (dz_prev, dv_prev) — all
    elementwise algebra after the f linearization, one launch."""
    if not use_pallas:
        pairs = _tm(lambda a, b, c, d, e, g:
                    ref.bwd_post_ref(a, b, c, d, e, g, h, eta),
                    k1, v_out, u1, a_z, a_v, dk1)
        return tuple(_pick(pairs, i) for i in range(4))
    cd = _common_dtype(k1, v_out, u1, a_z, a_v, dk1)
    kf, td, sh, n = _flatten(k1, cd)
    vf, _, _, _ = _flatten(v_out, cd)
    uf, _, _, _ = _flatten(u1, cd)
    azf, _, _, _ = _flatten(a_z, cd)
    avf, _, _, _ = _flatten(a_v, cd)
    df, _, _, _ = _flatten(dk1, cd)
    zp, vp, dz, dv = bwd_post_call(kf, vf, uf, azf, avf, df, h, eta=eta)
    return (_unflatten(zp, td, sh, n), _unflatten(vp, *_meta(v_out)),
            _unflatten(dz, *_meta(a_z)), _unflatten(dv, *_meta(a_v)))
