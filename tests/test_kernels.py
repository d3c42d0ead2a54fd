"""Per-kernel shape/dtype sweeps: Pallas (interpreted on CPU) vs the
pure-jnp ref.py oracle for every kernel in src/repro/kernels/."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.alf_step import ops as alf_ops
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.rmsnorm import ops as rn_ops
from repro.kernels.rmsnorm import ref as rn_ref

DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-5, atol=1e-5)


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# ---------------------------------------------------------------------------
# alf_step: fused elementwise ALF state updates (pytree-generic)
# ---------------------------------------------------------------------------

ALF_STATES = [
    {"z": (128,)},
    {"z": (3, 200)},                      # non-lane-aligned => pad path
    {"z": (2, 64, 64), "w": (257,)},      # multi-leaf pytree
]


@pytest.mark.parametrize("shapes", ALF_STATES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("eta", [1.0, 0.8])
def test_alf_kernels_vs_ref(shapes, dtype, eta):
    keys = jax.random.split(jax.random.PRNGKey(0), 3 * len(shapes))
    mk = lambda i: {k: _rand(keys[i * len(shapes) + j], s, dtype)
                    for j, (k, s) in enumerate(shapes.items())}
    z, v, u = mk(0), mk(1), mk(2)
    h = jnp.float32(0.23)

    for sign in (1.0, -1.0):
        got = alf_ops.alf_midpoint(z, v, h, sign=sign, use_pallas=True)
        want = alf_ops.alf_midpoint(z, v, h, sign=sign, use_pallas=False)
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(np.asarray(g, np.float32),
                                       np.asarray(w, np.float32),
                                       **_tol(dtype))

    zo_p, vo_p = alf_ops.alf_update(z, v, u, h, eta=eta, use_pallas=True)
    zo_r, vo_r = alf_ops.alf_update(z, v, u, h, eta=eta, use_pallas=False)
    for g, w in zip(jax.tree_util.tree_leaves((zo_p, vo_p)),
                    jax.tree_util.tree_leaves((zo_r, vo_r))):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), **_tol(dtype))

    zi_p, vi_p = alf_ops.alf_inverse_update(z, vo_p, u, h, eta=eta,
                                            use_pallas=True)
    zi_r, vi_r = alf_ops.alf_inverse_update(z, vo_r, u, h, eta=eta,
                                            use_pallas=False)
    for g, w in zip(jax.tree_util.tree_leaves((zi_p, vi_p)),
                    jax.tree_util.tree_leaves((zi_r, vi_r))):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), **_tol(dtype))


def test_alf_kernel_update_inverse_roundtrip():
    """Pallas update followed by Pallas inverse recovers v exactly."""
    z = {"s": jnp.linspace(-1, 1, 384, dtype=jnp.float32)}
    v = {"s": jnp.cos(jnp.linspace(0, 3, 384, dtype=jnp.float32))}
    u = {"s": jnp.sin(jnp.linspace(0, 5, 384, dtype=jnp.float32))}
    h = jnp.float32(0.11)
    zo, vo = alf_ops.alf_update(z, v, u, h, use_pallas=True)
    # inverse tail consumes (k1=z, v_out, u1) and must return v_in = v
    _, vi = alf_ops.alf_inverse_update(z, vo, u, h, use_pallas=True)
    np.testing.assert_allclose(np.asarray(vi["s"]), np.asarray(v["s"]),
                               rtol=1e-6, atol=1e-6)


def test_alf_solver_pallas_backend_parity():
    """ALF(backend='pallas') dispatches the fused midpoint/update kernels
    from inside the solver hierarchy; one trial step must match the
    reference alf_step bit-for-bit math (same f32 algebra, fused launch)."""
    from repro.core.alf import alf_step, alf_step_with_error
    from repro.core.solvers import ALF
    from repro.core.stepsize import AdaptiveController

    def f(params, z, t):
        return {"s": jnp.tanh(params * z["s"]) - 0.2 * z["s"] * t}

    params = jnp.float32(0.7)
    z = {"s": jnp.linspace(-1.0, 1.0, 300, dtype=jnp.float32)}
    v = f(params, z, jnp.float32(0.0))
    t, h = jnp.float32(0.1), jnp.float32(0.23)

    for eta in (1.0, 0.8):
        z_ref, v_ref = alf_step(f, params, z, v, t, h, eta)
        zr, vr, er = alf_step_with_error(f, params, z, v, t, h, eta)
        zp, vp, ep = alf_step_with_error(f, params, z, v, t, h, eta,
                                         backend="pallas")
        # with-error vs plain reference step: identical update
        np.testing.assert_array_equal(np.asarray(zr["s"]),
                                      np.asarray(z_ref["s"]))
        np.testing.assert_array_equal(np.asarray(vr["s"]),
                                      np.asarray(v_ref["s"]))
        for a, b in ((zr, zp), (vr, vp), (er, ep)):
            np.testing.assert_allclose(np.asarray(a["s"]), np.asarray(b["s"]),
                                       rtol=1e-6, atol=1e-6)

    # and through the full solver interface under a controller
    ctrl = AdaptiveController(1e-3, 1e-4, 16)
    for backend in ("reference", "pallas"):
        trial = ALF(eta=0.8, backend=backend).trial_fn(f, params, ctrl)
        out, ratio = trial((z, v), t, h)
        if backend == "reference":
            ref_out, ref_ratio = out, ratio
    np.testing.assert_allclose(np.asarray(out[0]["s"]),
                               np.asarray(ref_out[0]["s"]), rtol=1e-6)
    np.testing.assert_allclose(float(ratio), float(ref_ratio), rtol=1e-6)


@pytest.mark.parametrize("shapes", ALF_STATES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("eta", [1.0, 0.8])
def test_alf_backward_kernels_vs_ref(shapes, dtype, eta):
    """The MALI-backward ops (alf_inverse, alf_bwd_pre, alf_bwd_post):
    Pallas vs jnp-oracle parity over the same state sweep as the forward."""
    keys = jax.random.split(jax.random.PRNGKey(21), 6 * len(shapes))
    mk = lambda i: {k: _rand(keys[i * len(shapes) + j], s, dtype)
                    for j, (k, s) in enumerate(shapes.items())}
    z, v, u, a_z, a_v, dk1 = (mk(i) for i in range(6))
    h = jnp.float32(0.23)

    def check(got, want):
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            assert g.dtype == w.dtype
            np.testing.assert_allclose(np.asarray(g, np.float32),
                                       np.asarray(w, np.float32),
                                       **_tol(dtype))

    check(alf_ops.alf_inverse(z, v, u, h, eta=eta, use_pallas=True),
          alf_ops.alf_inverse(z, v, u, h, eta=eta, use_pallas=False))
    check(alf_ops.alf_bwd_pre(z, v, a_z, a_v, h, eta=eta, use_pallas=True),
          alf_ops.alf_bwd_pre(z, v, a_z, a_v, h, eta=eta, use_pallas=False))
    check(alf_ops.alf_bwd_post(z, v, u, a_z, a_v, dk1, h, eta=eta,
                               use_pallas=True),
          alf_ops.alf_bwd_post(z, v, u, a_z, a_v, dk1, h, eta=eta,
                               use_pallas=False))


def test_alf_kernel_step_inverse_roundtrip():
    """Pallas step followed by the ONE-PASS Pallas psi^-1 (alf_inverse,
    which re-derives k1 internally) recovers (z, v) to float rounding."""
    z = {"s": jnp.linspace(-1, 1, 384, dtype=jnp.float32)}
    v = {"s": jnp.cos(jnp.linspace(0, 3, 384, dtype=jnp.float32))}
    u = {"s": jnp.sin(jnp.linspace(0, 5, 384, dtype=jnp.float32))}
    h = jnp.float32(0.11)
    for eta in (1.0, 0.8):
        k1 = alf_ops.alf_midpoint(z, v, h, use_pallas=True)
        zo, vo = alf_ops.alf_update(k1, v, u, h, eta=eta, use_pallas=True)
        # the true inverse re-evaluates f at k1; feeding the forward's u1
        # makes the algebraic roundtrip exact
        zi, vi = alf_ops.alf_inverse(zo, vo, u, h, eta=eta, use_pallas=True)
        np.testing.assert_allclose(np.asarray(zi["s"]), np.asarray(z["s"]),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(vi["s"]), np.asarray(v["s"]),
                                   rtol=1e-6, atol=1e-6)


def test_alf_forward_ops_custom_vjp_vs_jnp():
    """jax.grad through the Pallas alf_midpoint + alf_update launches (the
    closed-form custom_vjp rules, themselves fused kernels) vs the plain
    jnp formula — including the h cotangent, which adaptive controllers
    feed back into states/params."""
    eta = 0.9
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    z = {"a": _rand(keys[0], (3, 200), jnp.float32)}
    v = {"a": _rand(keys[1], (3, 200), jnp.float32)}
    u = {"a": _rand(keys[2], (3, 200), jnp.float32)}
    h = jnp.float32(0.17)

    def loss_pallas(z, v, u, h):
        k1 = alf_ops.alf_midpoint(z, v, h, use_pallas=True)
        zo, vo = alf_ops.alf_update(k1, v, u, h, eta=eta, use_pallas=True)
        return jnp.sum(zo["a"] ** 2) + jnp.sum(jnp.sin(vo["a"]))

    def loss_jnp(z, v, u, h):
        k1 = {"a": z["a"] + v["a"] * (h / 2)}
        vo = {"a": v["a"] + 2.0 * eta * (u["a"] - v["a"])}
        zo = {"a": k1["a"] + vo["a"] * (h / 2)}
        return jnp.sum(zo["a"] ** 2) + jnp.sum(jnp.sin(vo["a"]))

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2, 3))(z, v, u, h)
    gj = jax.grad(loss_jnp, argnums=(0, 1, 2, 3))(z, v, u, h)
    for g, w in zip(jax.tree_util.tree_leaves(gp),
                    jax.tree_util.tree_leaves(gj)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-6)


def test_alf_ops_mixed_dtype_tree():
    """A {f32, bf16} mixed tree: one fused launch at the promoted common
    dtype, every output leaf restored to its own input dtype (the old
    _flatten force-cast to f32 silently upcast bf16 leaves)."""
    z = {"big": jnp.ones((2, 128), jnp.float32),
         "small": jnp.full((63,), 0.5, jnp.bfloat16)}
    v = {"big": jnp.full((2, 128), 0.25, jnp.float32),
         "small": jnp.full((63,), -0.5, jnp.bfloat16)}
    h = jnp.float32(0.2)
    for use_pallas in (True, False):
        k1 = alf_ops.alf_midpoint(z, v, h, use_pallas=use_pallas)
        assert k1["big"].dtype == jnp.float32
        assert k1["small"].dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(k1["big"]), 1.025, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(k1["small"], np.float32),
                                   0.45, rtol=2e-2)
        # gradients keep per-leaf dtypes too (cotangent avals == primal)
        g = jax.grad(lambda zz, vv: jnp.sum(
            alf_ops.alf_midpoint(zz, vv, h,
                                 use_pallas=use_pallas)["big"]) +
            jnp.sum(alf_ops.alf_midpoint(
                zz, vv, h, use_pallas=use_pallas)["small"]
                .astype(jnp.float32)), argnums=(0, 1))(z, v)
        assert g[0]["small"].dtype == jnp.bfloat16
        assert g[1]["big"].dtype == jnp.float32


def test_alf_ops_preserve_float64():
    """Under x64, f64 state trees stay f64 through the fused launch (the
    old _flatten force-cast every leaf to f32 and lost the precision)."""
    jax.config.update("jax_enable_x64", True)
    try:
        z = {"s": jnp.linspace(-1, 1, 200, dtype=jnp.float64)}
        v = {"s": jnp.cos(jnp.linspace(0, 3, 200, dtype=jnp.float64))}
        u = {"s": jnp.sin(jnp.linspace(0, 5, 200, dtype=jnp.float64))}
        h = jnp.float64(0.1)
        k1 = alf_ops.alf_midpoint(z, v, h, use_pallas=True)
        zo, vo = alf_ops.alf_update(k1, v, u, h, eta=0.8, use_pallas=True)
        assert zo["s"].dtype == jnp.float64
        assert vo["s"].dtype == jnp.float64
        want = np.asarray(z["s"], np.float64) \
            + np.asarray(v["s"], np.float64) * 0.05
        # f64 parity to ~1e-15: would fail at ~1e-7 under an f32 round-trip
        np.testing.assert_allclose(np.asarray(k1["s"]), want, rtol=1e-14)
    finally:
        jax.config.update("jax_enable_x64", False)


# ---------------------------------------------------------------------------
# flash_attention (Pallas-device only: interpret mode cannot emulate these
# kernels on CPU with current jax — see the requires_pallas_device marker)
# ---------------------------------------------------------------------------

FA_CASES = [
    # (B, Sq, Sk, H, KV, d, causal, window, softcap)
    (1, 128, 128, 4, 4, 64, True, 0, 0.0),      # MHA causal
    (2, 128, 128, 4, 2, 64, True, 0, 0.0),      # GQA 2:1
    (1, 256, 256, 8, 1, 64, True, 0, 0.0),      # MQA (granite kv=1)
    (1, 128, 128, 4, 4, 64, False, 0, 0.0),     # bidirectional
    (1, 256, 256, 4, 2, 64, True, 128, 0.0),    # sliding window (gemma2)
    (1, 128, 128, 4, 2, 64, True, 0, 50.0),     # softcap (gemma2)
    (2, 384, 384, 4, 2, 128, True, 256, 30.0),  # window+softcap, d=128
]


@pytest.mark.requires_pallas_device
@pytest.mark.parametrize("case", FA_CASES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_flash_attention_vs_ref(case, dtype):
    b, sq, sk, h, kv, d, causal, window, softcap = case
    kq, kk, kvk = jax.random.split(jax.random.PRNGKey(7), 3)
    q = _rand(kq, (b, sq, h, d), dtype)
    k = _rand(kk, (b, sk, kv, d), dtype)
    v = _rand(kvk, (b, sk, kv, d), dtype)
    got = fa_ops.flash_attention(q, k, v, causal=causal, window=window,
                                 softcap=softcap, use_pallas=True)
    want = fa_ref.attention_ref(q, k, v, causal=causal, window=window,
                                softcap=softcap)
    tol = dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


@pytest.mark.requires_pallas_device
def test_flash_attention_rows_sum_to_one_property():
    """Causal row 0 attends only to itself => output == v[0]."""
    b, s, h, d = 1, 64, 2, 32
    q = _rand(jax.random.PRNGKey(0), (b, s, h, d), jnp.float32)
    k = _rand(jax.random.PRNGKey(1), (b, s, h, d), jnp.float32)
    v = _rand(jax.random.PRNGKey(2), (b, s, h, d), jnp.float32)
    out = fa_ops.flash_attention(q, k, v, causal=True, use_pallas=True)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(v[:, 0]),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------

RN_SHAPES = [(4, 128), (2, 7, 256), (1, 384), (3, 5, 64)]


@pytest.mark.parametrize("shape", RN_SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_rmsnorm_vs_ref(shape, dtype):
    x = _rand(jax.random.PRNGKey(3), shape, dtype)
    scale = 1.0 + 0.1 * _rand(jax.random.PRNGKey(4), shape[-1:], jnp.float32)
    got = rn_ops.rmsnorm(x, scale, use_pallas=True)
    want = rn_ref.rmsnorm_ref(x, scale)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_rmsnorm_unit_output_norm():
    """RMS of output/scale must be ~1 per row."""
    x = 5.0 * _rand(jax.random.PRNGKey(5), (16, 128), jnp.float32)
    out = rn_ops.rmsnorm(x, jnp.ones((128,)), use_pallas=True)
    rms = np.sqrt(np.mean(np.asarray(out) ** 2, axis=-1))
    np.testing.assert_allclose(rms, 1.0, rtol=1e-3)


# ---------------------------------------------------------------------------
# mamba_scan: fused selective scan
# ---------------------------------------------------------------------------

from repro.kernels.mamba_scan import ops as ms_ops  # noqa: E402
from repro.kernels.mamba_scan import ref as ms_ref  # noqa: E402

MS_CASES = [
    # (Bt, S, DI, ST)
    (1, 16, 128, 16),
    (2, 33, 256, 16),     # odd seq
    (1, 8, 200, 8),       # DI padding path
    (2, 64, 512, 4),
]


@pytest.mark.parametrize("case", MS_CASES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_mamba_scan_vs_ref(case, dtype):
    bt, s, di, st = case
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    delta = jax.nn.softplus(_rand(ks[0], (bt, s, di), dtype))
    u = _rand(ks[1], (bt, s, di), dtype)
    A = -jnp.exp(0.3 * jax.random.normal(ks[2], (di, st)))
    B = _rand(ks[3], (bt, s, st), dtype)
    C = _rand(ks[4], (bt, s, st), dtype)
    y_p, h_p = ms_ops.selective_scan(delta, u, A, B, C, use_pallas=True)
    y_r, h_r = ms_ref.selective_scan_ref(delta, u, A, B, C)
    tol = dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_r), **tol)
    np.testing.assert_allclose(np.asarray(h_p), np.asarray(h_r), **tol)


def test_mamba_scan_carries_initial_state():
    bt, s, di, st = 1, 12, 128, 8
    ks = jax.random.split(jax.random.PRNGKey(12), 6)
    delta = jax.nn.softplus(jax.random.normal(ks[0], (bt, s, di)))
    u = jax.random.normal(ks[1], (bt, s, di))
    A = -jnp.exp(0.3 * jax.random.normal(ks[2], (di, st)))
    B = jax.random.normal(ks[3], (bt, s, st))
    C = jax.random.normal(ks[4], (bt, s, st))
    h0 = jax.random.normal(ks[5], (bt, di, st))
    # split scan == full scan (chunked-prefill invariant)
    y_full, h_full = ms_ops.selective_scan(delta, u, A, B, C, h0,
                                           use_pallas=True)
    y1, h1 = ms_ops.selective_scan(delta[:, :6], u[:, :6], A, B[:, :6],
                                   C[:, :6], h0, use_pallas=True)
    y2, h2 = ms_ops.selective_scan(delta[:, 6:], u[:, 6:], A, B[:, 6:],
                                   C[:, 6:], h1, use_pallas=True)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full),
                               rtol=1e-4, atol=1e-5)
