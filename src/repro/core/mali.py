"""MALI: Memory-efficient ALF Integrator (paper Algo 4) as a jax.custom_vjp.

The integrator is built around an *observation grid* ``ts`` of T timepoints
(the torchdiffeq ``odeint(func, y0, t)`` shape): the forward pass is a single
scan whose carry (z, v) crosses segment boundaries, emitting the augmented
state at every requested ``ts[k]``. The VJP residual set is exactly the
per-observation ``(z_k, v_k)`` pairs — O(T * N_z), *constant in the number of
solver steps*. The scalar ``t0 -> t1`` path is the length-1 grid
``ts = [t0, t1]``.

Both step-size policies go through ONE custom_vjp: the static
:class:`~repro.core.stepsize.StepController` in the config decides whether
the forward replays a uniform per-segment sub-grid (``ConstantSteps``) or
runs the bounded accept/reject loop of Algo 1 (``AdaptiveController``); the
backward sweep is controller-agnostic, masking over the recorded accepted
(t_i, h_i) of each segment.

Backward: per segment (in reverse), reconstruct the trajectory step-by-step
with the exact ALF inverse (psi^-1) starting from the stored segment-end
state, and run one local VJP of psi per accepted step, accumulating the
adjoint state a(t) and dL/dtheta — the discretized Eq. (2)/(3) of the paper.
The trajectory cotangent g[k] is injected into a(t) as the sweep crosses
observation k. The stepsize *search* (rejected trials) is excluded, so the
effective computation-graph depth is N_f x N_t (Table 1, MALI column).

Gradients w.r.t. the observation times are zeros by default; with
``MaliConfig(diff_bounds=True)`` (the ``solve(..., diff_bounds=True)``
surface) the backward emits the analytic boundary cotangents
``dL/dt_k = <g_k, f(z_k, t_k)>`` / ``dL/dt_0 = -<a(t0), f(z0, t0)>``
from state already in the replay buffer — the FFJORD trainable-end-time
hook. The forward also emits
:class:`~repro.core.interface.RunStats` integer counters (the
``Solution.stats`` feed); their cotangents are ignored.

:class:`MALI` is this module's :class:`~repro.core.interface.GradientMethod`
— the Table 1 row the paper contributes; it validates solver compatibility
(MALI is defined for the ALF solver only) and carries the ``fused_bwd``
backward-sharing switch.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from .alf import (alf_inverse, alf_step, alf_step_with_error, check_eta,
                  init_velocity, tree_add, tree_sub, tree_zeros_like)
from .integrate import (as_time_grid, integrate_grid, reverse_masked_scan,
                        reverse_segment_sweep, scalar_time_grid)
from .interface import (GradientMethod, RunStats, bounds_cotangents,
                        make_run_stats, state_nbytes)
from .solvers import ALF
from .stepsize import (AdaptiveController, StepController,
                       controller_from_kwargs)

_tm = jax.tree_util.tree_map

Pytree = Any
Dynamics = Callable[[Pytree, Pytree, jax.Array], Pytree]


class MaliConfig(NamedTuple):
    """Static (hashable) integrator configuration."""
    f: Dynamics
    eta: float
    controller: StepController
    fused_bwd: bool = True  # share the inverse's f-eval with the local VJP
    backend: str = "reference"  # forward step algebra: jnp or fused Pallas
    diff_bounds: bool = False  # emit analytic dL/dts boundary cotangents


def _traj_row(traj: Pytree, k: int) -> Pytree:
    return _tm(lambda b: b[k], traj)


def _step_backward(cfg: MaliConfig, params, z_i, v_i, t_start, h, a_z, a_v):
    """One reverse step: reconstruct the step input via psi^-1 and backprop
    psi, either fused (3 f-eval-equivalents) or via the reference two-pass.
    ``backend='pallas'`` dispatches the fused backward kernels: the whole
    elementwise algebra collapses to one launch on each side of the step's
    f-eval linearization (alf_bwd_pre / alf_bwd_post)."""
    if cfg.fused_bwd:
        if cfg.backend == "pallas":
            return _pallas_fused_inverse_and_vjp(cfg.f, cfg.eta, params,
                                                 z_i, v_i, t_start + h, h,
                                                 a_z, a_v)
        return _fused_inverse_and_vjp(cfg.f, cfg.eta, params, z_i, v_i,
                                      t_start + h, h, a_z, a_v)
    z_prev, v_prev = alf_inverse(cfg.f, params, z_i, v_i, t_start + h, h,
                                 cfg.eta, cfg.backend)
    dp, dz, dv = _local_step_vjp(cfg.f, cfg.eta, params, z_prev, v_prev,
                                 t_start, h, a_z, a_v, cfg.backend)
    return z_prev, v_prev, dz, dv, dp


def _local_step_vjp(f, eta, params, z_prev, v_prev, t_prev, h, a_z, a_v,
                    backend="reference"):
    """VJP of one ALF step at the reconstructed input state (reference
    path: re-plays psi under jax.vjp; kept as the oracle for the fused
    implementation below). With ``backend='pallas'`` the replayed step
    launches the fused kernels and jax.vjp differentiates through their
    closed-form custom_vjp rules — the same machinery Naive() uses."""
    def step_fn(p, z, v):
        return alf_step(f, p, z, v, t_prev, h, eta, backend)

    _, vjp_fn = jax.vjp(step_fn, params, z_prev, v_prev)
    return vjp_fn((a_z, a_v))  # (dL/dparams, dL/dz_prev, dL/dv_prev)


def _pallas_fused_inverse_and_vjp(f, eta, params, z_i, v_i, t_i, h, a_z,
                                  a_v):
    """The fused backward step of :func:`_fused_inverse_and_vjp` with its
    elementwise algebra as TWO Pallas launches instead of ~10 per-leaf jnp
    ops: ``alf_bwd_pre`` emits the inverse midpoint k1 AND the f-eval
    cotangent cot_u1 = 2*eta*(a_v + (h/2)*a_z) — which depends only on the
    adjoints, so it is available BEFORE the linearization — then one shared
    ``jax.vjp`` of f provides (u1, dparams, dk1), and ``alf_bwd_post``
    finishes both the psi^-1 reconstruction and the adjoint propagation.
    The f-evaluation VJP itself stays in JAX (it is the model's business,
    not the integrator's)."""
    from repro.kernels.alf_step.ops import alf_bwd_post, alf_bwd_pre
    s1 = t_i - h / 2
    k1, cot_u1 = alf_bwd_pre(z_i, v_i, a_z, a_v, h, eta=eta,
                             use_pallas=True)
    u1, vjp_f = jax.vjp(lambda p, kk: f(p, kk, s1), params, k1)
    dparams, dk1 = vjp_f(cot_u1)
    z_prev, v_prev, dz_prev, dv_prev = alf_bwd_post(
        k1, v_i, u1, a_z, a_v, dk1, h, eta=eta, use_pallas=True)
    return z_prev, v_prev, dz_prev, dv_prev, dparams


def _fused_inverse_and_vjp(f, eta, params, z_i, v_i, t_i, h, a_z, a_v):
    """One backward step of Algo 4 with the inverse's f-eval SHARED with the
    local VJP (beyond-paper optimization; EXPERIMENTS.md §Perf).

    The ALF inverse evaluates u1 = f(k1, s1) at k1 = z_i - v_i*h/2; the
    local VJP of psi needs the linearization of f at exactly the same point
    (k1 = z_prev + v_prev*h/2 by construction). One ``jax.vjp`` call
    provides both, cutting the backward from 4 to 3 f-eval-equivalents per
    step. The rest of psi is linear, so its VJP is written out by hand:

        v_out = (1-2*eta)*v_prev + 2*eta*u1 ;  z_out = k1 + v_out*h/2
        cot_vout = a_v + (h/2)*a_z
        cot_u1   = 2*eta*cot_vout
        (dparams, dk1) = vjp_f(cot_u1)
        cot_k1   = a_z + dk1
        dz_prev  = cot_k1
        dv_prev  = (h/2)*cot_k1 + (1-2*eta)*cot_vout

    Returns (z_prev, v_prev, dz_prev, dv_prev, dparams).
    """
    s1 = t_i - h / 2
    k1 = _tm(lambda zi, vi: zi - vi * (h / 2), z_i, v_i)
    u1, vjp_f = jax.vjp(lambda p, kk: f(p, kk, s1), params, k1)
    # inverse tail (Algo 3 / damped Appendix Algo 3)
    if eta == 1.0:
        v_prev = _tm(lambda ui, vo: 2.0 * ui - vo, u1, v_i)
    else:
        inv = 1.0 / (1.0 - 2.0 * eta)
        v_prev = _tm(lambda vo, ui: (vo - 2.0 * eta * ui) * inv, v_i, u1)
    z_prev = _tm(lambda ki, vp: ki - vp * (h / 2), k1, v_prev)
    # manual VJP of the (linear-except-f) forward step
    cot_vout = _tm(lambda av, az: av + (h / 2) * az, a_v, a_z)
    cot_u1 = _tm(lambda c: 2.0 * eta * c, cot_vout)
    dparams, dk1 = vjp_f(cot_u1)
    cot_k1 = _tm(jnp.add, a_z, dk1)
    dz_prev = cot_k1
    dv_prev = _tm(lambda ck, cv: (h / 2) * ck + (1.0 - 2.0 * eta) * cv,
                  cot_k1, cot_vout)
    return z_prev, v_prev, dz_prev, dv_prev, dparams


def _close_v0_vjp(f, params, z0, t0, a_z, a_v, g_params):
    """Close the v0 = f(z0, t0) initialization: route a_v into z0/params."""
    _, vjp_f = jax.vjp(lambda p, z: f(p, z, t0), params, z0)
    dp, dz = vjp_f(a_v)
    return tree_add(g_params, dp), tree_add(a_z, dz)


# ---------------------------------------------------------------------------
# The (single, controller-parameterized) MALI custom_vjp
# ---------------------------------------------------------------------------

def _mali_forward(cfg: MaliConfig, params, z0, ts):
    """Shared forward: one grid integration of the augmented (z, v) state
    under cfg's controller. Returns the full GridResult bookkeeping.

    The forward runs inside the custom_vjp primal — never differentiated
    through — so cfg.backend may route the step algebra through the fused
    Pallas kernels; the backward sweep honors the same backend, dispatching
    the fused inverse+VJP kernels (_pallas_fused_inverse_and_vjp) or the
    hand-fused jnp reference (_fused_inverse_and_vjp).
    """
    v0 = init_velocity(cfg.f, params, z0, ts[0])

    def trial(state, t, h):
        z, v = state
        z1, v1, err = alf_step_with_error(cfg.f, params, z, v, t, h,
                                          cfg.eta, cfg.backend)
        return (z1, v1), cfg.controller.error_ratio(err, z, z1)

    return integrate_grid(trial, (z0, v0), ts, controller=cfg.controller,
                          order=2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mali_grid(cfg: MaliConfig, params: Pytree, z0: Pytree,
               ts: jax.Array) -> Tuple[Pytree, RunStats]:
    res = _mali_forward(cfg, params, z0, ts)
    z_traj, _ = res.traj
    return z_traj, make_run_stats(res.n_accepted, res.n_trials, 1, 1)


def _mali_grid_fwd(cfg, params, z0, ts):
    res = _mali_forward(cfg, params, z0, ts)
    z_traj, v_traj = res.traj
    # Residuals: the per-observation (z_k, v_k) pairs — O(T * N_z), constant
    # in the solver-step count — plus the O(T * step_bound) recorded (t, h)
    # scalars the backward sweep replays.
    out = (z_traj, make_run_stats(res.n_accepted, res.n_trials, 1, 1))
    return out, (params, z_traj, v_traj, res.ts, res.hs, res.n_accepted, ts)


@jax.named_scope("mali_backward")
def _mali_grid_bwd(cfg, res, g):
    g_traj = g[0]  # RunStats cotangents (g[1]) are zero/float0 — ignored.
    params, z_traj, v_traj, seg_ts, seg_hs, seg_acc, ts = res

    def step_body(c, t_start, h):
        z_i, v_i, az, av, gp = c
        z_prev, v_prev, dz, dv, dp = _step_backward(
            cfg, params, z_i, v_i, t_start, h, az, av)
        return (z_prev, v_prev, dz, dv, tree_add(gp, dp))

    def seg(carry, g_k1, xs_k):
        a_z, a_v, g_p = carry
        z_k1, v_k1, ts_k, hs_k, n_k = xs_k
        # The stored segment-end state is the exact forward value: resetting
        # to it (rather than chaining psi^-1 across segments) stops float
        # drift from accumulating across observations.
        a_z = tree_add(a_z, g_k1)
        carry_k = (z_k1, v_k1, a_z, a_v, g_p)
        _, _, a_z, a_v, g_p = reverse_masked_scan(
            step_body, carry_k, ts_k, hs_k, n_k, cfg.controller.step_bound)
        return (a_z, a_v, g_p)

    z0 = _traj_row(z_traj, 0)
    carry0 = (tree_zeros_like(z0), tree_zeros_like(_traj_row(v_traj, 0)),
              tree_zeros_like(params))
    extras = (_tm(lambda b: b[1:], z_traj), _tm(lambda b: b[1:], v_traj),
              seg_ts, seg_hs, seg_acc)
    a_z, a_v, g_params = reverse_segment_sweep(seg, carry0, g_traj, extras)

    g_params, a_z = _close_v0_vjp(cfg.f, params, z0, ts[0], a_z, a_v, g_params)
    if cfg.diff_bounds:
        # a(t0) is the flow-swept adjoint: total dL/dz0 minus the
        # traj[0] == z0 identity-row cotangent.
        a_t0 = tree_sub(a_z, _traj_row(g_traj, 0))
        g_ts = bounds_cotangents(cfg.f, params, z_traj, ts, g_traj, a_t0)
        return g_params, a_z, g_ts
    return g_params, a_z, jnp.zeros_like(ts)


_mali_grid.defvjp(_mali_grid_fwd, _mali_grid_bwd)


# ---------------------------------------------------------------------------
# The GradientMethod object + legacy function API
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MALI(GradientMethod):
    """The paper's method (Algo 4): reconstruct-the-trajectory gradients at
    O(T * N_z) residual memory, reverse-accurate w.r.t. its own forward
    discretization. ``fused_bwd`` shares psi^-1's f-eval with the local VJP
    (3 instead of 4 f-eval-equivalents per backward step)."""

    fused_bwd: bool = True

    name = "mali"

    # Time direction: the recorded (t_i, h_i) replay buffers are *signed* —
    # a reverse-time solve (t1 < t0, h_i < 0) records negative steps and
    # the backward sweep's psi^-1 reconstruction runs with the same signed
    # h, so ALF's inverse is exercised in both directions and gradients of
    # a reverse solve match the time-reflected forward solve.

    def default_solver(self) -> ALF:
        return ALF()

    def validate(self, solver, controller) -> None:
        if not isinstance(solver, ALF):
            raise ValueError(
                "MALI is defined for the ALF solver only (paper Sec 3); got "
                f"solver {getattr(solver, 'name', solver)!r}. Pass "
                "solver=ALF(eta=...) or use gradient=Naive()/ACA() for "
                "Runge-Kutta solvers.")

    def integrate(self, f, params, z0, ts, solver, controller,
                  diff_bounds: bool = False):
        cfg = MaliConfig(f, solver.eta, controller, self.fused_bwd,
                         solver.backend, diff_bounds)
        traj, stats = _mali_grid(cfg, params, z0, ts)
        return traj, stats

    def residual_bytes(self, z0, n_obs, solver, controller) -> int:
        # The per-observation (z_k, v_k) pairs — constant in step count.
        return 2 * n_obs * state_nbytes(z0)


def odeint_mali(f: Dynamics, params: Pytree, z0: Pytree,
                t0=0.0, t1=1.0, *, ts=None, n_steps: int = 0,
                eta: float = 1.0, rtol: float = 1e-2, atol: float = 1e-3,
                max_steps: int = 64, fused_bwd: bool = True) -> Pytree:
    """Integrate dz/dt = f(params, z, t) with MALI gradients (legacy kwargs
    facade over the object API).

    Without ``ts``: integrate t0 -> t1 and return z(t1) (internally the
    length-1 observation grid ``[t0, t1]``). With ``ts`` (shape (T,), T >= 2):
    return the trajectory pytree with leading axis T, ``traj[0] == z0``.

    ``n_steps > 0`` selects ``ConstantSteps`` (the paper's large-scale
    setting, e.g. h=0.25 -> n_steps=4 on [0,1]); ``n_steps == 0`` selects
    ``AdaptiveController(rtol, atol, max_steps)``.
    """
    check_eta(eta)
    cfg = MaliConfig(f, float(eta),
                     controller_from_kwargs(n_steps, rtol, atol, max_steps),
                     bool(fused_bwd))
    scalar = ts is None
    grid = scalar_time_grid(t0, t1) if scalar else as_time_grid(ts)
    traj, _ = _mali_grid(cfg, params, z0, grid)
    return _traj_row(traj, -1) if scalar else traj


def mali_forward_stats(f: Dynamics, params: Pytree, z0: Pytree, t0=0.0,
                       t1=1.0, *, eta: float = 1.0, rtol: float = 1e-2,
                       atol: float = 1e-3, max_steps: int = 64):
    """Adaptive forward only, returning (zT, n_accepted, n_evals) for the
    paper's m / N_t accounting. Superseded by ``Solution.stats`` (where
    n_evals = n_accepted + n_rejected); kept as a compatibility shim."""
    check_eta(eta)
    cfg = MaliConfig(f, float(eta),
                     AdaptiveController(float(rtol), float(atol),
                                        int(max_steps)), True)
    res = _mali_forward(cfg, params, z0, scalar_time_grid(t0, t1))
    return res.state[0], jnp.sum(res.n_accepted), res.n_trials

