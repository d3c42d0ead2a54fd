"""solve(): the composable front door of the integrator library.

The paper's Table 1 is a matrix of gradient methods x solvers x step-size
policies; ``solve`` exposes exactly those axes as independent objects, so a
method-swap experiment is a one-argument change::

    from repro.core import (solve, SaveAt, Solution, ALF, Dopri5,
                            ConstantSteps, AdaptiveController,
                            MALI, Naive, ACA, Backsolve)

    sol = solve(f, params, z0, 0.0, 1.0,
                solver=ALF(eta=1.0),              # paper Algo 2/3
                controller=ConstantSteps(8),      # or AdaptiveController(...)
                gradient=MALI(fused_bwd=True),    # or Naive()/ACA()/Backsolve()
                saveat=SaveAt(ts=jnp.linspace(0., 1., 16)))
    sol.ys      # (16, ...) trajectory
    sol.stats   # accepted/rejected steps, f-evals, residual footprint

Each axis maps back to a paper concept:

* ``solver`` (:mod:`repro.core.solvers`) — the step map ``psi`` of Algo 1;
  :class:`ALF` is the invertible augmented-state solver of Algo 2/3 and
  carries the damping ``eta`` (Appendix A.5).
* ``controller`` (:mod:`repro.core.stepsize`) — Algo 1's accept/reject
  policy: :class:`ConstantSteps` (the large-scale fixed-h setting) or
  :class:`AdaptiveController` (rtol/atol with a bounded trial budget).
* ``gradient`` — the Table 1 row: :class:`MALI` (Algo 4),
  :class:`Naive` (direct backprop), :class:`ACA` (checkpoint adjoint),
  :class:`Backsolve` (reverse-time adjoint, Thm 2.1's drifting baseline).
* ``saveat`` — what to return: ``z(t1)``, the observation-grid trajectory
  (the shape MALI's O(T * N_z) residual claim is stated over), or dense
  per-step output.
* ``batching`` (:mod:`repro.core.interface`) — how a leading batch axis of
  ``z0`` is integrated: :class:`Lockstep` (one shared accept/reject per
  trial, the Chen et al. 2018 concatenated-system semantics),
  :class:`PerSample` (each row carries its own ``(t, h, done)`` through
  the masked scan), or :class:`Sharded` (shard_map data parallelism over
  a mesh axis — the serving path).

``Solution.stats`` replaces the old ``mali_forward_stats`` side channel:
accepted/rejected step counts and forward f-evals come from the actual run
(Algo 1's accounting, rejected trials included), the residual footprint is
the gradient method's analytic Table-1 memory column.

The legacy string-keyed :func:`repro.core.api.odeint` facade is a thin shim
that builds these objects and returns ``Solution.ys``.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from .aca import ACA
from .adjoint import Adjoint, Backsolve
from .dense import build_interpolation, locate_event
from .integrate import (as_time_grid, integrate_grid, scalar_time_grid,
                        validate_span)
from .interface import (Batching, Event, GradientMethod, Lockstep, PerSample,
                        RunStats, SaveAt, Sharded, Solution, Stats,
                        batch_size, make_run_stats, state_nbytes, tree_vdot)
from .mali import MALI
from .naive import Naive, check_direct_backprop as _check_direct_backprop
from .solvers import ALF, Solver, get_solver
from .stepsize import AdaptiveController, StepController

_tm = jax.tree_util.tree_map

Pytree = Any
Dynamics = Callable[[Pytree, Pytree, jax.Array], Pytree]


def _build_stats(rstats: RunStats, gradient: GradientMethod, z0: Pytree,
                 grid: jax.Array, solver: Solver,
                 controller: StepController) -> Stats:
    # NOTE: all counter arithmetic happened inside the gradient method's
    # primal (make_run_stats) — the integer outputs of a custom_vjp carry
    # instantiated float0 tangents under vmap-of-grad, so operating on them
    # here would crash jvp tracing. This only repackages.
    n_obs = int(grid.shape[0])
    return Stats(
        n_accepted=rstats.n_accepted,
        n_rejected=rstats.n_rejected,
        n_fevals=rstats.n_fevals,
        n_segments=n_obs - 1,
        residual_bytes=gradient.residual_bytes(z0, n_obs, solver, controller),
    )


def _record_span(f, params, z0, t0, t1, solver, controller):
    """One state-recording integration over the single [t0, t1] segment
    (the shared forward of SaveAt(steps=True), SaveAt(dense=True) and the
    event-detection pass). Works in both time directions."""
    grid = scalar_time_grid(t0, t1)
    state0 = solver.init_state(f, params, z0, grid[0])
    trial = solver.trial_fn(f, params, controller)
    res = integrate_grid(trial, state0, grid, controller=controller,
                         order=solver.order, record_states=True)
    return grid, res


def _span_interpolation(f, params, solver, grid, res):
    """Fit the dense cubic-Hermite record of one recorded span."""
    states = _tm(lambda b: b[0], res.state_traj)
    return build_interpolation(solver, f, params, states, res.state,
                               res.ts[0], res.hs[0], res.n_accepted[0],
                               grid[0], grid[-1])


def _solve_dense(f, params, z0, t0, t1, solver, controller,
                 gradient) -> Solution:
    """SaveAt(steps=True): record every accepted step of the single
    [t0, t1] segment. Per-step output pins each intermediate state by
    definition, so gradients flow by direct backprop through the recorded
    sequence (there is nothing for a memory-efficient method to save)."""
    _check_direct_backprop(solver, "SaveAt(steps=True)")
    grid, res = _record_span(f, params, z0, t0, t1, solver, controller)

    n_acc = res.n_accepted[0]
    starts = solver.output(_tm(lambda b: b[0], res.state_traj))  # (bound, ...)
    final = solver.output(res.state)
    # One padded buffer: rows 0..n_acc-1 are step-start states, row n_acc is
    # the final state, later rows stay zero. stats.n_accepted tells the
    # caller how many rows are live (n_accepted + 1 including the endpoint).
    ys = _tm(
        lambda b, fin: jnp.concatenate([b, jnp.zeros_like(b[:1])], 0)
        .at[n_acc].set(fin),
        starts, final)
    ts_out = jnp.concatenate([res.ts[0], jnp.zeros((1,), grid.dtype)])
    ts_out = ts_out.at[n_acc].set(grid[-1])

    init_evals = 1 if isinstance(solver, ALF) else 0
    rstats = make_run_stats(res.n_accepted, res.n_trials, solver.stages,
                            init_evals)
    # Dense residuals = the recorded buffer itself.
    stats = _build_stats(rstats, Naive(), z0, grid, solver, controller)
    stats = stats._replace(span_complete=res.completed)
    # Live rows: the n_acc step-start states plus the endpoint row.
    return Solution(ys=ys, ts=ts_out, stats=stats, n_live=n_acc + 1)


def _solve_dense_interp(f, params, z0, t0, t1, solver, controller,
                        gradient) -> Solution:
    """SaveAt(dense=True): record the span and fit the per-accepted-step
    cubic-Hermite interpolant, making ``Solution.evaluate(t)`` live.
    Like steps=True, continuous output pins every intermediate state, so
    gradients (through ``ys`` *and* through ``evaluate``'s interpolated
    values) flow by direct backprop through the recorded sequence."""
    _check_direct_backprop(solver, "SaveAt(dense=True)")
    grid, res = _record_span(f, params, z0, t0, t1, solver, controller)
    interp = _span_interpolation(f, params, solver, grid, res)

    init_evals = ((1 if isinstance(solver, ALF) else 0)
                  + solver.interpolant_fevals(controller.step_bound))
    rstats = make_run_stats(res.n_accepted, res.n_trials, solver.stages,
                            init_evals)
    stats = _build_stats(rstats, Naive(), z0, grid, solver, controller)
    stats = stats._replace(span_complete=res.completed)
    return Solution(ys=solver.output(res.state), ts=grid[-1], stats=stats,
                    interpolation=interp)


def _ift_event_time(f, params, event: Event, z_ev, t_event, fired):
    """Differentiable event time via the implicit function theorem.

    ``locate_event`` runs on a stop-gradient detection pass, so the raw
    ``t_event`` carries no cotangents. The crossing is defined implicitly
    by ``c(z(t*; theta), t*) = 0``, giving

        dt*/dtheta = -<c_z, dz(t*)/dtheta> / (<c_z, f(z*, t*)> + c_t).

    Re-expressed as a value-preserving correction (the torchdiffeq/diffrax
    trick): ``t* - (c(z_ev, t*) - sg(c)) / sg(cdot)`` — the subtraction is
    identically zero in the primal, and its pullback routes the re-solve's
    differentiable ``z_ev`` into exactly the IFT quotient. ``fired`` gates
    the correction so an event-free span keeps a plain (zero-gradient)
    span endpoint."""
    t_arr = jnp.asarray(t_event)
    cval = jnp.asarray(event.cond_fn(z_ev, t_arr))
    z_sg = lax.stop_gradient(z_ev)
    _, vjp_c = jax.vjp(lambda z, t: jnp.asarray(event.cond_fn(z, t)),
                       z_sg, t_arr)
    c_z, c_t = vjp_c(jnp.ones_like(cval))
    cdot = tree_vdot(c_z, f(lax.stop_gradient(params), z_sg, t_arr)) + c_t
    safe = jnp.where(jnp.abs(cdot) > 1e-12, cdot, jnp.ones_like(cdot))
    corr = (cval - lax.stop_gradient(cval)) / lax.stop_gradient(safe)
    return t_arr - jnp.where(fired, corr, jnp.zeros_like(corr))


def _solve_event(f, params, z0, t0, t1, solver, controller, gradient,
                 saveat, event: Event, diff_bounds: bool) -> Solution:
    """Terminating-event solve: dense-record the full span on frozen
    (stop-gradient) inputs, locate/refine the first crossing of
    ``event.cond_fn`` on the interpolant, then re-solve ``[t0, t_event]``
    with the chosen gradient method — the frozen-``t_event`` gradient path
    every method supports (``t_event`` is a constant of the re-solve, so
    MALI replays/reconstructs, ACA checkpoints and Backsolve re-integrates
    exactly as in a plain solve). ``Stats.event_time`` is made
    differentiable afterwards via :func:`_ift_event_time`."""
    if saveat.steps or saveat.dense:
        raise ValueError(
            "SaveAt(steps=True)/SaveAt(dense=True) with event= is not "
            "supported: the per-step record would mix pre- and post-event "
            "steps of the detection pass; use SaveAt(ts=grid) (post-event "
            "rows hold the terminal state) or the default end state")
    trajectory = saveat.ts is not None
    if trajectory:
        user_grid = as_time_grid(saveat.ts)
        t0, t1 = user_grid[0], user_grid[-1]

    # Detection pass — never differentiated (inputs are stop-gradient'd),
    # so it composes with any forward backend, and its bisection costs no
    # dynamics evaluations (polynomial arithmetic on the interpolant).
    p_det = lax.stop_gradient(params)
    z_det = lax.stop_gradient(z0)
    grid, res = _record_span(f, p_det, z_det, t0, t1, solver, controller)
    interp = _span_interpolation(f, p_det, solver, grid, res)
    t_event, fired = locate_event(interp, event.cond_fn, event.direction,
                                  event.max_bisections, grid[-1])
    t_event = lax.stop_gradient(t_event)

    # Differentiable re-solve over the event-terminated span. In grid mode
    # the observation times are clamped at t_event (sign-aware), which
    # turns every post-event segment into a zero-length no-op — those rows
    # of ys/ts hold the frozen terminal state/time by construction.
    if trajectory:
        forward = user_grid[-1] >= user_grid[0]
        clamped = jnp.where(forward, jnp.minimum(user_grid, t_event),
                            jnp.maximum(user_grid, t_event))
        traj, rstats = gradient.integrate(f, params, z0, clamped, solver,
                                          controller, diff_bounds)
        ys, ts_out, grid_out = traj, clamped, clamped
        z_ev = _tm(lambda b: b[-1], traj)
    else:
        grid_out = jnp.stack([grid[0], jnp.asarray(t_event, grid.dtype)])
        traj, rstats = gradient.integrate(f, params, z0, grid_out, solver,
                                          controller, diff_bounds)
        ys, ts_out = _tm(lambda b: b[-1], traj), grid_out[-1]
        z_ev = ys
    t_event = _ift_event_time(f, params, event, z_ev, t_event, fired)

    # Total accounting = re-solve + detection pass. The re-solve counters
    # come out of a custom_vjp primal — detach before arithmetic (their
    # instantiated float0 tangents would crash jvp tracing under
    # vmap-of-grad otherwise).
    det = make_run_stats(res.n_accepted, res.n_trials, solver.stages,
                         (1 if isinstance(solver, ALF) else 0)
                         + solver.interpolant_fevals(controller.step_bound))
    rstats = _detached(rstats)
    stats = Stats(
        n_accepted=rstats.n_accepted + det.n_accepted,
        n_rejected=rstats.n_rejected + det.n_rejected,
        n_fevals=rstats.n_fevals + det.n_fevals,
        n_segments=int(grid_out.shape[0]) - 1,
        residual_bytes=gradient.residual_bytes(z0, int(grid_out.shape[0]),
                                               solver, controller),
        event_fired=fired,
        event_time=t_event,
        span_complete=res.completed,
    )
    return Solution(ys=ys, ts=ts_out, stats=stats)


# ---------------------------------------------------------------------------
# Batched drivers (the Batching axis)
# ---------------------------------------------------------------------------

def _detached(rstats: RunStats) -> RunStats:
    # Counters are integer outputs of a custom_vjp primal; detach before any
    # arithmetic so their instantiated float0 tangents never reach a jvp rule.
    return RunStats(*(jax.lax.stop_gradient(c) for c in rstats))


def _batched_stats(per: RunStats, gradient: GradientMethod, z0: Pytree,
                   grid: jax.Array, solver: Solver,
                   controller: StepController) -> Stats:
    """Stats for a batched solve: ``per_sample`` keeps the (B,) rows, the
    scalar counters hold the per-row totals (sum over rows — so lockstep
    reports B x its shared trial count, comparable with per-sample)."""
    per = _detached(per)
    return Stats(
        n_accepted=jnp.sum(per.n_accepted).astype(jnp.int32),
        n_rejected=jnp.sum(per.n_rejected).astype(jnp.int32),
        n_fevals=jnp.sum(per.n_fevals).astype(jnp.int32),
        n_segments=int(grid.shape[0]) - 1,
        residual_bytes=gradient.residual_bytes(z0, int(grid.shape[0]),
                                               solver, controller),
        per_sample=per,
    )


def _broadcast_rows(rstats: RunStats, nb: int) -> RunStats:
    """Lockstep per-row counters: every row takes the shared step sequence
    and is evaluated on every shared trial, so each row's counters equal
    the batch-system's shared counters."""
    det = _detached(rstats)
    return RunStats(*(jnp.broadcast_to(c, (nb,)) for c in det))


def _batch_first(traj: Pytree) -> Pytree:
    """(T, B, ...) observation trajectory -> the batch-first (B, T, ...)
    convention every batched mode returns."""
    return _tm(lambda b: jnp.moveaxis(b, 0, 1), traj)


def _solve_lockstep(f, params, z0, grid, nb, solver, controller, gradient,
                    trajectory, diff_bounds=False):
    """One shared controller decision per trial: integrate the batch as a
    single concatenated system (the unbatched machinery on the batched
    state — exactly the implicit pre-Batching semantics, made explicit)."""
    traj, rstats = gradient.integrate(f, params, z0, grid, solver,
                                      controller, diff_bounds)
    per = _broadcast_rows(rstats, nb)
    ys = _batch_first(traj) if trajectory else _tm(lambda b: b[-1], traj)
    return ys, per


def _solve_per_sample(f, params, z0, grid, solver, controller, gradient,
                      trajectory, diff_bounds=False):
    """Row-independent adaptive control via the vmapped masked-scan driver
    (each sample carries its own (t, h, done); see integrate.py)."""
    traj, per = gradient.integrate_batched(f, params, z0, grid, solver,
                                           controller, diff_bounds)
    ys = traj if trajectory else _tm(lambda b: b[:, -1], traj)
    return ys, _detached(per)


def _solve_sharded(f, params, z0, grid, nb, solver, controller, gradient,
                   trajectory, batching: Sharded):
    """Data-parallel fleet: shard_map the inner batched driver over one
    mesh axis, one shard of the batch per device group (the serving path —
    reuses the ambient production/host mesh, see repro.launch.mesh)."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import ambient_mesh

    mesh = ambient_mesh()
    if mesh is None:
        raise ValueError(
            "Sharded() batching needs an active mesh context: wrap the "
            "solve in `with mesh:` (repro.launch.mesh.make_host_mesh() or "
            "make_production_mesh()), or use Lockstep()/PerSample() on a "
            "single device")
    if batching.axis not in mesh.axis_names:
        raise ValueError(
            f"Sharded(axis={batching.axis!r}): the active mesh has axes "
            f"{mesh.axis_names}; pass one of those (the production mesh "
            "uses 'data' for batch parallelism)")
    n_shards = mesh.shape[batching.axis]
    if nb % n_shards != 0:
        raise ValueError(
            f"Sharded(axis={batching.axis!r}): batch size {nb} is not "
            f"divisible by the axis size {n_shards}; pad the batch or "
            "pick a divisible size")

    inner_per_sample = isinstance(batching.inner, PerSample)

    def shard_body(p, z_local):
        if inner_per_sample:
            return _solve_per_sample(f, p, z_local, grid, solver,
                                     controller, gradient, trajectory)
        return _solve_lockstep(f, p, z_local, grid, nb // n_shards, solver,
                               controller, gradient, trajectory)

    spec = P(batching.axis)
    ys, per = jax.shard_map(shard_body, mesh=mesh, in_specs=(P(), spec),
                            out_specs=(spec, spec), check_vma=False)(params, z0)
    return ys, per


def _solve_batched(f, params, z0, t0, t1, solver, controller, gradient,
                   saveat, batching: Batching,
                   diff_bounds: bool = False) -> Solution:
    nb = batch_size(z0)

    if saveat.steps or saveat.dense:
        # Lockstep's shared step sequence keeps per-step output rectangular;
        # PerSample/Sharded raggedness is rejected in Batching.validate.
        if saveat.steps:
            sol = _solve_dense(f, params, z0, t0, t1, solver, controller,
                               gradient)
            ys = _batch_first(sol.ys)
        else:
            # dense=True: the end state is already batch-first; the fitted
            # interpolant carries the batch axis inside each coefficient
            # leaf, so evaluate(t) returns (B, ...) per scalar query.
            sol = _solve_dense_interp(f, params, z0, t0, t1, solver,
                                      controller, gradient)
            ys = sol.ys
        per = _broadcast_rows(
            RunStats(sol.stats.n_accepted, sol.stats.n_rejected,
                     sol.stats.n_fevals), nb)
        # Same contract as _batched_stats: scalars are the per-row totals.
        stats = Stats(
            n_accepted=jnp.sum(per.n_accepted).astype(jnp.int32),
            n_rejected=jnp.sum(per.n_rejected).astype(jnp.int32),
            n_fevals=jnp.sum(per.n_fevals).astype(jnp.int32),
            n_segments=sol.stats.n_segments,
            residual_bytes=sol.stats.residual_bytes,
            per_sample=per,
            span_complete=sol.stats.span_complete)
        return Solution(ys=ys, ts=sol.ts, stats=stats,
                        interpolation=sol.interpolation, n_live=sol.n_live)

    trajectory = saveat.ts is not None
    grid = as_time_grid(saveat.ts) if trajectory else scalar_time_grid(t0, t1)

    if isinstance(batching, Sharded):
        ys, per = _solve_sharded(f, params, z0, grid, nb, solver, controller,
                                 gradient, trajectory, batching)
    elif isinstance(batching, PerSample):
        ys, per = _solve_per_sample(f, params, z0, grid, solver, controller,
                                    gradient, trajectory, diff_bounds)
    else:
        ys, per = _solve_lockstep(f, params, z0, grid, nb, solver,
                                  controller, gradient, trajectory,
                                  diff_bounds)

    stats = _batched_stats(per, gradient, z0, grid, solver, controller)
    ts_out = grid if trajectory else grid[-1]
    return Solution(ys=ys, ts=ts_out, stats=stats)


@jax.named_scope("ode")
def solve(f: Dynamics, params: Pytree, z0: Pytree, t0=0.0, t1=1.0, *,
          solver: Optional[Solver] = None,
          controller: Optional[StepController] = None,
          gradient: Optional[GradientMethod] = None,
          saveat: Optional[SaveAt] = None,
          batching: Optional[Batching] = None,
          event: Optional[Event] = None,
          diff_bounds: bool = False) -> Solution:
    """Integrate ``dz/dt = f(params, z, t)`` and return a :class:`Solution`.

    Time is a first-class axis: ``t1 < t0`` (or a descending ``SaveAt.ts``
    grid) integrates in *reverse time* — the drivers carry the span's sign
    through step clipping and error control, and every gradient method
    replays its signed ``(t_i, h_i)`` step record, so values and gradients
    match the time-reflected forward solve. Only ``t0 == t1`` is rejected.

    Arguments (all axes default to the paper's MALI configuration):

    * ``solver`` — a :class:`~repro.core.solvers.Solver` (or legacy string
      name); defaults to the gradient method's paper pairing.
    * ``controller`` — a :class:`~repro.core.stepsize.StepController`;
      defaults to ``AdaptiveController(rtol=1e-2, atol=1e-3, max_steps=64)``.
    * ``gradient`` — a :class:`~repro.core.interface.GradientMethod`;
      defaults to ``MALI()``.
    * ``saveat`` — a :class:`~repro.core.interface.SaveAt`; defaults to the
      end state ``z(t1)``. With ``SaveAt(ts=grid)``, ``t0``/``t1`` are
      ignored and ``ys`` is the (T, ...) trajectory with ``ys[0] == z0``.
      With ``SaveAt(dense=True)`` the returned solution is callable in
      time: ``Solution.evaluate(t)`` interpolates anywhere in the span off
      per-step cubic-Hermite coefficients.
    * ``event`` — a terminating :class:`~repro.core.interface.Event`:
      integration stops at the first sign change of ``cond_fn(z, t)``
      (bisection-refined on the dense interpolant), ``stats.event_time`` /
      ``stats.event_fired`` record the outcome, and in grid mode the
      post-event rows of ``ys``/``ts`` hold the frozen terminal state.
      Gradients flow through the frozen-``t_event`` path for all four
      methods.
    * ``batching`` — a :class:`~repro.core.interface.Batching`, making the
      leading axis of ``z0`` an explicit batch axis: :class:`Lockstep`
      (one shared controller decision per trial — the implicit semantics
      an unbatched solve applies to a batch-shaped state, made explicit),
      :class:`PerSample` (row-independent adaptive control; fewer total
      f-evals on stiffness-heterogeneous batches), or :class:`Sharded`
      (data-parallel over a mesh axis). Batched ``ys`` is batch-first:
      ``(B, ...)`` end state or ``(B, T, ...)`` trajectory, identical
      across modes, and ``stats`` gains per-sample rows (see
      :class:`Stats`). ``None`` (default) keeps the single-trajectory
      semantics untouched.
    * ``diff_bounds`` — make the integration bounds differentiable: the
      chosen gradient method emits the analytic boundary cotangents
      ``dL/dt_k = <g_k, f(z_k, t_k)>`` (k >= 1) and
      ``dL/dt_0 = -<a(t0), f(z0, t0)>`` for ``t0``/``t1`` (and every
      ``SaveAt.ts`` entry) instead of zeros — the hook FFJORD-style
      trainable end-times (``repro.cnf``) need. Costs one extra batched
      f-sweep over the observation states in the backward. Not available
      with ``SaveAt(steps=True)``/``SaveAt(dense=True)`` (per-step output
      has no fixed observation grid) or ``Sharded`` batching (the grid is
      a closed-over constant inside shard_map).

    The returned :class:`Solution` is a pytree (jit/vmap/grad-safe);
    differentiate any loss of ``sol.ys`` and the chosen gradient method's
    custom VJP applies. Cross-axis compatibility (MALI => ALF, adaptive
    control => embedded error estimate, ACA => Runge-Kutta, per-sample
    batching => rectangular output) is validated eagerly with actionable
    errors.
    """
    gradient = MALI() if gradient is None else gradient
    if not isinstance(gradient, GradientMethod):
        raise TypeError(f"gradient must be a GradientMethod, got {gradient!r}")
    solver = gradient.default_solver() if solver is None else get_solver(solver)
    controller = AdaptiveController() if controller is None else controller
    if not isinstance(controller, StepController):
        raise TypeError(
            f"controller must be a StepController (ConstantSteps or "
            f"AdaptiveController), got {controller!r}")
    saveat = SaveAt() if saveat is None else saveat

    gradient.validate(solver, controller)
    if saveat.ts is None:
        validate_span(t0, t1)

    if diff_bounds:
        if saveat.steps or saveat.dense:
            raise ValueError(
                "diff_bounds=True needs a fixed observation grid; "
                "SaveAt(steps=True)/SaveAt(dense=True) output is indexed by "
                "accepted steps, which carry no boundary cotangents — use "
                "the default end state or SaveAt(ts=grid)")
        if isinstance(batching, Sharded):
            raise ValueError(
                "diff_bounds=True with Sharded() batching is not supported: "
                "the observation grid is a closed-over constant inside "
                "shard_map, so its cotangents cannot cross the mesh axis — "
                "use Lockstep()/PerSample(), or vmap sharded solves with "
                "static bounds")

    if event is not None:
        if not isinstance(event, Event):
            raise TypeError(f"event must be an Event, got {event!r}")
        if batching is not None:
            raise ValueError(
                "event= with batching= is not supported: per-sample event "
                "times are ragged; vmap single event solves, or solve the "
                "batch without an event and post-process")
        return _solve_event(f, params, z0, t0, t1, solver, controller,
                            gradient, saveat, event, diff_bounds)

    if batching is not None:
        if not isinstance(batching, Batching):
            raise TypeError(
                f"batching must be a Batching (Lockstep, PerSample or "
                f"Sharded), got {batching!r}")
        batching.validate(controller, saveat)
        return _solve_batched(f, params, z0, t0, t1, solver, controller,
                              gradient, saveat, batching, diff_bounds)

    if saveat.steps:
        return _solve_dense(f, params, z0, t0, t1, solver, controller,
                            gradient)
    if saveat.dense:
        return _solve_dense_interp(f, params, z0, t0, t1, solver,
                                   controller, gradient)

    trajectory = saveat.ts is not None
    grid = as_time_grid(saveat.ts) if trajectory else scalar_time_grid(t0, t1)
    traj, rstats = gradient.integrate(f, params, z0, grid, solver, controller,
                                      diff_bounds)
    stats = _build_stats(rstats, gradient, z0, grid, solver, controller)
    if trajectory:
        return Solution(ys=traj, ts=grid, stats=stats)
    return Solution(ys=_tm(lambda b: b[-1], traj), ts=grid[-1], stats=stats)


__all__ = ["solve", "Solution", "SaveAt", "Stats", "Event", "GradientMethod",
           "Batching", "Lockstep", "PerSample", "Sharded",
           "MALI", "Naive", "ACA", "Backsolve", "Adjoint", "ALF",
           "AdaptiveController", "state_nbytes"]
