"""The Trainer: resumable, fault-tolerant continuous-depth training.

Composes the whole substrate behind one object::

    from repro.train import Trainer, TrainerConfig
    t = Trainer(TrainerConfig(steps=20, ckpt_dir="/tmp/run1"))
    t.train()
    t.loss_trace()      # per-step losses (records survive restarts)

The model's residual branches are native ``solve()`` calls —
``gradient=MALI(...)`` (or naive/aca/adjoint), ``ALF(backend='pallas')``
when an accelerator is present (``ode_backend='auto'``), and
``Sharded(axis, inner=Lockstep())`` batching over the ambient mesh when
``ode_batch_axis`` names one. The loop driver is a registered
:class:`~repro.train.loop.TrainLoop`; the jitted step is the module-level
value-hash-keyed ``jitted_train_step`` (one trace per distinct config
*value*, not instance).

Resumability: every checkpoint carries ``(params, opt, ef, rng)`` plus the
:func:`~repro.train.state.config_fingerprint` of the integrator/optimizer
settings, and a resume under a different config raises
:class:`~repro.train.state.ConfigMismatchError` instead of silently
continuing a different trajectory. Failures inside the loop restart from
the latest checkpoint via ``run_with_recovery``; because batches are pure
functions of (seed, step) and the step is deterministic, the recomputed
post-checkpoint steps reproduce the uninterrupted run's loss trace
bit-for-bit.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Callable, Dict, Optional

import jax
import numpy as np

from repro.checkpoint.checkpoint import AsyncCheckpointer, list_checkpoints
from repro.configs import ModelConfig, get_config, smoke_config
from repro.core.ode_block import OdeSettings
from repro.data.synthetic import DataConfig, make_batch
from repro.distributed.fault_tolerance import run_with_recovery
from repro.distributed.sharding import (batch_shardings, opt_state_shardings,
                                        param_shardings, replicated)
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import init_lm
from repro.optim.optimizer import OptimizerConfig, OptState, init_opt_state
from repro.train.loop import get_train_loop
from repro.train.metrics import MetricsEmitter, StepRecord, make_emitter
from repro.train.spans import CompileCounter, StepSpans
from repro.train.state import (TrainState, config_fingerprint,
                               restore_train_state, state_tree)

log = logging.getLogger("repro.train")

# The paper's default pairings (GradientMethod.default_solver()).
_SOLVER_FOR = {"mali": "alf", "naive": "alf", "aca": "heun_euler",
               "adjoint": "dopri5"}


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    """Value-hashable run description (frozen: equal values reuse traces)."""
    arch: str = "qwen3-1.7b"
    smoke: bool = True              # reduced config; --full on a real slice
    ode: bool = True                # continuous depth on/off
    ode_steps: int = 2              # 0 = adaptive controller
    ode_method: str = "mali"        # mali | naive | aca | adjoint
    ode_backend: str = "auto"       # auto | reference | pallas
    ode_batch_axis: str = ""        # mesh axis for Sharded() solves; '' = off
    steps: int = 50
    global_batch: int = 8
    seq_len: int = 64
    microbatches: int = 1
    loop: str = "standard"          # TRAIN_LOOPS key
    ckpt_dir: str = ""
    ckpt_every: int = 20
    keep: int = 3
    seed: int = 0
    log_every: int = 10
    emit: str = "stdout"            # EMITTERS key
    metrics_path: str = ""          # for emit='jsonl'
    production_mesh: bool = False   # needs a real multi-chip slice
    multi_pod: bool = False
    max_failures: int = 3

    def ode_settings(self) -> OdeSettings:
        if not self.ode:
            return OdeSettings(mode="off")
        backend = self.ode_backend
        if backend == "auto":
            backend = ("pallas" if jax.default_backend() != "cpu"
                       else "reference")
        return OdeSettings(
            mode="per_block", method=self.ode_method,
            solver=_SOLVER_FOR[self.ode_method], n_steps=self.ode_steps,
            backend=backend, batch_axis=self.ode_batch_axis or None)


def build(tc: TrainerConfig):
    """(model config, mesh, optimizer config) for one run description."""
    ode = tc.ode_settings()
    cfg = (smoke_config(tc.arch, ode) if tc.smoke
           else get_config(tc.arch, ode))
    mesh = (make_production_mesh(multi_pod=tc.multi_pod)
            if tc.production_mesh else make_host_mesh())
    opt_cfg = OptimizerConfig(total_steps=tc.steps,
                              warmup_steps=max(tc.steps // 20, 1))
    return cfg, mesh, opt_cfg


class Trainer:
    """One training run. ``step_hook(step)`` (if given) runs before each
    step on the host, outside the step's spans — the fault-injection
    point for recovery tests.
    ``model_config`` (if given) replaces the model that ``config.arch`` and
    ``config.smoke`` name, e.g. a published config cut in depth to fit one
    chip; ``config``'s ODE settings are applied to it."""

    def __init__(self, config: TrainerConfig,
                 emitter: Optional[MetricsEmitter] = None,
                 step_hook: Optional[Callable[[int], None]] = None,
                 model_config: Optional[ModelConfig] = None):
        self.config = config
        self.cfg, self.mesh, self.opt_cfg = build(config)
        if model_config is not None:
            self.cfg = model_config.with_ode(config.ode_settings()).validate()
        self.loop = get_train_loop(config.loop)
        self.emitter = emitter if emitter is not None else make_emitter(
            config.emit, config.metrics_path)
        self.step_hook = step_hook
        self.records: Dict[int, StepRecord] = {}
        self._state: Optional[TrainState] = None

    @property
    def state(self) -> Optional[TrainState]:
        """Final :class:`TrainState` after :meth:`train` (None before)."""
        return self._state

    def loss_trace(self):
        """Per-step losses in step order. Restarted steps overwrite their
        first attempt, so after a recovery this equals the uninterrupted
        run's trace (the continuity property the tests assert)."""
        return [self.records[s].loss for s in sorted(self.records)]

    def train(self) -> int:
        tc = self.config
        cfg, mesh, opt_cfg = self.cfg, self.mesh, self.opt_cfg
        dcfg = DataConfig(seed=tc.seed, global_batch=tc.global_batch,
                          seq_len=tc.seq_len)
        fingerprint = config_fingerprint(
            cfg, opt_cfg, arch=tc.arch, loop=tc.loop,
            microbatches=tc.microbatches, seed=tc.seed,
            global_batch=tc.global_batch, seq_len=tc.seq_len)
        ckpt = (AsyncCheckpointer(tc.ckpt_dir, keep=tc.keep)
                if tc.ckpt_dir else None)

        with mesh, CompileCounter() as compiles:
            params = init_lm(jax.random.PRNGKey(tc.seed), cfg)
            p_sh = param_shardings(cfg, mesh, params)
            o_sh = OptState(replicated(mesh),
                            *(opt_state_shardings(cfg, mesh, p_sh,
                                                  params),) * 3)
            params = jax.device_put(params, p_sh)
            opt_state = jax.tree_util.tree_map(
                lambda x, s: jax.device_put(x, s),
                init_opt_state(opt_cfg, params),
                OptState(o_sh.step, o_sh.m, o_sh.v, o_sh.master))
            state = TrainState(params, opt_state,
                               self.loop.init_carry(params),
                               jax.random.PRNGKey(tc.seed + 1))
            zero1 = mesh.size > 1
            b_sh = None

            def put_batch(step: int):
                nonlocal b_sh
                batch = {k: jax.numpy.asarray(v)
                         for k, v in make_batch(cfg, dcfg, step).items()}
                if b_sh is None:
                    b_sh = batch_shardings(cfg, mesh, batch)
                return {k: jax.device_put(v, b_sh[k])
                        for k, v in batch.items()}

            def one_step(step: int, state: TrainState,
                         spans: StepSpans) -> TrainState:
                with spans.phase("batch"):
                    batch = put_batch(step)
                with spans.phase("dispatch"):
                    p, o, carry, metrics = self.loop.step(
                        state.params, state.opt, state.ef, batch, cfg=cfg,
                        opt_cfg=opt_cfg, microbatches=tc.microbatches,
                        zero1=zero1)
                with spans.phase("wait"):
                    jax.block_until_ready((p, o))
                with spans.phase("readback"):
                    loss = float(metrics["loss"])
                    if not np.isfinite(loss):
                        raise RuntimeError(f"non-finite loss at step {step}")
                    lr = float(metrics["lr"])
                    grad_norm = float(metrics["grad_norm"])
                    fevals, accepted, rejected, routed, dropped = (
                        int(metrics[k]) for k in (
                            "ode_fevals", "ode_accepted", "ode_rejected",
                            "moe_routed", "moe_dropped"))
                wall_s = spans.wall_s()
                with spans.phase("record"):
                    state = TrainState(p, o, carry,
                                       jax.random.fold_in(state.rng, step))
                    sec = spans.seconds
                    rec = StepRecord(
                        step=step, loss=loss, lr=lr, grad_norm=grad_norm,
                        wall_s=wall_s, batch_s=sec["batch"],
                        dispatch_s=sec["dispatch"], wait_s=sec["wait"],
                        readback_s=sec["readback"],
                        compiles=spans.compiles, fevals=fevals,
                        accepted=accepted, rejected=rejected,
                        moe_routed=routed, moe_dropped=dropped)
                    self.records[step] = rec
                    self.emitter.emit(rec)
                    if step % tc.log_every == 0 or step == tc.steps - 1:
                        log.info("step %d loss %.4f lr %.2e gnorm %.2f "
                                 "fevals %d", step, loss, rec.lr,
                                 rec.grad_norm, rec.fevals)
                if ckpt is not None and (step + 1) % tc.ckpt_every == 0:
                    with spans.phase("ckpt"):
                        ckpt.save(step + 1, state_tree(state),
                                  metadata={**fingerprint, "loss": loss})
                return state

            def train_loop(resume: Optional[int]) -> int:
                nonlocal state
                start = 0
                if resume is not None and ckpt is not None:
                    got = restore_train_state(tc.ckpt_dir, state, fingerprint)
                    if got is not None:
                        start, restored, _meta = got
                        state = TrainState(
                            jax.device_put(restored.params, p_sh),
                            restored.opt, restored.ef, restored.rng)
                        log.info("resumed from step %d", start)
                for step in range(start, tc.steps):
                    if self.step_hook is not None:
                        self.step_hook(step)
                    with StepSpans(step, compiles) as spans:
                        state = one_step(step, state, spans)
                return tc.steps

            def restore_step() -> Optional[int]:
                if ckpt is None:
                    return None
                ckpt.wait()   # a crash may race an in-flight save
                ckpts = list_checkpoints(tc.ckpt_dir)
                return ckpts[-1][0] if ckpts else None

            final, rstats = run_with_recovery(
                train_loop, restore_step, max_failures=tc.max_failures)
            if ckpt is not None:
                ckpt.save(final, state_tree(state),
                          metadata={**fingerprint, "final": True})
                ckpt.close()
            self.emitter.close()
            self._state = state
            log.info("done: step %d (failures=%d)", final, rstats.failures)
            return final
