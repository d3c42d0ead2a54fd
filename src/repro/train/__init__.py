"""repro.train — the continuous-depth training subsystem.

First-class training on the composable ``solve()`` API: the
:class:`~repro.train.trainer.Trainer` composes the continuous-depth LM
(whose residual branches are native ``solve(..., gradient=MALI(...))``
calls) with a registered :class:`~repro.train.loop.TrainLoop` driver,
resumable checkpoint state (:mod:`repro.train.state` — params, optimizer,
error-feedback, RNG *and* the solver/gradient config fingerprint), fault
recovery (:func:`repro.distributed.fault_tolerance.run_with_recovery`),
and structured telemetry (:mod:`repro.train.metrics`).

``repro.launch.train`` is a thin CLI over this package; see
``src/repro/train/README.md`` for the architecture.
"""
from .loop import (TRAIN_LOOPS, CompressedLoop, StandardLoop, TrainLoop,
                   get_train_loop, loss_and_grads, train_step)
from .metrics import (EMITTERS, JsonlEmitter, MemoryEmitter, MetricsEmitter,
                      StdoutEmitter, StepRecord, make_emitter)
from .state import (ConfigMismatchError, TrainState, config_fingerprint,
                    restore_train_state, state_tree)
from .trainer import Trainer, TrainerConfig

__all__ = [
    "Trainer", "TrainerConfig",
    "TrainLoop", "StandardLoop", "CompressedLoop", "TRAIN_LOOPS",
    "get_train_loop", "loss_and_grads", "train_step",
    "StepRecord", "MetricsEmitter", "StdoutEmitter", "JsonlEmitter",
    "MemoryEmitter", "EMITTERS", "make_emitter",
    "TrainState", "ConfigMismatchError", "config_fingerprint",
    "restore_train_state", "state_tree",
]
