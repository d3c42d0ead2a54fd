"""Structured per-step training telemetry.

A :class:`StepRecord` is one row of the run's metrics table: optimizer
scalars (loss, lr, grad-norm), the step's host time and the seconds of its
host phases (the ``train.*`` spans of :mod:`repro.train.spans`), the
backend compiles it triggered, the continuous-depth accounting —
dynamics evaluations and accepted/rejected trials from the step's
``solve()`` calls — and the MoE layers' routing counts (threaded out of
the jitted step as ``TrainStats`` aux).

:class:`MetricsEmitter` is the registered sink axis (R004): stdout JSON
lines, a JSONL file, or an in-memory list for tests.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Dict, List, Type


@dataclasses.dataclass(frozen=True)
class StepRecord:
    """One training step's telemetry row (all host scalars)."""
    step: int
    loss: float
    lr: float
    grad_norm: float
    wall_s: float           # host time from the step's start to its record
    batch_s: float          # make the batch and place it on the devices
    dispatch_s: float       # enqueue the jitted step
    wait_s: float           # block until its outputs are ready
    readback_s: float       # read its metrics back to the host
    compiles: int           # backend compiles during the step
    fevals: int             # dynamics evaluations across the step's solves
    accepted: int           # accepted solver trials
    rejected: int           # rejected solver trials
    moe_routed: int         # token assignments to held experts, each MoE
                            # branch routing its entry state once
    moe_dropped: int        # of those, over the device budget

    def as_row(self) -> Dict:
        return dataclasses.asdict(self)


class MetricsEmitter:
    """Base of the metrics-sink axis; registered in :data:`EMITTERS`."""

    name: str = "?"

    def emit(self, record: StepRecord) -> None:
        raise NotImplementedError

    def close(self) -> None:
        """Flush/release the sink (default: nothing to do)."""


class StdoutEmitter(MetricsEmitter):
    """One JSON line per step on stdout."""

    name = "stdout"

    def emit(self, record: StepRecord) -> None:
        print(json.dumps(record.as_row()), flush=True)


class JsonlEmitter(MetricsEmitter):
    """Append-only JSONL file (one row per step)."""

    name = "jsonl"

    def __init__(self, path: str):
        self.path = path
        self._f = open(path, "a")

    def emit(self, record: StepRecord) -> None:
        self._f.write(json.dumps(record.as_row()) + "\n")
        self._f.flush()

    def close(self) -> None:
        if not self._f.closed:
            self._f.close()


class MemoryEmitter(MetricsEmitter):
    """In-memory record list (tests / programmatic consumers)."""

    name = "memory"

    def __init__(self):
        self.records: List[StepRecord] = []

    def emit(self, record: StepRecord) -> None:
        self.records.append(record)


EMITTERS: Dict[str, Type[MetricsEmitter]] = {
    "stdout": StdoutEmitter,
    "jsonl": JsonlEmitter,
    "memory": MemoryEmitter,
}


def make_emitter(name: str, path: str = "") -> MetricsEmitter:
    try:
        cls = EMITTERS[name]
    except KeyError:
        raise ValueError(f"unknown metrics emitter {name!r}; "
                         f"choose from {sorted(EMITTERS)}") from None
    if cls is JsonlEmitter:
        if not path:
            raise ValueError("emitter 'jsonl' needs a file path")
        return cls(path)
    return cls()
