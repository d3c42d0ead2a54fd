"""Host spans of the Trainer's steps.

Each step of :meth:`repro.train.Trainer.train` is one profiler step,
``train.step`` (``jax.profiler.StepTraceAnnotation``, arguments
``step_num`` and ``compiles``), holding one span per host phase:

==================  ====================================================
``train.batch``     build the step's batch and place it on the devices
``train.dispatch``  enqueue the jitted step, up to its return
``train.wait``      block until the step's outputs are ready
``train.readback``  read the step's metrics back to the host
``train.record``    advance the state; build, emit and log the StepRecord
``train.ckpt``      save a checkpoint, on steps that save one
==================  ====================================================

Outside a profiler session a span is inactive and costs about a
microsecond; inside one, the spans lie on the device trace's clock, so a
device idle gap can be put down to the host phase that covers it. The
same phases are timed with ``time.perf_counter`` in every run, for the
step's :class:`~repro.train.metrics.StepRecord`.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, Iterator

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts backend compiles while entered (a ``jax.monitoring``
    listener, registered on entry and removed on exit)."""

    def __init__(self):
        self.count = 0

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.count += 1

    def __enter__(self) -> "CompileCounter":
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)


class StepSpans:
    """The ``train.step`` span of one step and its phases' host seconds::

        with StepSpans(step, counter) as s:
            with s.phase("batch"):
                ...
            s.seconds["batch"], s.compiles, s.wall_s()
    """

    def __init__(self, step: int, counter: CompileCounter):
        self.step = step
        self.counter = counter
        self.seconds: Dict[str, float] = {}
        self._span = jax.profiler.StepTraceAnnotation("train.step",
                                                      step_num=step)

    def __enter__(self) -> "StepSpans":
        self._compiles0 = self.counter.count
        self._t0 = time.perf_counter()
        self._span.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._span.set_metadata(compiles=self.compiles)
        self._span.__exit__(*exc)

    @property
    def compiles(self) -> int:
        """Backend compiles since the step began."""
        return self.counter.count - self._compiles0

    def wall_s(self) -> float:
        """Host seconds since the step began."""
        return time.perf_counter() - self._t0

    @contextlib.contextmanager
    def phase(self, name: str) -> Iterator[None]:
        """The span ``train.<name>``; its seconds add to ``seconds[name]``."""
        t = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("train." + name):
                yield
        finally:
            self.seconds[name] = (self.seconds.get(name, 0.0)
                                  + time.perf_counter() - t)
