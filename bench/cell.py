"""A training cell, driven through ``repro.train.Trainer.train``.

One ``Trainer`` is built from the cell's configuration and traffic files
and trained from the seed. Its first ``warmup_steps`` steps are set-up:
the first compiles, and the first ``reference_steps`` of them are
observed for the comparison. From then on the steps are the measured
window, which ends at the first step boundary at least ``seconds`` after
it opened. The step hook of the ``Trainer`` takes the host timestamps;
the window ends by raising :class:`StopWindow` from it.

The observation wraps the Trainer's train loop (:class:`ObservedLoop`):
it copies the batches and the initial weights to the host before the
first steps, reads the first clipped gradient from the optimizer's first
moment after step 1, and each leaf's change from the f32 master weights
after the last observed step. Its time is left out of ``setup_s``.

The program runs on exactly the cell's chips: the Trainer's mesh is the
program's host mesh over those devices. What the harness knows of the
model's block (its published keys, its leaf names, its FLOPs, its
reference) comes from the reference module the configuration names
(``bench/spec.py``), carried in the run's context as ``reference``.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import os
import shutil
import time
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType

from repro.configs import get_config
from repro.train import Trainer, TrainerConfig
from repro.train.loop import TrainLoop

from bench import compare, spec, tracereduce

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class StopWindow(Exception):
    """Ends the measured window from the step hook (the Trainer retries
    only RuntimeError, OSError and ValueError, so this passes through)."""


def model_config(cfile: Dict):
    """The repo model of a configuration file, checked against the file's
    published keys (the file holds the configuration as it is run) by the
    fields its reference module says they fix."""
    cfg = dataclasses.replace(get_config(cfile["arch"]), **cfile["program"])
    want = spec.reference(cfile).program_fields(cfile["published"])
    got = {f: getattr(cfg, f) for f in want}
    if got != want:
        diff = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise SystemExit(f"bench: {cfile['name']}: the program's model "
                         f"differs from the file (program, file): {diff}")
    return cfg


def trainer_config(cfile: Dict, traffic: Dict, seed: int) -> TrainerConfig:
    ode = traffic["ode"]
    return TrainerConfig(
        arch=cfile["arch"], smoke=False, ode=bool(ode["on"]),
        ode_steps=int(ode.get("n_steps", 2)),
        ode_method=ode.get("method", "mali"),
        ode_backend=ode.get("backend", "pallas"),
        steps=int(traffic["optimizer"]["total_steps"]),
        global_batch=int(traffic["global_batch"]),
        seq_len=int(traffic["seq_len"]), seed=int(seed), max_failures=0,
        emit="memory", log_every=10 ** 9)


def host_mesh(devices) -> jax.sharding.Mesh:
    """The program's host mesh (``repro.launch.mesh.make_host_mesh``: data
    over every device, Auto axes) over exactly ``devices``."""
    return jax.make_mesh((len(devices), 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2, devices=devices)


def build_trainer(cfile: Dict, traffic: Dict, seed: int, devices,
                  step_hook: Optional[Callable[[int], None]] = None
                  ) -> Trainer:
    """The cell's Trainer, its mesh over the cell's ``devices``."""
    trainer = Trainer(trainer_config(cfile, traffic, seed),
                      step_hook=step_hook, model_config=model_config(cfile))
    trainer.mesh = host_mesh(devices)
    return trainer


def check_trainer(trainer: Trainer, traffic: Dict, reference) -> None:
    """The Trainer runs the integrator and optimizer the reference follows."""
    opt = trainer.opt_cfg
    want = traffic["optimizer"]
    got = {k: getattr(opt, k) for k in want}
    ode = trainer.cfg.ode
    job = reference.job(traffic)
    if job.ode:
        got.update(t1=ode.t1, eta=ode.eta, t0=ode.t0, n_steps=ode.n_steps)
        want = dict(want, t1=job.t1, eta=job.eta, t0=0.0,
                    n_steps=job.n_steps)
    if got != want or (ode.mode != "off") != job.ode:
        raise SystemExit(f"bench: the Trainer's settings differ from the "
                         f"traffic file: {got} vs {want}")


def _key(p) -> str:
    return str(getattr(p, "key", getattr(p, "name", getattr(p, "idx", p))))


def _named(tree, reference):
    """(name, layers, leaf) of every leaf of a program parameter-shaped
    tree, named by the configuration's reference module. ``layers`` is
    None outside ``blocks``, the layer's number for a prelude layer's leaf,
    and a tuple of one number per period for a period's leaf (stacked over
    the periods): prelude layers come first, then each period's sublayers
    in order."""
    leaves = [(tuple(_key(p) for p in path), x) for path, x in
              jax.tree_util.tree_flatten_with_path(tree)[0]]
    n_pre = len({k[2] for k, _ in leaves if k[:2] == ("blocks", "prelude")})
    n_sub = len({k[2] for k, _ in leaves if k[:2] == ("blocks", "period")})
    for k, x in leaves:
        if k[:2] == ("blocks", "prelude"):
            layers = int(k[2])
            name = reference.layer_leaf("prelude", layers, k[3:])
        elif k[:2] == ("blocks", "period") and k[2].startswith("sub"):
            j = int(k[2][3:])
            layers = tuple(n_pre + p * n_sub + j for p in range(x.shape[0]))
            name = reference.layer_leaf("period", j, k[3:])
        else:
            layers, name = None, reference.top_leaf(k)
        if name is None:
            raise SystemExit(f"bench: unknown parameter leaf {k}")
        yield name, layers, x


@functools.partial(jax.jit, static_argnames="per_layer")
def _norm(x, per_layer):
    x = x.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(x * x, axis=tuple(range(int(per_layer), x.ndim))))


def _put(out, name, layers, norms, scale=1.0):
    if layers is None:
        out[name] = float(norms) * scale
    else:
        for i, n in zip(np.ravel(layers), np.ravel(norms)):
            out[f"L{i}.{name}"] = float(n) * scale


def leaf_norms(tree, reference, scale: float = 1.0) -> Dict[str, float]:
    """Norm of every leaf of a device tree shaped like the parameters."""
    out: Dict[str, float] = {}
    for name, layers, x in _named(tree, reference):
        _put(out, name, layers, _norm(x, isinstance(layers, tuple)), scale)
    return out


def change_norms(tree, before, reference) -> Dict[str, float]:
    """Norm of every leaf's change from ``before`` (a host copy), worked
    out on the host so that it adds nothing to the chip's peak memory."""
    out: Dict[str, float] = {}
    for (name, layers, x), b in zip(_named(tree, reference),
                                    jax.tree_util.tree_leaves(before)):
        d = np.asarray(x, np.float32) - np.asarray(b, np.float32)
        axes = tuple(range(int(isinstance(layers, tuple)), d.ndim))
        _put(out, name, layers,
             np.sqrt(np.sum(d * d, axis=axes, dtype=np.float64)))
    return out


class ObservedLoop(TrainLoop):
    """The Trainer's train loop, observed during its first steps."""

    def __init__(self, inner: TrainLoop, n_ref: int, b1: float, reference):
        self.inner, self.name = inner, inner.name
        self.n_ref, self.b1, self.reference = n_ref, b1, reference
        self.spans = False
        self.calls, self.capture_s = 0, 0.0
        self.batches: List[Dict] = []
        self.grad: Optional[Dict] = None
        self.change: Optional[Dict] = None
        self._p0 = None

    def init_carry(self, params):
        return self.inner.init_carry(params)

    def step(self, params, opt_state, carry, batch, **kw):
        i = self.calls
        self.calls += 1
        if i < self.n_ref:
            t = time.perf_counter()
            if i == 0:
                self._p0 = jax.device_get(params)
            self.batches.append({k: np.asarray(v) for k, v in batch.items()})
            self.capture_s += time.perf_counter() - t
        if self.spans:
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                out = self.inner.step(params, opt_state, carry, batch, **kw)
        else:
            out = self.inner.step(params, opt_state, carry, batch, **kw)
        if i < self.n_ref:
            t = time.perf_counter()
            opt = out[1]
            if i == 0:   # m after one step is (1 - b1) times the gradient
                self.grad = leaf_norms(opt.m, self.reference,
                                       scale=1.0 / (1.0 - self.b1))
            if i == self.n_ref - 1:
                self.change = change_norms(opt.master, self._p0,
                                           self.reference)
                self._p0 = None
            self.capture_s += time.perf_counter() - t
        return out


class Window:
    """The step hook: set-up ends and the window opens at step ``warm``;
    the window closes at the first step boundary ``seconds`` later."""

    def __init__(self, warm: int, seconds: float,
                 trace_dir: Optional[str] = None):
        self.warm, self.seconds, self.trace_dir = warm, seconds, trace_dir
        self.loop: Optional[ObservedLoop] = None
        self.setup_end = self.start = self.end = None
        self.steps = 0
        self.compiles = 0
        self.gc_pauses: List[List] = []   # [generation, s] in the window
        self.cpu_s: List[float] = []      # process CPU time at each step
        self._gc_t = 0.0
        self._span = None
        self._open = False

    def on_compile(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT and self._open:
            self.compiles += 1

    def on_gc(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._gc_t = time.perf_counter()
        elif self._open:
            self.gc_pauses.append([info["generation"],
                                   time.perf_counter() - self._gc_t])

    def _enter(self, step: int) -> None:
        if self.trace_dir:
            self._span = jax.profiler.TraceAnnotation("bench.step",
                                                      step=step)
            self._span.__enter__()

    def _exit(self) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def hook(self, step: int) -> None:
        now = time.perf_counter()
        if step < self.warm:
            return
        self.cpu_s.append(time.process_time())
        if step == self.warm:
            self.setup_end = now
            if self.trace_dir:
                jax.profiler.start_trace(self.trace_dir)
                self.loop.spans = True
            self._open = True
            self.start = time.perf_counter()
            self._enter(step)
            return
        self._exit()
        if now - self.start >= self.seconds:
            self.close(now, step - self.warm)
            raise StopWindow
        self._enter(step)

    def close(self, now: float, steps: int) -> None:
        self._exit()
        self.end, self.steps, self._open = now, steps, False
        if self.trace_dir:
            jax.profiler.stop_trace()
            self.loop.spans = False


def peak_bytes(stats: List[Optional[Dict]]) -> Optional[int]:
    """The fullest chip's peak: its buffers' peak plus the region the TPU
    runtime reserves for programs' temporaries (not among the buffers);
    None where a chip reports no peak."""
    if not all(s and "peak_bytes_in_use" in s for s in stats):
        return None
    return max(s["peak_bytes_in_use"] + s.get("peak_bytes_reserved", 0)
               for s in stats)


@dataclasses.dataclass
class Observation:
    """What one run of the program gave: the compared numbers and the
    readings the metrics take."""
    prog: Dict
    batches: List[Dict]
    ctx: Dict


def drive(cfile: Dict, traffic: Dict, seed: int, seconds: float, *,
          t_start: float, trace_dir: Optional[str] = None,
          devices: Optional[Sequence] = None,
          fault: Optional[Callable[[TrainLoop], TrainLoop]] = None
          ) -> Observation:
    """Set-up, the window and the observation of one Trainer run on
    ``devices`` (the cell's chips; the first device where not given)."""
    devices = list(devices or jax.local_devices()[:1])
    reference = spec.reference(cfile)
    warm, n_ref = int(traffic["warmup_steps"]), int(
        traffic["reference_steps"])
    if not 1 <= n_ref <= warm:
        raise SystemExit("bench: need 1 <= reference_steps <= warmup_steps")
    if trace_dir and os.path.isdir(trace_dir):
        shutil.rmtree(trace_dir)
    win = Window(warm, seconds, trace_dir)
    trainer = build_trainer(cfile, traffic, seed, devices, win.hook)
    tc = trainer.config
    check_trainer(trainer, traffic, reference)
    inner = fault(trainer.loop) if fault is not None else trainer.loop
    loop = ObservedLoop(inner, n_ref, trainer.opt_cfg.b1, reference)
    trainer.loop = win.loop = loop
    jax.monitoring.register_event_duration_secs_listener(win.on_compile)
    gc.callbacks.append(win.on_gc)
    try:
        trainer.train()
    except StopWindow:
        pass
    else:
        win.close(time.perf_counter(), tc.steps - warm)
    finally:
        jax.monitoring.unregister_event_duration_listener(win.on_compile)
        gc.callbacks.remove(win.on_gc)
    if win.setup_end is None:
        raise SystemExit("bench: the run ended before its window opened")
    stats = [d.memory_stats() for d in devices]
    peak = peak_bytes(stats)
    recs = trainer.records
    window = range(warm, warm + win.steps)
    prog = {"loss": [recs[i].loss for i in range(n_ref)],
            "grad": loop.grad, "change": loop.change}
    ctx = {
        "chips": len(devices), "seed": seed,
        "device_kind": devices[0].device_kind,
        "tokens_per_step": tc.global_batch * tc.seq_len,
        "seq_len": tc.seq_len, "global_batch": tc.global_batch,
        "setup_s": win.setup_end - t_start - loop.capture_s,
        "capture_s": loop.capture_s,
        "window_s": win.end - win.start, "window_steps": win.steps,
        "steps_run": warm + win.steps,
        "compiles_in_window": win.compiles,
        "peak_bytes": peak, "memory_stats": stats[0],
        "fevals": [recs[i].fevals for i in window if i in recs],
        "step_s": [recs[i].wall_s for i in window if i in recs],
        "step_cpu_s": [float(x) for x in np.diff(win.cpu_s)],
        "gc_pauses": win.gc_pauses,
        "reference": reference,
        "model": reference.sizes(cfile["published"]),
        "job": reference.job(traffic),
    }
    batches = loop.batches
    del trainer, loop, recs
    gc.collect()
    if trace_dir:
        ctx["trace"] = tracereduce.reduce(tracereduce.load(trace_dir))
    return Observation(prog, batches, ctx)


def reference_numbers(cfile: Dict, traffic: Dict, seed: int,
                      batches: List[Dict], matmul: Optional[str] = None,
                      devices: Optional[Sequence] = None) -> Dict:
    """The reference's loss per step, first clipped gradient and change
    per leaf, over the observed batches, spread over ``devices``."""
    reference = spec.reference(cfile)
    return reference.run(seed, reference.sizes(cfile["published"]),
                         reference.job(traffic),
                         [(b["tokens"], b["labels"]) for b in batches],
                         matmul, devices)


def run(cfile: Dict, traffic: Dict, limits: Dict, seed: int,
        seconds: float, *, t_start: float, trace_dir: Optional[str] = None,
        devices: Optional[Sequence] = None,
        fault: Optional[Callable[[TrainLoop], TrainLoop]] = None) -> Dict:
    """One run on ``devices``: the window, then (the program's state freed)
    the reference and the comparison."""
    obs = drive(cfile, traffic, seed, seconds, t_start=t_start,
                trace_dir=trace_dir, devices=devices, fault=fault)
    t = time.perf_counter()
    ref = reference_numbers(cfile, traffic, seed, obs.batches,
                            devices=devices)
    obs.ctx["reference_s"] = time.perf_counter() - t
    correct, checks = compare.judge(compare.gaps(obs.prog, ref), limits)
    return {"correct": correct, "checks": checks, "ctx": obs.ctx,
            "prog": obs.prog, "ref": ref}
