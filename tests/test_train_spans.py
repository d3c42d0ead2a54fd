"""The Trainer's host spans and phase timings under the profiler, and the
layer scopes its compiled step carries in every instruction's op_name."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.data.synthetic import DataConfig, make_batch
from repro.models import init_lm
from repro.optim.optimizer import init_opt_state
from repro.train import Trainer, TrainerConfig
from repro.train.loop import jitted_train_step

STEPS = 3
PHASES = ("train.batch", "train.dispatch", "train.wait", "train.readback",
          "train.record")
LAYER_SCOPES = {"embed", "attention", "mlp", "norm", "ode", "alf_kernel",
                "head_loss", "optimizer"}


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """A smoke run (shapes of its own, so its first step compiles) traced
    by the profiler, with a checkpoint after step 1: (trainer, host
    events)."""
    trace = str(tmp_path_factory.mktemp("trace"))
    t = Trainer(TrainerConfig(steps=STEPS, global_batch=2, seq_len=24,
                              ckpt_dir=str(tmp_path_factory.mktemp("ckpt")),
                              ckpt_every=2, log_every=100, emit="memory"))
    jax.profiler.start_trace(trace)
    try:
        t.train()
    finally:
        jax.profiler.stop_trace()
    path = max(glob.glob(os.path.join(trace, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    data = jax.profiler.ProfileData.from_file(path)
    events = [e for p in data.planes for ln in p.lines for e in ln.events
              if e.name.startswith("train.")]
    return t, events


def test_each_step_is_one_profiler_step_holding_its_phases(profiled):
    _, events = profiled
    steps = sorted((e for e in events if e.name == "train.step"),
                   key=lambda e: e.start_ns)
    assert [dict(e.stats)["step_num"] for e in steps] == list(range(STEPS))
    for e in steps:
        inside = sorted((p for p in events if p.name != "train.step"
                         and e.start_ns <= p.start_ns
                         and p.end_ns <= e.end_ns),
                        key=lambda p: p.start_ns)
        names = [p.name for p in inside]
        assert names[:len(PHASES)] == list(PHASES)
        assert names[len(PHASES):] == (
            ["train.ckpt"] if dict(e.stats)["step_num"] == 1 else [])
    assert sum(e.name.startswith("train.") and e.name != "train.step"
               for e in events) == STEPS * len(PHASES) + 1


def test_step_span_counts_the_compiles(profiled):
    t, events = profiled
    steps = sorted((e for e in events if e.name == "train.step"),
                   key=lambda e: e.start_ns)
    got = [dict(e.stats)["compiles"] for e in steps]
    assert got == [t.records[s].compiles for s in range(STEPS)]


def test_step_records_time_the_host_phases(profiled):
    t, _ = profiled
    recs = [t.records[s] for s in range(STEPS)]
    for r in recs:
        phases = (r.batch_s, r.dispatch_s, r.wait_s, r.readback_s)
        assert all(x >= 0 for x in phases)
        assert sum(phases) <= r.wall_s
    assert recs[0].compiles >= 1
    assert [r.compiles for r in recs[1:]] == [0] * (STEPS - 1)


def _scopes(op_name):
    words = re.findall(r"[A-Za-z0-9_\-]+", op_name)
    return [w for w in words if w in LAYER_SCOPES], "mali_backward" in words


def test_compiled_step_names_every_layer():
    """Forward, transpose and MALI's backward keep the layer scopes, and
    every matmul of the step falls under one."""
    t = Trainer(TrainerConfig(steps=2, global_batch=2, seq_len=16,
                              ode_backend="pallas", emit="memory"))
    cfg, opt_cfg = t.cfg, t.opt_cfg
    params = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg))
    opt = jax.eval_shape(lambda p: init_opt_state(opt_cfg, p), params)
    batch = {k: jnp.asarray(v) for k, v in make_batch(
        cfg, DataConfig(seed=0, global_batch=2, seq_len=16), 0).items()}
    text = jitted_train_step.lower(params, opt, None, batch, cfg=cfg,
                                   opt_cfg=opt_cfg).compile().as_text()
    seen, mali, matmuls = set(), False, 0
    for line in text.splitlines():
        m = re.search(r'op_name="((?:[^"\\]|\\.)*)"', line)
        if not m:
            continue
        layers, in_mali = _scopes(m.group(1))
        seen.update(layers)
        mali = mali or in_mali
        if re.search(r"= \S+ (dot|convolution)\(", line):
            matmuls += 1
            assert layers, line
    assert seen == LAYER_SCOPES
    assert mali and matmuls
