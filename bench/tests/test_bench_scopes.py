"""Device time by layer scope and idle time by host phase, on a hand-built
trace whose answers are known, and the HLO modules of a real profile."""
import glob
import os

import jax
import jax.numpy as jnp
import pytest

from bench import scopes, spec
from bench.tracereduce import Event, Line, Plane

MS = 1e6  # ns

NEW = ("attention_ms_per_step", "mlp_ms_per_step", "head_loss_ms_per_step",
       "optimizer_ms_per_step", "mali_backward_ms_per_step",
       "unscoped_device_ms_per_step", "idle_batch_ms_per_step",
       "idle_dispatch_ms_per_step", "idle_readback_ms_per_step")

STEP = "jit(train_step)"
OP_NAMES = {
    "jit_train_step(7)": {
        "fusion.1": f"{STEP}/jvp()/while/body/ode/attention/dot_general",
        "fusion.2": f"{STEP}/transpose(jvp(mlp))/dot_general",
        "fusion.3": (f"{STEP}/transpose(jvp())/ode/mali_backward/"
                     "jvp(attention)/norm/mul"),
        "fusion.4": f"{STEP}/transpose(jvp())/ode/mali_backward/add",
        "copy.5": f"{STEP}/jvp()/while/body/dynamic_update_slice",
        "while.6": f"{STEP}/jvp()/while",
        "alf_bwd_pre.7": (f"{STEP}/ode/mali_backward/jit(alf_bwd_pre)/"
                          "alf_kernel/cond/branch_0_fun/alf_bwd_pre/"
                          "pallas_call"),
    },
    # the same instruction name in another module is another op
    "jit_fold_in(9)": {"fusion.1": "jit(fold_in)/threefry2x32"},
}


def _ev(name, start_ms, dur_ms):
    return Event(name, start_ms * MS, dur_ms * MS)


def _fixture():
    """One chip and a host over a 100 ms window (two 50 ms steps).

    chip: train_step module 0-40 and 50-90, fold_in module 40-42.
          while 0-20 holding fusion.1 0-10 (attention) and fusion.3 10-20
          (norm, under mali_backward); fusion.2 30-40 (mlp); fold_in's
          fusion.1 40-42 (unscoped); fusion.4 50-60 (ode, mali_backward);
          alf_bwd_pre 60-65 (alf_kernel, mali_backward); copy 70-90
          (unscoped). Busy 0-20, 30-42, 50-65, 70-90 = 67 ms; idle 20-30,
          42-50, 65-70, 90-100.
    host: bench.step 0-50, 50-100; train.step 0-48 and 49-100 (48-49
          under none, as the step hook is); train.wait 20-28,
          train.dispatch 44-47, train.readback 65-68, train.record 68-72,
          train.batch 95-99.
    """
    chip = Plane("/device:TPU:0", [
        Line("XLA Modules", [_ev("jit_train_step(7)", 0, 40),
                             _ev("jit_fold_in(9)", 40, 2),
                             _ev("jit_train_step(7)", 50, 40)]),
        Line("XLA Ops", [
            _ev("%while.6 = (s32[]) while(...)", 0, 20),
            _ev("%fusion.1 = f32[8] fusion(...)", 0, 10),
            _ev("%fusion.3 = f32[8] fusion(...)", 10, 10),
            _ev("%fusion.2 = f32[8] fusion(...)", 30, 10),
            _ev("%fusion.1 = u32[2] fusion(...)", 40, 2),
            _ev("%fusion.4 = f32[8] fusion(...)", 50, 10),
            _ev("%alf_bwd_pre.7 = (f32[8,128]) custom-call(...)", 60, 5),
            _ev("%copy.5 = f32[8] copy(...)", 70, 20)])])
    host = Plane("/host:CPU", [Line("python", [
        _ev("bench.step", 0, 50), _ev("bench.step", 50, 50),
        _ev("train.step", 0, 48), _ev("train.step", 49, 51),
        _ev("train.wait", 20, 8), _ev("train.dispatch", 44, 3),
        _ev("train.readback", 65, 3), _ev("train.record", 68, 4),
        _ev("train.batch", 95, 4)])])
    return [chip, host]


def test_scope_of_takes_the_innermost_layer_inside_transforms():
    assert scopes.scope_of(f"{STEP}/transpose(jvp(mlp))/mul") == (
        "mlp", False)
    assert scopes.scope_of(
        f"{STEP}/ode/mali_backward/jvp(attention)/norm/mul") == (
        "norm", True)
    assert scopes.scope_of(f"{STEP}/jvp()/while/body/dynamic_slice") == (
        None, False)
    assert scopes.scope_of("") == (None, False)


def test_reduce_by_scope_sums_to_busy_time():
    r = scopes.reduce(_fixture(), OP_NAMES)
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.067)
    want = {"attention": 0.010, "norm": 0.010, "mlp": 0.010, "ode": 0.010,
            "alf_kernel": 0.005, "unscoped": 0.022}   # copy 20 + fold_in 2
    assert r["scopes"] == pytest.approx(want)
    assert sum(r["scopes"].values()) == pytest.approx(r["busy_s"])
    assert r["mali_backward"] == pytest.approx(0.025)   # 10 + 10 + 5
    assert r["scoped"] and r["host_spans"]


def test_reduce_puts_idle_time_under_the_innermost_train_span():
    r = scopes.reduce(_fixture(), OP_NAMES)
    assert r["idle"] == pytest.approx({
        "train.wait": 0.008,         # 20-28
        "train.step": 0.012,         # 28-30, 42-44, 47-48, 49-50, 90-95,
                                     # 99-100
        "train.dispatch": 0.003,     # 44-47
        "none": 0.001,               # 48-49
        "train.readback": 0.003,     # 65-68
        "train.record": 0.002,       # 68-70
        "train.batch": 0.004})       # 95-99
    assert sum(r["idle"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])


def test_reduce_without_chip_window_or_names():
    chip, host = _fixture()
    assert scopes.reduce([host], OP_NAMES) == {}
    r = scopes.reduce([chip, host], {})
    assert not r["scoped"]
    assert r["scopes"] == pytest.approx({"unscoped": r["busy_s"]})


def _ctx(reduction, trace=True):
    ctx = {"window_steps": 2, "trace": {"busy_s": 1.0} if trace else None}
    scopes._CACHE["out"] = reduction
    return ctx


def test_readers_per_step():
    r = scopes.reduce(_fixture(), OP_NAMES)
    ctx = _ctx(r)
    try:
        got = {n: spec.reader(n)(ctx) for n in NEW}
    finally:
        scopes._CACHE.clear()
    assert got == pytest.approx({
        "attention_ms_per_step": 5.0, "mlp_ms_per_step": 5.0,
        "head_loss_ms_per_step": 0.0, "optimizer_ms_per_step": 0.0,
        "mali_backward_ms_per_step": 12.5,
        "unscoped_device_ms_per_step": 11.0,
        "idle_batch_ms_per_step": 2.0, "idle_dispatch_ms_per_step": 1.5,
        "idle_readback_ms_per_step": 2.5})


def test_readers_are_silent_without_a_trace_or_the_program_s_names():
    r = scopes.reduce(_fixture(), OP_NAMES)
    try:
        assert all(spec.reader(n)(_ctx(r, trace=False)) is None
                   for n in NEW)
        # a program that names no scope and has no train.* span
        bare = scopes.reduce(_fixture()[:1] + [Plane("/host:CPU", [
            Line("python", [_ev("bench.step", 0, 100)])])], {})
        assert all(spec.reader(n)(_ctx(bare)) is None for n in NEW)
    finally:
        scopes._CACHE.clear()


def test_hlo_modules_of_a_profile(tmp_path):
    """The profile's metadata plane holds each module's optimized HLO,
    whose instructions keep the named scopes."""
    @jax.jit
    def f(x):
        with jax.named_scope("attention"):
            y = jnp.tanh(x @ x)
        with jax.named_scope("mlp"):
            return jnp.sin(y @ x).sum()

    x = jnp.ones((64, 64))
    jax.block_until_ready(jax.grad(f)(x))
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(jax.grad(f)(x))
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    with open(path, "rb") as fh:
        modules = scopes.hlo_modules(fh.read())
    names = {k: scopes.op_names(v) for k, v in modules.items()}
    found = {scopes.scope_of(op)[0] for k, v in names.items()
             if k.startswith("jit_f(") for op in v.values()}
    assert {"attention", "mlp"} <= found


HLO = '''HloModule jit_train_step, is_scheduled=true

%fused_computation.3 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  %constant.2 = f32[] constant(0), metadata={op_name="jit(train_step)/ode/mali_backward/while"}
  %broadcast.4 = f32[8]{0} broadcast(%constant.2), dimensions={}
  ROOT %select.5 = f32[8]{0} add(%param_0.1, %broadcast.4)
}

%body.20 (arg.21: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg.21 = (s32[], f32[8]{0}) parameter(0)
  %get-tuple-element.22 = f32[8]{0} get-tuple-element(%arg.21), index=1
  %copy-start.23 = (f32[8]{0}, f32[8]{0:S(1)}, u32[]) copy-start(%get-tuple-element.22)
  %copy-done.24 = f32[8]{0:S(1)} copy-done(%copy-start.23)
  %exp.25 = f32[8]{0} exponential(%copy-done.24), metadata={op_name="jit(train_step)/jvp()/while/body/attention/while/body/exp"}
  %i.26 = s32[] constant(1), metadata={op_name="jit(train_step)/jvp()/while/body/attention/while/body/add"}
  ROOT %tuple.27 = (s32[], f32[8]{0}) tuple(%i.26, %exp.25)
}

ENTRY %main.9 (p.1: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0), metadata={op_name="params[\'mlp\']"}
  %fusion.6 = f32[8]{0:S(1)} fusion(%p.1), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(train_step)/transpose(jvp(attention))/mul"}
  %copy-start.7 = (f32[8]{0}, f32[8]{0:S(1)}, u32[]) copy-start(%fusion.6)
  %copy-done.8 = f32[8]{0} copy-done(%copy-start.7)
  %fusion.10 = f32[8]{0} fusion(%copy-done.8), kind=kLoop, calls=%fused_computation.3
  %convert.11 = f32[8]{0} convert(%p.1)
  %alf_update.13 = (f32[8,128]{1,0}, f32[8,128]{1,0}) custom-call(%p.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/ode/alf_kernel/alf_update/pallas_call"}
  %pallas_call.14 = f32[8,128]{1,0} get-tuple-element(%alf_update.13), index=0, metadata={op_name="jit(train_step)/ode/alf_kernel/alf_update/pallas_call"}
  %copy.15 = f32[8,128]{1,0:S(1)} copy(%pallas_call.14)
  ROOT %broadcast.12 = f32[8]{0} broadcast(%constant.13), dimensions={}
}
'''


def test_op_names_give_compiler_made_instructions_an_owner():
    names = scopes.op_names(HLO)
    assert scopes.scope_of(names["fusion.6"])[0] == "attention"
    # a copy between memory spaces moves its operand's data
    assert names["copy-done.8"] == names["copy-start.7"] == names["fusion.6"]
    # a fusion without metadata takes its fused computation's
    assert scopes.scope_of(names["fusion.10"]) == ("ode", True)
    # inside a layer's loop body, the body's layer
    assert scopes.scope_of(names["copy-done.24"])[0] == "attention"
    # an argument's path names no layer; a kernel's time is its call
    assert scopes.scope_of(names["convert.11"]) == (None, False)
    assert scopes.scope_of(names["alf_update.13"])[0] == "alf_kernel"
    assert scopes.scope_of(names["copy.15"])[0] is None
    assert scopes.scope_of(names["broadcast.12"])[0] is None
