"""Device time under the MLA and MoE scopes (``bench/stack_scopes.py``) and
their metrics, on a hand-built trace whose answers are known."""
import json
import os

import pytest

from bench import spec, stack_scopes
from bench.reference import deepseek_v2 as reference
from bench.tracereduce import Event, Line, Plane

MS = 1e6  # ns
NEW = ("mla_ms_per_step", "moe_ms_per_step", "moe_dispatch_ms_per_step",
       "moe_experts_roofline")
STEP = "jit(train_step)"
OP_NAMES = {"jit_train_step(3)": {
    "fusion.1": f"{STEP}/jvp()/while/body/ode/mla/dot_general",
    "fusion.2": f"{STEP}/ode/mali_backward/jvp(mla)/norm/mul",
    "fusion.3": f"{STEP}/jvp()/ode/moe/dot_general",
    "sort.4": f"{STEP}/jvp()/ode/moe/moe_dispatch/sort",
    "ragged.5": f"{STEP}/transpose(jvp(moe))/moe_experts/ragged_dot",
    "fusion.6": f"{STEP}/jvp()/ode/moe/mlp/dot_general",
    "fusion.7": f"{STEP}/transpose(jvp(mlp))/params['moe']/mul",
    "ragged-dot-none.8": f"{STEP}/transpose(jvp())/ode/mali_backward",
}}


def _ev(name, start_ms, dur_ms):
    return Event(name, start_ms * MS, dur_ms * MS)


def _fixture():
    """One chip over two 50 ms steps: mla 0-10 and its norm 10-14, the
    router 20-22, dispatch 22-25, the experts 30-40 (their 30-35 also
    under nothing else), the shared experts 40-45, a dense MLP whose
    argument path names ``moe`` 50-60 (under no new scope), a grouped
    product's kernel 60-66, named by XLA without the program's scopes."""
    chip = Plane("/device:TPU:0", [
        Line("XLA Modules", [_ev("jit_train_step(3)", 0, 100)]),
        Line("XLA Ops", [
            _ev("%fusion.1 = f32[8] fusion(...)", 0, 10),
            _ev("%fusion.2 = f32[8] fusion(...)", 10, 4),
            _ev("%fusion.3 = f32[8] fusion(...)", 20, 2),
            _ev("%sort.4 = f32[8] sort(...)", 22, 3),
            _ev("%ragged.5 = f32[8] ragged-dot(...)", 30, 10),
            _ev("%fusion.6 = f32[8] fusion(...)", 40, 5),
            _ev("%fusion.7 = f32[8] fusion(...)", 50, 10),
            _ev("%ragged-dot-none.8 = f32[8] custom-call(...)", 60, 6)])])
    host = Plane("/host:CPU", [Line("python", [
        _ev("bench.step", 0, 50), _ev("bench.step", 50, 50)])])
    return [chip, host]


def test_an_op_counts_under_every_new_word_of_its_stack():
    r = stack_scopes.reduce(_fixture(), OP_NAMES)
    assert r == pytest.approx({"mla": 0.014, "moe": 0.026,
                               "moe_dispatch": 0.003, "moe_experts": 0.016})
    # without the modules' names only the kernel counts, by its name
    assert stack_scopes.reduce(_fixture(), {}) == pytest.approx(
        {"moe": 0.006, "moe_experts": 0.006})
    assert stack_scopes.reduce(_fixture()[:1], OP_NAMES) == {}


def _ctx(reduction, trace=True):
    with open(os.path.join(spec.BENCH, "configs",
                           "deepseek-v2-lite-6l-ep8-f32w.json")) as f:
        m = reference.sizes(json.load(f)["published"])
    job = reference.job(spec.traffic("train_s4096_b2_mali"))
    stack_scopes._CACHE["out"] = reduction
    return {"window_steps": 2, "trace": {"busy_s": 1.0} if trace else None,
            "chips": 1, "tokens_per_step": 8192, "device_kind": "TPU v5 lite",
            "reference": reference, "model": m, "job": job}


def test_readers_per_step():
    try:
        got = {n: spec.reader(n)(_ctx({"mla": 0.5, "moe": 0.3,
                                       "moe_dispatch": 0.04,
                                       "moe_experts": 0.1}))
               for n in NEW}
    finally:
        stack_scopes._CACHE.clear()
    # 5 MoE layers x 6,144 rows x 18 units x 3 x 2,048 x 1,408 a step
    need = 5 * 6144 * 18 * 3 * 2048 * 1408
    assert got == pytest.approx({
        "mla_ms_per_step": 250.0, "moe_ms_per_step": 150.0,
        "moe_dispatch_ms_per_step": 20.0,
        "moe_experts_roofline": 100 * need / 0.05 / 197e12})


def test_readers_are_silent_without_a_trace_or_the_program_s_scopes():
    try:
        full = {"mla": 0.5, "moe": 0.3, "moe_dispatch": 0.04,
                "moe_experts": 0.1}
        assert all(spec.reader(n)(_ctx(full, trace=False)) is None
                   for n in NEW)
        stack_scopes._CACHE.clear()
        assert all(spec.reader(n)(_ctx({})) is None for n in NEW)
    finally:
        stack_scopes._CACHE.clear()
