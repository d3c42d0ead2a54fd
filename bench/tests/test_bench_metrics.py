"""The metric readers on a hand-made run context whose answers are known."""
import json
import os

import pytest

from bench import spec
from bench.reference import lm as reference
from bench.tests.test_bench_flops import BWD_POST, FUSION, MIDPOINT

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _ctx(trace=True):
    with open(os.path.join(_BENCH, "configs", "stablelm-1.6b-llama-block-6l-f32w.json")) as f:
        m = reference.sizes(json.load(f)["published"])
    with open(os.path.join(_BENCH, "traffic",
                           "train_s4096_b2_mali.json")) as f:
        job = reference.job(json.load(f))
    state = 2 * 4096 * 2048
    ops = {
        # 2 steps: 32 midpoint calls in 10 ms, 32 bwd_post calls in 50 ms
        MIDPOINT: {"count": 32, "seconds": 0.010},
        BWD_POST: {"count": 32, "seconds": 0.050},
        FUSION: {"count": 100, "seconds": 2.5}}
    ctx = {"chips": 1, "device_kind": "TPU v5 lite", "seq_len": 4096,
           "global_batch": 2, "tokens_per_step": 8192, "window_steps": 2,
           "window_s": 3.0, "setup_s": 40.0, "peak_bytes": 12.5e9,
           "fevals": [48, 48], "reference": reference, "model": m,
           "job": job}
    if trace:
        ctx["trace"] = {"window_s": 3.0, "busy_s": 2.7, "ops": ops}
    return ctx, state


def _read(name, ctx):
    return spec.reader(name)(ctx)


def test_end_to_end_readers():
    ctx, _ = _ctx(trace=False)
    assert _read("train_tokens_per_s", ctx) == pytest.approx(2 * 8192 / 3.0)
    assert _read("peak_hbm_gb", ctx) == pytest.approx(12.5)
    assert _read("setup_s", ctx) == 40.0


def test_per_layer_readers():
    ctx, state = _ctx()
    assert _read("device_idle_share", ctx) == pytest.approx(10.0)
    assert _read("ode_fevals_per_step", ctx) == 48
    assert _read("alf_kernel_ms_per_step", ctx) == pytest.approx(30.0)
    need = 32 * (2 * state * 4 + 4) + 32 * (9 * state * 4 + 4)
    assert _read("alf_kernel_roofline", ctx) == pytest.approx(
        100 * need / 819e9 / 0.060)
    per_token = 7.69e9      # 63.0 TFLOP per 8,192-token step (PERF.md)
    mfu = _read("train_step_mfu", ctx)
    assert mfu == pytest.approx(100 * per_token * 8192 * 2 / 3.0 / 197e12,
                                rel=2e-3)


def test_readers_are_silent_where_there_is_nothing_to_read():
    ctx, _ = _ctx(trace=False)
    for name in ("device_idle_share", "train_step_mfu", "alf_kernel_roofline",
                 "alf_kernel_ms_per_step"):
        assert _read(name, ctx) is None
    ctx, _ = _ctx()
    ctx["job"] = ctx["job"]._replace(ode=False)
    for name in ("ode_fevals_per_step", "alf_kernel_roofline",
                 "alf_kernel_ms_per_step"):
        assert _read(name, ctx) is None


def test_peak_counts_the_region_reserved_for_temporaries():
    from bench import cell
    gb = 10 ** 9
    stats = [{"peak_bytes_in_use": 8 * gb, "peak_bytes_reserved": 6 * gb},
             {"peak_bytes_in_use": 9 * gb, "peak_bytes_reserved": 4 * gb}]
    assert cell.peak_bytes(stats) == 14 * gb
    assert cell.peak_bytes([{"peak_bytes_in_use": 3 * gb}]) == 3 * gb
    assert cell.peak_bytes([None]) is None
