"""CPU rehearsal of chip_smoke.py: its phases at smoke size (Pallas kernels
interpreted), and its refusal to run without a TPU."""
import dataclasses
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402
from repro.configs import smoke_config  # noqa: E402


def test_refuses_without_tpu():
    with pytest.raises(SystemExit) as e:
        chip_smoke.require_tpu()
    assert e.value.code not in (0, None)


def test_main_runs_no_phase_on_cpu(capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_depth_cut_keeps_published_widths():
    cfg, reduced = chip_smoke.depth_cut()
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head, cfg.d_ff,
            cfg.vocab_size) == (2048, 16, 8, 128, 6144, 151936)
    assert cfg.param_dtype == cfg.compute_dtype == "bfloat16"
    assert cfg.n_periods == chip_smoke.N_PERIODS
    assert reduced == f"reduced: n_periods 28→{chip_smoke.N_PERIODS}"


def test_train_phase_at_smoke_size():
    cfg = dataclasses.replace(smoke_config("qwen3-1.7b"), n_periods=1)
    t = chip_smoke.train_phase(cfg, global_batch=2, seq_len=16, steps=3)
    assert len(t["losses"]) == 3
    # f32 smoke model: the interpreted kernels match the jnp reference far
    # inside the chip's bf16 tolerances
    assert abs(t["losses"][0] - t["ref_loss0"]) <= 1e-5 * t["ref_loss0"]
    assert abs(t["grad_norm0"] - t["ref_grad_norm0"]) <= \
        1e-4 * t["ref_grad_norm0"]
    # interpreted on CPU: the compiled step holds no Mosaic kernel
    assert t["kernels_in_step"] == 0
    chip_smoke.report_train(t, t["ref_loss0"], t["ref_grad_norm0"],
                            "reference")


def test_serve_phase_at_smoke_size():
    s = chip_smoke.serve_phase(slots=8, chunk_steps=8, n_requests=16,
                               d_state=4, n_check=4)
    assert s["completed"] == s["n_requests"] == 16
    assert s["n_checked"] == 4
    assert s["worst_err_over_tol"] <= 1.0
