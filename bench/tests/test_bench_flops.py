"""FLOP and byte functions, and the peaks table."""
import pytest

from bench import flops, peaks
from bench.reference import lm as reference

@pytest.mark.parametrize("ode,n,executed,units", [
    (True, 2, False, 18.0), (True, 2, True, 28.0), (False, 2, False, 6.0),
    (False, 2, True, 6.0), (True, 1, False, 12.0), (True, 4, True, 48.0)])
def test_branch_units(ode, n, executed, units):
    assert flops.branch_units(ode, n, executed) == units


def _tiny(tie=False):
    return reference.Sizes(d_model=8, n_heads=2, n_kv_heads=1, d_head=4,
                           d_ff=16, vocab_size=32, n_layers=3, qk_norm=True,
                           rope_theta=1e4, tie_embeddings=tie,
                           param_dtype="float32")


def _job(ode):
    opt = reference.Opt(3e-4, 100, 2000, 0.1, 0.9, 0.95, 1e-8, 0.1, 1.0)
    return reference.Job(ode, 2, 1.0, 1.0, opt)


@pytest.mark.parametrize("ode", [True, False])
def test_required_flops_per_token_by_hand(ode):
    m, s = _tiny(), 64
    attn = 8 * 4 * 2 + 8 * 4 * 1 * 2 + 2 * 4 * 8    # wq, wk+wv, wo
    mlp = 3 * 8 * 16
    evals = 3 if ode else 1
    units = 18 if ode else 6
    scores = evals * 3 * (2 * 2 * 4 * (s / 2) * 2)  # QK^T + PV, causal half
    head = 6 * 8 * 32
    want = 3 * (units * (attn + mlp) + scores) + head
    assert reference.required_flops_per_token(m, _job(ode), s) == want


# ALF kernel calls as a v5e trace names them (shapes and layouts of the
# [2, 4096, 2048] float32 state flattened to [131072, 128]); the bwd_post
# call keeps one operand, the midpoint call its result, in on-chip memory.
ROWS = "f32[131072,128]{1,0:T(8,128)}"
ONCHIP = "f32[131072,128]{1,0:T(8,128)S(1)}"
H = "f32[1,1]{1,0:T(1,128)} %h"
TAIL = (', custom_call_target="tpu_custom_call", operand_layout_constraints='
        '{f32[1,1]{1,0}, f32[131072,128]{1,0}, f32[131072,128]{1,0}}')
BWD_POST = (f"%branch_0_fun.57 = ({ROWS}, {ROWS}, {ROWS}, {ROWS}) "
            f"custom-call({H}, {ROWS} %a, {ROWS} %b, {ROWS} %c, {ROWS} %d, "
            f"{ONCHIP} %e, {ROWS} %f)" + TAIL)
MIDPOINT = (f"%branch_0_fun.52 = {ONCHIP} custom-call({H}, {ROWS} %z, "
            f"{ROWS} %v)" + TAIL)
FUSION = f"%fusion.3 = {ROWS} fusion({ROWS} %a, {ROWS} %b), kind=kLoop"
ARRAY = 131072 * 128 * 4


def test_hlo_call_bytes_from_shapes():
    """Results and operands once each, unpadded; arrays the compiler keeps
    on chip (S(1)) and the attributes' shapes are not HBM traffic."""
    assert flops.hlo_call_bytes(BWD_POST) == 9 * ARRAY + 4
    assert flops.hlo_call_bytes(MIDPOINT) == 2 * ARRAY + 4
    assert flops.hlo_shapes(MIDPOINT)[0] == ("f32", (131072, 128), 1)
    assert flops.hlo_call_bytes("%x = bf16[2,3]{1,0} add(s8[6] %a)") == 18


def test_alf_calls_are_mosaic_calls_over_the_state():
    state = 2 * 4096 * 2048
    assert flops.is_alf_call(BWD_POST, state)
    assert flops.is_alf_call(MIDPOINT, state)
    assert not flops.is_alf_call(MIDPOINT, state // 2)
    assert not flops.is_alf_call(FUSION, state)


def test_peaks_keyed_by_device_kind():
    row = peaks.peaks("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks("TPU v99")
    with pytest.raises(ValueError):
        peaks.peaks("cpu")
