"""DeepSeek-V2-Lite's configuration and its reference module
(``bench/reference/deepseek_v2.py``) against the program, at smoke size
on the CPU and, for names and FLOPs, at the configuration's own sizes."""
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import cell, compare, spec
from bench.reference import deepseek_v2 as reference
from bench.tests.test_bench_reference import tiny_traffic
from repro.configs.base import LayerSpec
from repro.data.synthetic import DataConfig, make_batch
from repro.models import attention as A
from repro.models import init_lm
from repro.models.mla import init_mla, mla_train
from repro.models.moe import apply_moe, init_moe, route_counts
from repro.train.loop import loss_and_grads

BM = spec.benchmark()
CELL = "train-deepseek-v2-lite-ep8-mali-f32w"
CFILE = spec.config(BM, spec.workload(BM, CELL)["config"])


def tiny_config(held: int = 4, first: int = 4, factor: float = 1.0,
                layers: int = 3, experts: int = 16) -> dict:
    """The configuration file at smoke widths: 4 heads of 16 + 8 (q/k)
    and 16 (v) over a 32-wide latent, YaRN over 16 original positions,
    ``held`` of ``experts`` experts (d_ff 32) from ``first``, top-3, 2
    shared, a budget factor ``factor``, float32 throughout."""
    pub = dict(CFILE["published"], hidden_size=64, num_attention_heads=4,
               num_key_value_heads=4, intermediate_size=96, kv_lora_rank=32,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               moe_intermediate_size=32, n_routed_experts=held,
               n_router_experts=experts, first_held_expert=first,
               num_experts_per_tok=3, device_capacity_factor=factor,
               vocab_size=256, num_hidden_layers=layers,
               rope_scaling=dict(CFILE["published"]["rope_scaling"],
                                 original_max_position_embeddings=16))
    return {"name": "deepseek-v2-tiny", "arch": "deepseek-v2-lite",
            "reference": "deepseek_v2", "published": pub,
            "program": {"d_model": 64, "n_heads": 4, "n_kv_heads": 4,
                        "d_head": 24, "vocab_size": 256, "d_ff": 32,
                        "n_periods": layers - 1, "prelude_d_ff": 96,
                        "moe_d_ff": 32, "moe_experts": experts,
                        "moe_experts_held": held, "moe_first_expert": first,
                        "moe_top_k": 3, "moe_capacity_factor": factor,
                        "mla_kv_rank": 32, "mla_nope_dim": 16,
                        "mla_rope_dim": 8, "mla_v_dim": 16,
                        "rope_yarn_original_len": 16,
                        "param_dtype": "float32",
                        "compute_dtype": "float32"}}


def test_the_configuration_names_its_reference():
    assert CFILE["reference"] == "deepseek_v2"
    assert spec.reference(CFILE) is reference
    cfg = cell.model_config(CFILE)
    assert cfg.n_experts_held == 8 and cfg.moe_experts == 64
    assert [l.mixer for l in cfg.layers()] == ["mla"] * 6
    assert [l.mlp for l in cfg.layers()] == ["dense"] + ["moe"] * 5
    m = reference.sizes(CFILE["published"])
    assert reference.budget(2 * 4096, m) == 6144
    assert CFILE["published_values"]["n_routed_experts"] == m.n_experts


def test_every_program_leaf_has_a_reference_name():
    """At the configuration's own sizes (shapes only)."""
    cfg = cell.model_config(CFILE)
    shapes = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg))
    names = set()
    for name, layers, _ in cell._named(shapes, reference):
        names |= ({name} if layers is None else
                  {f"L{i}.{name}" for i in np.ravel(layers)})
    m = reference.sizes(CFILE["published"])
    ref = jax.eval_shape(lambda: reference.init_params(0, m))
    want = {k for k in ref if k != "layers"} | {
        f"L{i}.{k}" for i, lp in enumerate(ref["layers"]) for k in lp}
    assert names == want
    assert len(want) == 3 + 10 + 5 * 14


def test_required_flops_per_token_pinned():
    """MLA 13.76M weights and its core at 192/128, layer 0's 10,944-wide
    MLP, five MoE layers (router 64, shared 2 x 1,408, 0.75 of a held
    expert's rows a token), the 12,800-row head, 18 units a branch."""
    traffic = spec.traffic("train_s4096_b2_mali")
    m = reference.sizes(CFILE["published"])
    assert reference.mla_weights(m) == 13762560
    got = reference.required_flops_per_token(m, reference.job(traffic),
                                             traffic["seq_len"])
    assert got == 6139281408.0


def test_init_draws_the_models_weights():
    cfile = tiny_config()
    cfg = cell.model_config(cfile)
    seed = 2 ** 33 + 7
    prog = init_lm(jax.random.PRNGKey(seed), cfg)
    ref = reference.init_params(seed, reference.sizes(cfile["published"]))
    got, want = reference.leaf_norms(ref), cell.leaf_norms(prog, reference)
    assert set(got) == set(want)
    blocks = prog["blocks"]
    np.testing.assert_array_equal(blocks["prelude"][0]["mlp"]["w_up"],
                                  ref["layers"][0]["w_up"])
    sub = blocks["period"]["sub0"]
    for p in range(2):
        lp = ref["layers"][1 + p]
        np.testing.assert_array_equal(sub["mixer"]["wkv_b"][p], lp["wkv_b"])
        np.testing.assert_array_equal(sub["mlp"]["router"][p], lp["router"])
        np.testing.assert_array_equal(sub["mlp"]["w_down"][p],
                                      lp["experts_down"])
        np.testing.assert_array_equal(sub["mlp"]["shared"]["w_gate"][p],
                                      lp["shared_gate"])


def _mla_inputs(cfile):
    cfg = cell.model_config(cfile)
    m = reference.sizes(cfile["published"])
    params = init_mla(jax.random.PRNGKey(1), cfg)
    lp = {"wq": params["wq"], "wkv_a": params["wkv_a"],
          "kv_norm": params["kv_norm"]["scale"], "wkv_b": params["wkv_b"],
          "wo": params["wo"]}
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 64, cfg.d_model))
    return cfg, m, params, lp, x


@pytest.mark.parametrize("path", ["direct", "flash"])
def test_mla_matches_the_reference(path, monkeypatch):
    """The MLA layer's output and its gradient in every weight and the
    input, the program's attention core on its direct path or on the
    chunked flash ``custom_vjp`` tiled in 16 x 32 blocks."""
    if path == "flash":
        monkeypatch.setattr(A, "_DIRECT_SEQ_LIMIT", 0)
        monkeypatch.setattr(A, "_sdpa_chunked_flash", functools.partial(
            A._sdpa_chunked_flash, block_q=16, block_kv=32))
    cfg, m, params, lp, x = _mla_inputs(tiny_config())
    cot = jax.random.normal(jax.random.PRNGKey(3), x.shape)

    def prog(p, x):
        return jnp.sum(mla_train(p, cfg, LayerSpec(mixer="mla"), x) * cot)

    def ref(p, x):
        with jax.default_matmul_precision("highest"):
            return jnp.sum(reference.mla(p, x, m, None) * cot)

    np.testing.assert_allclose(
        np.asarray(mla_train(params, cfg, LayerSpec(mixer="mla"), x)),
        np.asarray(reference.mla(lp, x, m, None)), rtol=1e-4, atol=1e-5)
    (gp, gx), (rp, rx) = (jax.grad(prog, (0, 1))(params, x),
                          jax.grad(ref, (0, 1))(lp, x))
    gp = dict(gp, kv_norm=gp["kv_norm"]["scale"])
    for k in rp:
        np.testing.assert_allclose(np.asarray(gp[k]), np.asarray(rp[k]),
                                   rtol=1e-4, atol=1e-5, err_msg=k)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx), rtol=1e-4,
                               atol=1e-5)


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight chips' shares of a 64-expert layer, each holding 8 experts
    and computing its part under a budget that does not bind
    (``moe_dropped`` 0), add up, with the shared experts counted once, to
    the uncut reference layer."""
    cfile = tiny_config(held=64, first=0, experts=64, factor=8.0)
    cfg = dataclasses.replace(cell.model_config(cfile), moe_top_k=6)
    m = reference.sizes(dict(cfile["published"], num_experts_per_tok=6))
    whole = init_moe(jax.random.PRNGKey(4), cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, cfg.d_model))
    shared = None
    total = 0.0
    for c in range(8):
        share = dataclasses.replace(cfg, moe_experts_held=8,
                                    moe_first_expert=8 * c)
        p = dict(whole, **{k: whole[k][8 * c:8 * c + 8]
                           for k in ("w_gate", "w_up", "w_down")})
        assert int(route_counts(p, share, x).dropped) == 0
        total = total + apply_moe(p, share, x)
        if shared is None:
            shared = apply_moe(dict(p, w_down=jnp.zeros_like(p["w_down"])),
                               share, x)
    lp = {"router": whole["router"], "experts_gate": whole["w_gate"],
          "experts_up": whole["w_up"], "experts_down": whole["w_down"],
          "shared_gate": whole["shared"]["w_gate"],
          "shared_up": whole["shared"]["w_up"],
          "shared_down": whole["shared"]["w_down"]}
    with jax.default_matmul_precision("highest"):
        want = reference.moe(lp, x, m, None)
    np.testing.assert_allclose(np.asarray(total - 7 * shared),
                               np.asarray(want), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("ode", [True, False], ids=["mali", "discrete"])
def test_step0_loss_and_grads_match_the_program(ode):
    """Step 0's loss and every gradient leaf with the budget binding (4 of
    16 experts held from the 5th, factor 0.5), program vs reference."""
    cfile, traffic = tiny_config(factor=0.5), tiny_traffic(ode)
    tc = cell.trainer_config(cfile, traffic, seed=3)
    cfg = cell.model_config(cfile).with_ode(tc.ode_settings()).validate()
    params = init_lm(jax.random.PRNGKey(3), cfg)
    batch = make_batch(cfg, DataConfig(seed=3, global_batch=2, seq_len=64), 0)
    loss, stats, grads = jax.jit(
        lambda p, b: loss_and_grads(p, b, cfg=cfg))(
        params, {k: jnp.asarray(v) for k, v in batch.items()})
    assert int(stats.moe_dropped) > 0
    m, job = reference.sizes(cfile["published"]), reference.job(traffic)
    r_loss, r_grads = reference.loss_and_grads(
        reference.init_params(3, m), batch["tokens"], batch["labels"], m, job)
    assert abs(float(loss) - r_loss) <= 1e-5 * abs(r_loss)
    gap, at = compare.leaf_gap(cell.leaf_norms(grads, reference),
                               reference.leaf_norms(r_grads),
                               sorted(reference.leaf_norms(r_grads)))
    assert gap <= 1e-4, (gap, at)


def test_trainer_run_agrees_with_the_reference():
    """The harness path over two training steps (MALI), float32."""
    res = cell.run(tiny_config(), tiny_traffic(True),
                   {"loss_rel": 1e-5, "grad_leaf": 1e-2, "change_leaf": 1e-2,
                    "grad_own": 1e-2, "change_own": 1e-2},
                   seed=2 ** 31 + 5, seconds=0.0, t_start=time.perf_counter())
    assert res["correct"], res["checks"]
    assert res["ctx"]["fevals"] == [18]
