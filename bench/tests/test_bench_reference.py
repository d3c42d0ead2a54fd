"""The plain float32 reference against the program at smoke size."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import cell, compare
from bench.reference import lm as reference
from repro.configs import smoke_config
from repro.data.synthetic import DataConfig, make_batch
from repro.models import init_lm
from repro.train.loop import loss_and_grads

ARCHS = {"stablelm-1.6b": False, "qwen3-1.7b": True}   # arch -> tied head


def tiny_config(arch: str, dtype: str = "float32",
                compute: str = "") -> dict:
    """A configuration file's content at smoke size (the same fields a
    benchmark configuration carries): weights in ``dtype``, activations
    in ``compute`` (default: the same)."""
    c = smoke_config(arch)
    tie = ARCHS[arch]
    return {
        "name": f"{arch}-tiny", "arch": arch,
        "program": {"d_model": c.d_model, "n_heads": c.n_heads,
                    "n_kv_heads": c.n_kv_heads, "d_head": c.d_head,
                    "d_ff": c.d_ff, "vocab_size": c.vocab_size,
                    "n_periods": 2, "tie_embeddings": tie,
                    "param_dtype": dtype,
                    "compute_dtype": compute or dtype},
        "published": {"hidden_size": c.d_model,
                      "num_attention_heads": c.n_heads,
                      "num_key_value_heads": c.n_kv_heads,
                      "head_dim": c.d_head, "intermediate_size": c.d_ff,
                      "vocab_size": c.vocab_size, "num_hidden_layers": 2,
                      "rope_theta": c.rope_theta, "qk_norm": c.qk_norm,
                      "tie_word_embeddings": tie, "torch_dtype": dtype}}


def tiny_traffic(ode: bool, backend: str = "reference") -> dict:
    return {"global_batch": 2, "seq_len": 64,
            "ode": ({"on": True, "method": "mali", "backend": backend,
                     "n_steps": 2, "t1": 1.0, "eta": 1.0} if ode
                    else {"on": False}),
            "optimizer": {"peak_lr": 3e-4, "warmup_steps": 100,
                          "total_steps": 2000, "min_lr_ratio": 0.1,
                          "b1": 0.9, "b2": 0.95, "eps": 1e-8,
                          "weight_decay": 0.1, "clip_norm": 1.0},
            "warmup_steps": 2, "reference_steps": 2}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_init_draws_the_models_weights(arch, dtype):
    cfile = tiny_config(arch, dtype)
    cfg = cell.model_config(cfile)
    seed = 2 ** 33 + 7
    prog = init_lm(jax.random.PRNGKey(seed), cfg)
    ref = reference.init_params(seed, reference.sizes(cfile["published"]))
    want = cell.leaf_norms(prog, reference)
    got = reference.leaf_norms(ref)
    assert set(got) == set(want)
    assert np.array_equal(np.asarray(prog["embed"], np.float32),
                          np.asarray(ref["embed"]))
    wq = prog["blocks"]["period"]["sub0"]["mixer"]["wq"]
    for i in range(2):
        assert np.array_equal(np.asarray(wq[i], np.float32),
                              np.asarray(ref["layers"][i]["wq"]))


@pytest.mark.parametrize("ode", [True, False], ids=["mali", "discrete"])
@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_step0_loss_and_grads_match_the_program(arch, ode):
    """Unclipped step-0 loss and every gradient leaf, program vs reference,
    in float32 (the program's MALI backward against direct
    differentiation of the unrolled ALF)."""
    cfile, traffic = tiny_config(arch), tiny_traffic(ode)
    tc = cell.trainer_config(cfile, traffic, seed=3)
    cfg = cell.model_config(cfile).with_ode(tc.ode_settings()).validate()
    seed = 3
    params = init_lm(jax.random.PRNGKey(seed), cfg)
    batch = make_batch(cfg, DataConfig(seed=seed, global_batch=2,
                                       seq_len=64), 0)
    loss, _, grads = jax.jit(lambda p, b: loss_and_grads(p, b, cfg=cfg))(
        params, {k: jnp.asarray(v) for k, v in batch.items()})
    m, job = reference.sizes(cfile["published"]), reference.job(traffic)
    r_loss, r_grads = reference.loss_and_grads(
        reference.init_params(seed, m), batch["tokens"], batch["labels"],
        m, job)
    assert abs(float(loss) - r_loss) <= 1e-5 * abs(r_loss)
    g_prog = cell.leaf_norms(grads, reference)
    g_ref = reference.leaf_norms(r_grads)
    gap, at = compare.leaf_gap(g_prog, g_ref, sorted(g_ref))
    assert gap <= 1e-4, (gap, at)
    diff = reference.leaf_norms(_tree_diff(grads, r_grads))
    worst = max(diff[k] / max(g_ref[k], 1e-30) for k in diff
                if g_ref[k] > 1e-6)
    assert worst <= 1e-3


def _tree_diff(prog, ref):
    """The program's gradient minus the reference's, in reference layout."""
    out = {"embed": prog["embed"] - ref["embed"],
           "final_norm": prog["final_norm"]["scale"] - ref["final_norm"],
           "layers": []}
    if "head" in ref:
        out["head"] = prog["head"] - ref["head"]
    sub = prog["blocks"]["period"]["sub0"]
    for i, lr in enumerate(ref["layers"]):
        d = {}
        for (k, name) in reference._LAYER_LEAF.items():
            if name in lr:
                leaf = sub[k[0]]
                for part in k[1:]:
                    leaf = leaf[part]
                d[name] = leaf[i] - lr[name]
        out["layers"].append(d)
    return out


@pytest.mark.parametrize("ode", [True, False], ids=["mali", "discrete"])
@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_trainer_run_agrees_with_the_reference(arch, ode):
    """The harness path: a Trainer run observed over its first steps, then
    the reference over the same batches; float32 at smoke size, so the
    gaps are those of float32 round-off and of the bf16 Adam moments."""
    cfile, traffic = tiny_config(arch), tiny_traffic(ode)
    res = cell.run(cfile, traffic, {"loss_rel": 1e-5, "grad_leaf": 1e-2,
                                    "change_leaf": 1e-2, "grad_own": 1e-2,
                                    "change_own": 1e-2},
                   seed=2 ** 31 + 5, seconds=0.0, t_start=time.perf_counter())
    assert res["correct"], res["checks"]
    assert res["ctx"]["window_steps"] == 1
    assert res["ctx"]["fevals"] == ([12] if ode else [0])
