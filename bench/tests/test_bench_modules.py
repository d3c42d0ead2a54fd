"""The architecture-specific part of the harness is the reference module a
configuration names: its leaf names, its FLOP count, its reference."""
import sys
import time

import jax
import numpy as np
import pytest

from bench import cell, spec
from bench.reference import lm
from bench.tests import reference_by_path
from bench.tests.test_bench_reference import tiny_config, tiny_traffic
from repro.configs import smoke_config
from repro.models import init_lm

BM = spec.benchmark()
LIMITS = {"loss_rel": 1e-5, "grad_leaf": 1e-2, "change_leaf": 1e-2,
          "grad_own": 1e-2, "change_own": 1e-2}


def test_naming_lm_is_the_default():
    cfile, traffic = tiny_config("qwen3-1.7b"), tiny_traffic(True)
    named = dict(cfile, reference="lm")
    assert spec.reference(cfile) is spec.reference(named) is lm
    runs = [cell.run(c, traffic, LIMITS, seed=2 ** 31 + 11, seconds=0.0,
                     t_start=time.perf_counter()) for c in (cfile, named)]
    for key in ("prog", "ref", "checks"):
        assert runs[0][key] == runs[1][key]
    assert runs[0]["correct"]


def _smoke_params(arch):
    return init_lm(jax.random.PRNGKey(0), smoke_config(arch))


def _norm(x):
    return float(np.sqrt(np.sum(np.square(np.asarray(x, np.float32)))))


def test_a_module_names_leaves_of_other_blocks(monkeypatch):
    """A leading dense layer and a sparse-expert period (deepseek-moe), and
    a period of eight sublayers (jamba), named by a module of the tests."""
    monkeypatch.setitem(sys.modules, "bench.reference.by_path",
                        reference_by_path)
    ref = spec.reference({"reference": "by_path"})
    moe = _smoke_params("deepseek-moe-16b")
    got = cell.leaf_norms(moe, ref)
    blocks = moe["blocks"]
    assert got["L0.mlp.w_gate"] == pytest.approx(
        _norm(blocks["prelude"][0]["mlp"]["w_gate"]), rel=1e-6)
    for p in range(2):
        sub = blocks["period"]["sub0"]["mlp"]
        assert got[f"L{1 + p}.mlp.router"] == pytest.approx(
            _norm(sub["router"][p]), rel=1e-6)
        assert got[f"L{1 + p}.mlp.shared.w_up"] == pytest.approx(
            _norm(sub["shared"]["w_up"][p]), rel=1e-6)
    assert {"embed", "head", "final_norm"} <= set(got)
    assert len(got) == 3 + len(jax.tree_util.tree_leaves(blocks["prelude"])) \
        + 2 * len(jax.tree_util.tree_leaves(blocks["period"]))
    jamba = _smoke_params("jamba-v0.1-52b")
    got = cell.leaf_norms(jamba, ref)
    a_log = jamba["blocks"]["period"]["sub1"]["mixer"]["A_log"]
    assert got["L9.mixer.A_log"] == pytest.approx(_norm(a_log[1]), rel=1e-6)
    assert max(int(k[1:].split(".")[0]) for k in got if k[0] == "L") == 15


def test_an_unnamed_leaf_stops_the_run():
    with pytest.raises(SystemExit, match="unknown parameter leaf.*router"):
        cell.leaf_norms(_smoke_params("deepseek-moe-16b"), lm)


def test_a_module_without_a_function_is_refused(monkeypatch):
    partial = type(sys)("bench.reference.partial")
    for f in spec.REFERENCE_API:
        if f not in ("run", "head_vjp"):
            setattr(partial, f, getattr(lm, f))
    monkeypatch.setitem(sys.modules, "bench.reference.partial", partial)
    with pytest.raises(SystemExit, match="'partial' lacks run, head_vjp"):
        spec.reference({"reference": "partial"})


# each configuration's FLOPs per token as bench/flops.py counted them
# before the count moved into the reference module
PARENT_FLOPS = {"stablelm-1.6b-llama-block-6l-f32w": 7688159232.0,
                "qwen3-1.7b-7l-f32w": 9265741824.0}


@pytest.mark.parametrize("w", BM["workloads"], ids=lambda w: w["name"])
def test_required_flops_per_token_as_before(w):
    cfile = spec.config(BM, w["config"])
    traffic = spec.traffic(w["traffic"])
    ref = spec.reference(cfile)
    got = ref.required_flops_per_token(ref.sizes(cfile["published"]),
                                       ref.job(traffic), traffic["seq_len"])
    assert got == PARENT_FLOPS[w["config"]]
