"""BENCHMARK.json against the files the harness finds by name, and the
refusal to run without a chip."""
import os
import re
import subprocess
import sys

import pytest

from bench import cell, spec
from bench.reference import lm as reference

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
BM = spec.benchmark()


def test_names_and_keys():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in BM["configs"]]
             + [w["name"] for w in BM["workloads"]]
             + [m["name"] for m in BM["end_to_end"] + BM["per_layer"]])
    assert all(NAME.match(n) for n in names)
    assert len(set(c["name"] for c in BM["configs"])) == len(BM["configs"])
    assert len(set(w["name"] for w in BM["workloads"])) == len(
        BM["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in BM["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert "setup_s" in [m["name"] for m in BM["end_to_end"]]


@pytest.mark.parametrize("w", BM["workloads"], ids=lambda w: w["name"])
def test_every_cell_finds_its_files(w):
    cfile = spec.config(BM, w["config"])
    traffic = spec.traffic(w["traffic"])
    limits = spec.limits(w["name"])
    assert set(limits) == {"loss_rel", "grad_leaf", "change_leaf",
                           "grad_own", "change_own"}
    cfg = cell.model_config(cfile)            # agrees with the file
    assert cfg.n_layers == cfile["published"]["num_hidden_layers"]
    for k in cfile["reduced"]:
        assert cfile["published"][k] != cfile["published_values"][k]
    reference.job(traffic)
    e2e = spec.metrics(BM, w["name"], trace=False)
    per_layer = spec.metrics(BM, w["name"], trace=True)
    assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
    assert per_layer
    for m in e2e + per_layer:
        assert callable(spec.reader(m["name"]))


def test_per_layer_metrics_move_a_reported_metric():
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    for m in BM["per_layer"]:
        moved = e2e[m["moves"]]
        for cell_name in m.get("workloads", []):
            assert cell_name in moved.get("workloads", [cell_name])


def test_run_refuses_a_machine_without_a_tpu():
    root = os.path.dirname(spec.BENCH)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    w = BM["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, os.path.join(root, *BM["command"][1:]),
         "--workload", w, "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not p.stdout.strip()


CONFIG_FILES = sorted(n[:-5] for n in os.listdir(
    os.path.join(spec.BENCH, "configs")) if n.endswith(".json"))


@pytest.mark.parametrize("name", CONFIG_FILES)
def test_every_configuration_file_is_the_program_s_model(name):
    """Also the configurations no cell runs now, kept for their cells'
    return: the program builds each as its file states it."""
    cfile = spec.config(BM, name)
    assert cfile["name"] == name
    cfg = cell.model_config(cfile)
    assert cfg.n_layers == cfile["published"]["num_hidden_layers"]
    assert set(cfile["reduced"]) == set(cfile["published_values"])
