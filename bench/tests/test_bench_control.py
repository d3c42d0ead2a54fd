"""The comparison rejects the lower-precision control and the faults a
training cell can have, and passes a sound run: the rest of a benchmark
run driven on the CPU at smoke size (the look for a chip skipped), with
each cell's own limits, weight dtype, batch and integrator."""
import time

import pytest

from bench import cell, compare, faults, spec
from bench.calibrate import FP8
from bench.tests.test_bench_reference import tiny_config, tiny_traffic

BM = spec.benchmark()
CELLS = [(w["name"], spec.traffic(w["traffic"])) for w in BM["workloads"]]
DTYPE = {w["name"]: spec.config(BM, w["config"])["program"].get(
    "param_dtype", "bfloat16") for w in BM["workloads"]}


def _tiny(name, cell_traffic, backend="pallas"):
    """The cell at smoke widths: its architecture, batch and integrator."""
    cfile = tiny_config("qwen3-1.7b" if "qwen3" in name else
                        "stablelm-1.6b", DTYPE[name], "bfloat16")
    traffic = tiny_traffic(cell_traffic["ode"]["on"], backend)
    traffic["global_batch"] = cell_traffic["global_batch"]
    return cfile, traffic


def _run(name, cell_traffic, fault=None, seed=2 ** 32 + 9):
    cfile, traffic = _tiny(name, cell_traffic)
    return cell.run(cfile, traffic, spec.limits(name), seed, 0.0,
                    t_start=time.perf_counter(), fault=fault)


@pytest.mark.parametrize("name,traffic", CELLS, ids=[c[0] for c in CELLS])
def test_sound_run_is_correct(name, traffic):
    res = _run(name, traffic)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("fault", list(faults.FAULTS.values()),
                         ids=list(faults.FAULTS))
@pytest.mark.parametrize("name,traffic", CELLS, ids=[c[0] for c in CELLS])
def test_faults_are_not_correct(name, traffic, fault):
    res = _run(name, traffic, fault)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("name,traffic", CELLS, ids=[c[0] for c in CELLS])
def test_float8_control_is_not_correct(name, traffic):
    """The reference in float8 matrix products, put in the program's
    place, against the float32 reference."""
    cfile, traffic = _tiny(name, traffic, backend="reference")
    obs = cell.drive(cfile, traffic, 17, 0.0, t_start=time.perf_counter())
    ref = cell.reference_numbers(cfile, traffic, 17, obs.batches)
    ctrl = cell.reference_numbers(cfile, traffic, 17, obs.batches,
                                  matmul=FP8)
    correct, checks = compare.judge(compare.gaps(ctrl, ref),
                                    spec.limits(name))
    assert not correct, checks
