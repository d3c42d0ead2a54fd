"""Readings that the limits of a cell's comparison are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,... \\
        [--control 3] [--half-batch 3] [--frozen-norms 3] [--out FILE]

In one process, for each seed: the program's first steps through the
Trainer (as a benchmark run drives them, with a one-step window), the
float32 reference, and the compared numbers (``program``). On the first
``--control`` seeds also the reference computed with float8 (e4m3)
matrix products put in the program's place (``control``); on the first
``--half-batch`` seeds the reference over half of each batch put in the
program's place (``half_batch``: half the rows, or of a single
sequence's positions, left out, the mean taken over the rest); on the
first ``--frozen-norms`` seeds the program again with the norms' scales
frozen (``frozen_norms``, ``bench/faults.py``). A step that returns its
state unchanged reads 1 on ``change_leaf`` by the comparison's own
measure and needs no run. Each reading is one JSON line, with the worst
own-scale gap of each kind of leaf (``kinds``), to standard output and
to ``--out``. The program and the reference run on the cell's chips,
the reference being the one its configuration names.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench import spec  # noqa: E402
from bench.run import enable_cache, require_chips  # noqa: E402

FP8 = "float8_e4m3fn"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--half-batch", type=int, default=3)
    ap.add_argument("--frozen-norms", type=int, default=3)
    ap.add_argument("--out", default="")
    a = ap.parse_args(argv)
    bm = spec.benchmark()
    w = spec.workload(bm, a.workload)
    cfile, traffic = spec.config(bm, w["config"]), spec.traffic(w["traffic"])
    devices = require_chips(int(w["chips"]))
    enable_cache()
    from bench import cell, compare, faults
    out = open(a.out, "a") if a.out else None

    def emit(kind, seed, numbers, ref, extra=None):
        found = compare.gaps(numbers, ref)
        row = {"cell": w["name"], "kind": kind, "seed": seed,
               **{k: v[0] for k, v in found.items()},
               "at": {k: v[1] for k, v in found.items()},
               "kinds": compare.by_kind(numbers, ref), **(extra or {})}
        print(json.dumps(row), flush=True)
        if out:
            out.write(json.dumps(row) + "\n")
            out.flush()

    for i, seed in enumerate(int(s) for s in a.seeds.split(",")):
        t = time.perf_counter()
        obs = cell.drive(cfile, traffic, seed, 0.0, t_start=t,
                         devices=devices)
        t_ref = time.perf_counter()
        ref = cell.reference_numbers(cfile, traffic, seed, obs.batches,
                                     devices=devices)
        emit("program", seed, obs.prog, ref, {
            "loss_program": obs.prog["loss"], "loss_reference": ref["loss"],
            "program_s": t_ref - t,
            "reference_s": time.perf_counter() - t_ref})
        if i < a.control:
            ctrl = cell.reference_numbers(cfile, traffic, seed, obs.batches,
                                          matmul=FP8, devices=devices)
            emit("control", seed, ctrl, ref, {"loss_control": ctrl["loss"]})
        if i < a.half_batch:
            half = [faults.half_batch(b) for b in obs.batches]
            hb = cell.reference_numbers(cfile, traffic, seed, half,
                                        devices=devices)
            emit("half_batch", seed, hb, ref, {"loss_half": hb["loss"]})
        if i < a.frozen_norms:
            fz = cell.drive(cfile, traffic, seed, 0.0,
                            t_start=time.perf_counter(), devices=devices,
                            fault=faults.FrozenNorms)
            emit("frozen_norms", seed, fz.prog, ref)
    return 0


if __name__ == "__main__":
    sys.exit(main())
