"""Benchmark orchestrator — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only toy_gradient_error ...]
                                            [--json [BENCH_core.json]]

Emits ``name,value,derived`` CSV to stdout; with ``--json`` additionally
writes a perf-trajectory artifact (per-bench rows + wall-clock, plus the
run's totals) that CI uploads so bench numbers are comparable across
commits. Roofline numbers come from the dry-run (reports/dryrun/) and are
summarized here if present.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time

from .common import print_rows

BENCHES = ("toy_gradient_error", "memory_cost", "solver_invariance",
           "speed", "damped", "adversarial", "observation_grid",
           "batched_throughput", "event_dense", "serve_load",
           "train_memory", "cnf_bits_dim")


def _dryrun_summary_rows():
    path = os.path.join("reports", "dryrun_final", "summary.jsonl")
    if not os.path.exists(path):
        path = os.path.join("reports", "dryrun", "summary.jsonl")
    if not os.path.exists(path):
        return []
    best = {}
    with open(path) as f:
        for line in f:
            r = json.loads(line)
            if r.get("status") != "ok":
                continue
            key = (r["arch"], r["shape"], r["mesh"])
            best[key] = r  # last write wins (most recent run)
    rows = []
    for (arch, shape, mesh), r in sorted(best.items()):
        roof = r["roofline"]
        t_dom = max(roof["t_compute_s"], roof["t_memory_s"],
                    roof["t_collective_s"])
        frac = roof["t_compute_s"] / t_dom if t_dom else 0.0
        rows.append((f"roofline/{arch}/{shape}/{mesh}/bottleneck_frac",
                     frac, roof["bottleneck"]))
    return rows


def _write_json(path: str, benches, extra_rows, t_start: float,
                failures: int) -> None:
    payload = {
        "schema": "bench_core/v1",
        "generated_unix": time.time(),
        "python": platform.python_version(),
        "platform": platform.platform(),
        "total_wall_s": time.time() - t_start,
        "failures": failures,
        "benches": [
            {
                "bench": name,
                "wall_s": wall,
                "rows": [{"name": n, "value": float(v), "derived": d}
                         for (n, v, d) in rows],
            }
            for (name, wall, rows) in benches
        ],
        "extra_rows": [{"name": n, "value": float(v), "derived": d}
                       for (n, v, d) in extra_rows],
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {path}", file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", nargs="*", default=None,
                    help=f"subset of {BENCHES}")
    ap.add_argument("--json", nargs="?", const="BENCH_core.json",
                    default=None, metavar="PATH",
                    help="also write the perf-trajectory JSON artifact "
                         "(default path: BENCH_core.json)")
    args = ap.parse_args()
    names = args.only or BENCHES

    t_start = time.time()
    print("name,value,derived")
    failures = 0
    bench_results = []
    for name in names:
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        t0 = time.time()
        try:
            rows = mod.run()
        except Exception as e:  # keep the harness going; report at exit
            print(f"{name}/ERROR,nan,{type(e).__name__}: {e}",
                  file=sys.stderr)
            failures += 1
            continue
        wall = time.time() - t0
        print_rows(rows)
        print(f"{name}/wall_s,{wall:.1f},harness")
        bench_results.append((name, wall, list(rows)))
    extra = _dryrun_summary_rows()
    print_rows(extra)
    if args.json:
        _write_json(args.json, bench_results, extra, t_start, failures)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    main()
