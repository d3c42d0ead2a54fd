"""Run one benchmark cell once on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

``<cell>`` is a workload of ``BENCHMARK.json``. The run trains the cell's
model through ``repro.train.Trainer.train``: set-up (weights from the
seed, the first steps, which compile), then the measured window of at
least ``--seconds``, then, with the program's state freed, the plain
float32 reference over the first steps and the comparison that decides
``correct``. The program and the reference run on the cell's chips and
no others. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` a ``breakdown`` of the traced window, and
last ``checks``: each compared number beside its limit. The same
numbers end standard error.

Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero before it prints a result. JAX's compilation cache lives in
``$JAX_COMPILATION_CACHE_DIR`` if that is set, else in ``.jax_cache`` at
the checkout's root; traces go to ``bench/out/`` there.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs under /tmp

from bench import spec  # noqa: E402


def enable_cache() -> None:
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chips(chips: int):
    """The devices the cell runs on; exits non-zero without a TPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU; JAX found "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips; JAX found "
                         f"{len(devs)}")
    return devs[:chips]


def metric_values(bm, cell: str, trace: bool, ctx) -> dict:
    out = {}
    for m in spec.metrics(bm, cell, trace):
        value = spec.reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result_line(res: dict, bm, cell: str, trace: bool, devices) -> dict:
    ctx = res["ctx"]
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": ctx["peak_bytes"]}
    line = {"correct": res["correct"], "attempted": ctx["steps_run"],
            "failed": 0, "metrics": metric_values(bm, cell, trace, ctx),
            "device": device}
    if trace:
        t = ctx.get("trace") or {}
        device.update(busy_s=t.get("busy_s"), window_s=t.get("window_s"))
        line["breakdown"] = {"device_ops": t.get("top_ops", []),
                             "idle_gaps": t.get("idle_gaps", [])}
    line["checks"] = {c["name"]: {"value": _num(c["value"]),
                                  "limit": c["limit"]}
                      for c in res["checks"]}
    return line


def _num(x: float):
    return x if math.isfinite(x) else str(x)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    bm = spec.benchmark()
    w = spec.workload(bm, a.workload)
    cfile, traffic = spec.config(bm, w["config"]), spec.traffic(w["traffic"])
    limits = spec.limits(a.workload)
    devices = require_chips(int(w["chips"]))
    enable_cache()
    from bench import cell
    trace_dir = (os.path.join(_HERE, "out", a.workload, "trace")
                 if a.trace else None)
    res = cell.run(cfile, traffic, limits, a.seed, a.seconds,
                   t_start=T_START, trace_dir=trace_dir, devices=devices)
    line = result_line(res, bm, a.workload, bool(a.trace), devices)
    ctx = res["ctx"]
    print(f"window: {ctx['window_steps']} steps in {ctx['window_s']!r} s; "
          f"set-up {ctx['setup_s']!r} s (+{ctx['capture_s']!r} s "
          f"observing); reference {ctx['reference_s']!r} s; compiles in "
          f"window {ctx['compiles_in_window']}", file=sys.stderr)
    print(f"window step times (s): {ctx['step_s']}", file=sys.stderr)
    gcs = ctx["gc_pauses"]
    print(f"window host CPU per step (s): {ctx['step_cpu_s']}; Python "
          f"collections: {len(gcs)} taking {sum(p[1] for p in gcs)!r} s, "
          f"over 10 ms [generation, s]: {[p for p in gcs if p[1] > 0.01]}",
          file=sys.stderr)
    print(f"memory_stats after the window: {ctx['memory_stats']}",
          file=sys.stderr)
    print(f"losses: program {res['prog']['loss']} reference "
          f"{res['ref']['loss']}", file=sys.stderr)
    for c in res["checks"]:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r}, "
              f"at {c['at']})", file=sys.stderr)
    print(f"correct: {res['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
