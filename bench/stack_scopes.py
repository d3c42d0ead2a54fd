"""Device time under a scope word anywhere in an op's name stack, in a
traced run: the MLA and MoE layers' scopes, which ``bench/scopes.py``'s
fixed ``LAYER_SCOPES`` does not name.

The program names them with ``jax.named_scope``: ``mla`` over a
multi-head latent attention call (``models/mla.py``), ``moe`` over a MoE
layer (``models/moe.py``) and, inside it, ``moe_dispatch`` (routing's
sort, the gathers and the weighted scatter back) and ``moe_experts`` (the
grouped products). An op counts under each of these words that its
``op_name`` holds, whatever other scope lies inside it: ``mla`` takes the
latent's norm, ``moe`` the router, dispatch, experts and the shared
experts' MLP. XLA compiles ``jax.lax.ragged_dot`` to Mosaic kernels
(``ragged-dot-none.<n>``, and ``ragged-dot-metadata.<n>`` for the group
offsets) whose instructions carry none of the program's scopes; the
program calls ``ragged_dot`` for the MoE experts alone, so those kernels
count under ``moe`` and ``moe_experts`` by their name (``KERNELS``).
The reading is ``bench/scopes.py``'s: each op's self time in the
``bench.step`` window, per chip, averaged over chips, its ``op_name``
looked up in the module its ``XLA Modules`` event names.

``python3 -m bench.stack_scopes <dir>`` prints the seconds of the newest
trace under ``<dir>``.
"""
from __future__ import annotations

import glob
import json
import os
import sys
from typing import Dict, Optional, Sequence, Tuple

from bench import scopes, tracereduce
from bench.tracereduce import Plane

WORDS = ("mla", "moe", "moe_dispatch", "moe_experts")
KERNELS = {"ragged-dot": {"moe", "moe_experts"}}   # name prefix -> words


def words_of(op_name: str) -> set:
    """The words of an ``op_name``, an argument's path left out."""
    return set(scopes._TOKEN.findall(scopes._INDEX.sub("", op_name)))


def reduce(planes: Sequence[Plane], modules: Dict[str, Dict[str, str]],
           words: Sequence[str] = WORDS,
           window: Optional[Tuple[float, float]] = None) -> Dict[str, float]:
    """Seconds under each of ``words`` inside ``window`` (default: the
    ``bench.step`` spans); a word under which no op ran is left out."""
    window = window or tracereduce.span_window(
        tracereduce.host_spans(planes))
    chips = [p for p in planes if tracereduce.DEVICE_PLANE.match(p.name)]
    if window is None or not chips:
        return {}
    lo, hi = window
    out: Dict[str, float] = {}
    for chip in chips:
        ops = [e for ln in chip.lines if ln.name == tracereduce.OPS_LINE
               for e in ln.events if e.end_ns > lo and e.start_ns < hi]
        module_at = scopes._module_of([e for ln in chip.lines
                                       if ln.name == scopes.MODULES_LINE
                                       for e in ln.events])
        for e, own in zip(ops, tracereduce.self_times(ops)):
            if not lo <= e.start_ns < hi:
                continue
            inst = scopes._INSTRUCTION.match(e.name).group(1)
            names = modules.get(module_at(e.start_ns), {})
            found = words_of(names.get(inst, "")).union(*(
                ws for prefix, ws in KERNELS.items()
                if inst.startswith(prefix)))
            for w in found.intersection(words):
                out[w] = out.get(w, 0.0) + own / 1e9 / len(chips)
    return out


def load(trace_dir: str) -> Dict[str, float]:
    """The reduction of the newest ``.xplane.pb`` under ``trace_dir``."""
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return {}
    with open(files[-1], "rb") as f:
        raw = f.read()
    modules = {k: scopes.op_names(v)
               for k, v in scopes.hlo_modules(raw).items()}
    return reduce(tracereduce.load(os.path.dirname(files[-1])), modules)


_CACHE: Dict[str, Dict[str, float]] = {}


def traced(ctx) -> Dict[str, float]:
    """The reduction of a traced run's trace (the newest under
    ``bench/out/``), loaded once per process; empty for an untraced run."""
    if not ctx.get("trace") or not ctx.get("window_steps"):
        return {}
    if "out" not in _CACHE:
        _CACHE["out"] = load(scopes.OUT)
    return _CACHE["out"]


def seconds_per_step(ctx, word: str) -> Optional[float]:
    """Device seconds per step under ``word``; None where no op ran under
    it (a program without the scope)."""
    r = traced(ctx)
    if word not in r:
        return None
    return r[word] / ctx["window_steps"]


def ms_per_step(ctx, word: str) -> Optional[float]:
    sec = seconds_per_step(ctx, word)
    return None if sec is None else 1e3 * sec


if __name__ == "__main__":
    json.dump(load(sys.argv[1] if len(sys.argv) > 1 else scopes.OUT),
              sys.stdout, indent=1, sort_keys=True)
    print()
