"""Device time by the program's layer scopes, and device idle time by the
Trainer's host phases, in a traced run.

The program names its layers with ``jax.named_scope`` (``embed``,
``attention``, ``mlp``, ``norm``, ``ode``, ``alf_kernel``, ``head_loss``,
``optimizer``; the phase ``mali_backward`` around MALI's backward sweep)
and the host phases of each step with ``train.*`` spans
(``repro.train.spans``). XLA keeps the name stack of every instruction in
its ``metadata={op_name=...}``; the profile holds each executed module's
optimized HLO in its ``/host:metadata`` plane (a serialized ``HloProto``
per module), and names each op event on a chip's ``XLA Ops`` line by its
HLO instruction. So an op's scope is looked up in the module that the
enclosing ``XLA Modules`` event names.

Within the ``bench.step`` window (``tracereduce.span_window``), per chip,
averaged over chips, in seconds:

* ``scopes``: each op's self time (``tracereduce.self_times``) under the
  innermost layer scope of its ``op_name``; a scope wrapped in transform
  names, as ``transpose(jvp(mlp))``, counts as that scope; an instruction
  the compiler made is given an ``op_name`` (``op_names``). ``unscoped``
  takes the ops under none. ``mali_backward`` is the self time of the ops
  under that phase, whatever their layer, so it overlaps the scopes.
* ``idle``: the time in which a chip runs nothing, cut at the ``train.*``
  span boundaries, each piece under the innermost span that covers it
  (``none`` under no ``train.*`` span).

``python3 -m bench.scopes <dir>`` prints the numbers of the newest trace
under ``<dir>``.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from bench import tracereduce
from bench.tracereduce import Event, Plane

LAYER_SCOPES = ("embed", "attention", "mlp", "norm", "ode", "alf_kernel",
                "head_loss", "optimizer")
PHASE_SCOPE = "mali_backward"
HOST_PREFIX = "train."
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "out")

_LINE = re.compile(r"^\s*(ROOT )?%([^\s=]+) = ")
_META = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_KERNEL = 'custom_call_target="tpu_custom_call"'
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
_TOKEN = re.compile(r"[A-Za-z0-9_\-]+")
_INDEX = re.compile(r"\[[^\]]*\]")   # an argument's path: params['mlp']
_INSTRUCTION = re.compile(r"^%?([^\s=]+)")


# -- the profile's HLO modules (protobuf wire format, the fields needed) --

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return x, i


def _fields(buf: bytes, lo: int = 0, hi: Optional[int] = None
            ) -> Iterator[Tuple[int, object]]:
    """(field number, value) of a message: an int, or a (start, end) span
    of ``buf`` for a length-delimited field."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 1:
            v, i = None, i + 8
        elif kind == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif kind == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"protobuf wire type {kind} at {i}")
        yield key >> 3, v


def _string(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def hlo_modules(raw: bytes) -> Dict[str, str]:
    """HLO text of each module in a serialized ``XSpace``, by its name in
    the profile (``jit_train_step(<id>)``): the ``Hlo Proto`` stats of the
    ``/host:metadata`` plane's event metadata."""
    from jax._src.lib import xla_client
    out: Dict[str, str] = {}
    for f, plane in _fields(raw):
        if f != 1:                               # XSpace.planes
            continue
        fields = list(_fields(raw, *plane))
        name = next((_string(raw, v) for g, v in fields if g == 2), "")
        if name != METADATA_PLANE:
            continue
        stat_names = {}
        for g, entry in fields:                  # XPlane.stat_metadata
            if g == 5:
                meta = dict(_fields(raw, *entry)).get(2)
                if meta:
                    sm = dict(_fields(raw, *meta))
                    stat_names[sm.get(1)] = _string(raw, sm.get(2, (0, 0)))
        for g, entry in fields:                  # XPlane.event_metadata
            if g != 4:
                continue
            meta = dict(_fields(raw, *entry)).get(2)
            if not meta:
                continue
            em = list(_fields(raw, *meta))
            mname = next((_string(raw, v) for h, v in em if h == 2), "")
            for h, stat in em:
                if h != 5:                       # XEventMetadata.stats
                    continue
                st = dict(_fields(raw, *stat))
                if stat_names.get(st.get(1)) != "Hlo Proto" or 6 not in st:
                    continue
                proto = dict(_fields(raw, *st[6])).get(1)  # .hlo_module
                if proto:
                    module = xla_client._xla.HloModule.\
                        from_serialized_hlo_module_proto(
                            raw[proto[0]:proto[1]])
                    out[mname] = module.to_string()
    return out


def op_names(hlo_text: str) -> Dict[str, str]:
    """Each instruction's ``op_name`` in an HLO module's text.

    An instruction the compiler made carries none: a copy between memory
    spaces, an asynchronous slice, a zero fill, a fusion of such. It takes
    the ``op_name`` of its fused computation (the root's, else the last one
    there), else of the data it moves: its first operand, followed back to
    a named instruction, but not into a Mosaic kernel, whose time is its
    call alone. Else it takes the name stack that the named instructions
    of its own computation share (a loop body's).
    """
    own: Dict[str, str] = {}
    first: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    home: Dict[str, str] = {}
    inner: Dict[str, List[str]] = {}
    roots: Dict[str, str] = {}
    kernels = set()
    comp = ""
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _LINE.match(line)
        if not m:
            continue
        name, rest = m.group(2), line[m.end():]
        home[name] = comp
        if m.group(1):
            roots[comp] = name
        meta = _META.search(line)
        if meta:
            own[name] = meta.group(1)
            inner.setdefault(comp, []).append(meta.group(1))
        operand = _OPERAND.search(rest)
        if operand:
            first[name] = operand.group(1)
        called = _CALLS.search(rest)
        if called:
            calls[name] = called.group(1)
        if _KERNEL in rest or (" get-tuple-element(" in rest
                               and first.get(name) in kernels):
            kernels.add(name)
    shared = {c: "/".join(os.path.commonprefix([n.split("/") for n in v]))
              for c, v in inner.items()}

    def owner(name: str) -> str:
        cur, seen = name, set()
        while cur not in own and cur not in seen:
            seen.add(cur)
            fused = calls.get(cur)
            if fused in inner:
                return own.get(roots.get(fused), inner[fused][-1])
            if first.get(cur) in kernels:
                break
            cur = first.get(cur, cur)
        return own.get(cur) or shared.get(home[name], "")

    return {name: owner(name) for name in home}


def scope_of(op_name: str) -> Tuple[Optional[str], bool]:
    """(innermost layer scope or None, under ``mali_backward``) of an
    ``op_name``: its words, transform names and all, in order, leaving
    out the path of an argument (``params['mlp']['w_up']``)."""
    words = _TOKEN.findall(_INDEX.sub("", op_name))
    layer = None
    for w in words:
        if w in LAYER_SCOPES:
            layer = w
    return layer, PHASE_SCOPE in words


# -- the reduction --

def _module_of(events: Sequence[Event]):
    """A function from a time to the name of the module event covering it
    (None outside every module)."""
    mods = sorted(events, key=lambda e: e.start_ns)
    starts = [e.start_ns for e in mods]

    def find(t: float) -> Optional[str]:
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t < mods[i].end_ns:
            return mods[i].name
        return None
    return find


def _labelled(spans: Sequence[Event], lo: float, hi: float
              ) -> List[Tuple[float, float, str]]:
    """[lo, hi) cut at the spans' boundaries, each piece named by the
    innermost (shortest) span covering it, or "none"."""
    cuts = sorted({lo, hi} | {t for s in spans for t in (s.start_ns, s.end_ns)
                              if lo < t < hi})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        cover = [s for s in spans if s.start_ns <= mid < s.end_ns]
        name = min(cover, key=lambda s: s.dur_ns).name if cover else "none"
        out.append((a, b, name))
    return out


def reduce(planes: Sequence[Plane], modules: Dict[str, Dict[str, str]],
           window: Optional[Tuple[float, float]] = None) -> Dict:
    """Seconds by scope and idle seconds by host phase inside ``window``
    (default: the ``bench.step`` spans). ``modules`` maps a module's name
    in the profile to its instructions' ``op_name``s."""
    window = window or tracereduce.span_window(
        tracereduce.host_spans(planes))
    chips = [p for p in planes if tracereduce.DEVICE_PLANE.match(p.name)]
    if window is None or not chips:
        return {}
    lo, hi = window
    host = [e for p in planes if not tracereduce.DEVICE_PLANE.match(p.name)
            for ln in p.lines for e in ln.events
            if e.name.startswith(HOST_PREFIX)]
    pieces = _labelled(host, lo, hi)
    n = len(chips)
    scopes: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    busy = mali = 0.0
    found = False
    for chip in chips:
        ops = [e for ln in chip.lines if ln.name == tracereduce.OPS_LINE
               for e in ln.events if e.end_ns > lo and e.start_ns < hi]
        module_at = _module_of([e for ln in chip.lines
                                if ln.name == MODULES_LINE
                                for e in ln.events])
        for e, own in zip(ops, tracereduce.self_times(ops)):
            if not lo <= e.start_ns < hi:
                continue
            inst = _INSTRUCTION.match(e.name).group(1)
            names = modules.get(module_at(e.start_ns), {})
            layer, in_mali = scope_of(names.get(inst, ""))
            found = found or layer is not None
            key = layer or "unscoped"
            scopes[key] = scopes.get(key, 0.0) + own / 1e9 / n
            if in_mali:
                mali += own / 1e9 / n
        union = tracereduce.merge(tracereduce.clip(
            [(e.start_ns, e.end_ns) for e in ops], lo, hi))
        busy += tracereduce.total(union) / 1e9 / n
        j = 0
        for s, t in tracereduce.subtract([(lo, hi)], union):
            while pieces[j][1] <= s:
                j += 1
            k = j
            while k < len(pieces) and pieces[k][0] < t:
                a, b, name = pieces[k]
                idle[name] = idle.get(name, 0.0) + (
                    min(b, t) - max(a, s)) / 1e9 / n
                k += 1
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy,
            "scoped": found, "host_spans": bool(host), "scopes": scopes,
            "mali_backward": mali, "idle": idle}


def load(trace_dir: str) -> Dict:
    """The reduction of the newest ``.xplane.pb`` under ``trace_dir``."""
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return {}
    with open(files[-1], "rb") as f:
        raw = f.read()
    modules = {k: op_names(v) for k, v in hlo_modules(raw).items()}
    return reduce(tracereduce.load(os.path.dirname(files[-1])), modules)


_CACHE: Dict[str, Dict] = {}


def traced(ctx) -> Dict:
    """The reduction of a traced run's trace (the newest under
    ``bench/out/``), loaded once per process; empty for an untraced run."""
    if not ctx.get("trace") or not ctx.get("window_steps"):
        return {}
    if "out" not in _CACHE:
        _CACHE["out"] = load(OUT)
    return _CACHE["out"]


def scope_ms_per_step(ctx, scope: Optional[str]) -> Optional[float]:
    """Device ms per step under a layer scope, ``mali_backward``, or no
    layer scope (``None``); None where the program names no scope."""
    r = traced(ctx)
    if not r.get("scoped"):
        return None
    if scope == PHASE_SCOPE:
        sec = r["mali_backward"]
    else:
        sec = r["scopes"].get(scope or "unscoped", 0.0)
    return 1e3 * sec / ctx["window_steps"]


def idle_ms_per_step(ctx, *phases: str) -> Optional[float]:
    """Device idle ms per step under the ``train.<phase>`` spans; None
    where the program has no ``train.*`` span."""
    r = traced(ctx)
    if not r.get("host_spans"):
        return None
    sec = sum(r["idle"].get(HOST_PREFIX + p, 0.0) for p in phases)
    return 1e3 * sec / ctx["window_steps"]


if __name__ == "__main__":
    json.dump(load(sys.argv[1] if len(sys.argv) > 1 else OUT), sys.stdout,
              indent=1, sort_keys=True)
    print()
