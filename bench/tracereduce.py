"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A trace is a list of planes (``/device:TPU:<n>`` for a chip, ``/host:CPU``
for the host threads), each a list of lines of events with a start and a
duration in nanoseconds on one clock. On a chip plane the ``XLA Ops`` line
holds one event per executed operation. The benchmark's own host spans
(``TraceAnnotation`` named ``bench.*``) lie on the host plane.

* busy: the union of a chip's operation intervals inside the window;
  the idle share is one minus busy over the window, averaged over chips;
* operations: per name (on a chip, the HLO instruction with its shapes),
  how often they started in the window and their self time (seconds
  summed, averaged over chips; a ``while`` op's body ops nest inside it
  on the same line and are its children);
* exposed collectives: the part of each collective's interval in which
  no other operation runs on that chip;
* idle gaps: the intervals inside the window in which a chip runs
  nothing, each labelled with the innermost ``bench.*`` host span that
  covers its midpoint ("none" outside every span).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all",
    re.IGNORECASE)
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Line:
    name: str
    events: List[Event]


@dataclasses.dataclass
class Plane:
    name: str
    lines: List[Line]


def load(trace_dir: str) -> List[Plane]:
    """Planes of the newest ``.xplane.pb`` under ``trace_dir``."""
    import jax
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    return [Plane(p.name, [Line(ln.name, [
        Event(e.name, e.start_ns, e.duration_ns) for e in ln.events])
        for ln in p.lines]) for p in data.planes]


def merge(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> List[Tuple[float, float]]:
    """Parts of the disjoint sorted intervals ``a`` not covered by ``b``."""
    out, j = [], 0
    b = merge(b)
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def device_ops(planes: Sequence[Plane]) -> Dict[str, List[Event]]:
    """Operation events of each chip plane, by plane name."""
    out = {}
    for p in planes:
        if DEVICE_PLANE.match(p.name):
            out[p.name] = [e for ln in p.lines if ln.name == OPS_LINE
                           for e in ln.events]
    return out


def host_spans(planes: Sequence[Plane]) -> List[Event]:
    return [e for p in planes if not DEVICE_PLANE.match(p.name)
            for ln in p.lines for e in ln.events
            if e.name.startswith(SPAN_PREFIX)]


def span_window(spans: Sequence[Event], name: str = "bench.step"
                ) -> Optional[Tuple[float, float]]:
    """From the first start to the last end of the spans named ``name``."""
    steps = [s for s in spans if s.name == name]
    if not steps:
        return None
    return min(s.start_ns for s in steps), max(s.end_ns for s in steps)


def label(t: float, spans: Sequence[Event]) -> str:
    """Name of the innermost (shortest) span covering time ``t``."""
    cover = [s for s in spans if s.start_ns <= t < s.end_ns]
    return min(cover, key=lambda s: s.dur_ns).name if cover else "none"


def self_times(events: Sequence[Event]) -> List[float]:
    """Each event's duration less that of the events nested in it (a
    ``while`` op holds its body's ops on the same line); an event that
    only overlaps another, as an asynchronous collective may, is not
    nested in it."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i].start_ns, -events[i].dur_ns))
    own = [e.dur_ns for e in events]
    stack: List[int] = []
    for i in order:
        e = events[i]
        while stack and events[stack[-1]].end_ns < e.end_ns:
            stack.pop()
        if stack:
            own[stack[-1]] -= e.dur_ns
        stack.append(i)
    return own


_SHORT = re.compile(r"^%?(\S+) = (\([^)]*\)|\S+) ([\w\-]+)\(")


def short_name(instruction: str) -> str:
    """``name opcode result`` of an HLO instruction, without layouts."""
    text = re.sub(r"\{[^{}]*\}", "", instruction)
    m = _SHORT.match(text)
    return (f"{m.group(1)} {m.group(3)} {m.group(2)}" if m
            else text)[:160]


def reduce(planes: Sequence[Plane],
           window: Optional[Tuple[float, float]] = None,
           top: int = 10) -> Dict:
    """The trace's numbers inside ``window`` (default: the ``bench.step``
    spans). Times in seconds; per-chip sums averaged over the chips."""
    spans = host_spans(planes)
    window = window or span_window(spans)
    chips = device_ops(planes)
    if window is None or not chips:
        return {}
    lo, hi = window
    n = len(chips)
    busy, exposed = 0.0, 0.0
    ops: Dict[str, List[float]] = {}
    gaps: List[Tuple[str, float]] = []
    for events in chips.values():
        inside = [e for e in events if e.end_ns > lo and e.start_ns < hi]
        union = merge(clip([(e.start_ns, e.end_ns) for e in inside], lo, hi))
        busy += total(union)
        for e, own in zip(inside, self_times(inside)):
            if lo <= e.start_ns < hi:
                rec = ops.setdefault(e.name, [0, 0.0])
                rec[0] += 1
                rec[1] += own / 1e9
        coll = [e for e in inside if COLLECTIVE.search(e.name)]
        rest = [(e.start_ns, e.end_ns) for e in inside
                if not COLLECTIVE.search(e.name)]
        exposed += total(subtract(
            merge(clip([(e.start_ns, e.end_ns) for e in coll], lo, hi)),
            rest))
        for s, e in subtract([(lo, hi)], union):
            gaps.append((label((s + e) / 2, spans), (e - s) / 1e9))
    op_list = sorted(((k, v[0] / n, v[1] / n) for k, v in ops.items()),
                     key=lambda r: -r[2])
    by_label: Dict[str, float] = {}
    for name, sec in gaps:
        by_label[name] = by_label.get(name, 0.0) + sec / n
    return {
        "chips": n,
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / n / 1e9,
        "collective_exposed_s": exposed / n / 1e9,
        "ops": {k: {"count": c, "seconds": s} for k, c, s in op_list},
        "top_ops": [[short_name(k), s] for k, _, s in op_list[:top]],
        "idle_gaps": [[name, sec] for name, sec in
                      sorted(gaps, key=lambda g: -g[1])[:top]],
        "idle_by_span": by_label,
    }
