"""HLO-text cost model with loop-trip multiplication.

``compiled.cost_analysis()`` counts every while-loop body ONCE and reports
per-device numbers (verified experimentally — see EXPERIMENTS.md §Dry-run).
Scan-over-layers + MALI's backward scan + chunked-loss scans make that a
>20x undercount for this framework, so we parse the post-SPMD HLO text and
account per computation with a symbol table (operand types are not inline
in compiled HLO — they resolve through each computation's definitions):

  flops:
    dot       2 * prod(result_dims) * prod(lhs contracting dim sizes)
    elementwise / transcendental / compare ...   prod(result_dims)
    reduce    prod(operand_dims)
  bytes (HBM-traffic proxy):
    fusion    operand bytes + result bytes of the fusion instruction only
              (internals are register/VMEM-resident — the TPU model)
    other     operand + result bytes
  control flow:
    while     (condition + body) * trip_count, from the while op's
              backend_config known_trip_count (fallback: largest integer
              constant in the condition computation)
    call/conditional/reduce-to_apply: called computations once

Collectives are handled separately in roofline.py (wire-byte multipliers).
Validated against closed forms in tests/test_roofline.py.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Tuple

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "abs",
    "negate", "exponential", "exponential-minus-one", "log", "log-plus-one",
    "tanh", "rsqrt", "sqrt", "power", "compare", "select", "and", "or",
    "not", "xor", "clamp", "floor", "ceil", "round-nearest-afz", "sign",
    "cosine", "sine", "atan2", "erf", "logistic",
    "round-nearest-even", "cbrt", "remainder", "shift-left",
    "shift-right-logical", "shift-right-arithmetic", "is-finite",
}

_FREE_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "opt-barrier",
    # dtype converts: XLA-CPU legalizes EVERY bf16 elementwise op as
    # convert->f32 op->convert, inflating instruction-boundary bytes ~5x on
    # bf16-heavy programs. On the TPU target converts fuse into the
    # producer/consumer (native bf16 VPU ops), so they carry no HBM traffic
    # of their own. Verified against jamba train_4k: 264 converts of a
    # 9.4 GB MoE intermediate in one loop body, all CPU legalization.
    "convert",
}

# type group: tuple types may contain /*index=N*/ comments (with '=') and
# one level of nested parens (tiled layouts); allow both.
_INST_RE = re.compile(
    r"^\s*(?:ROOT\s+)?(?P<name>%[\w.\-]+)\s*=\s*"
    r"(?P<type>\((?:[^()]|\([^()]*\))*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s*"
    r"(?P<op>[a-z][\w\-]*)\(")

_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')


def _dims(dim_str: str) -> List[int]:
    return [int(d) for d in dim_str.split(",") if d]


def _count_elems(type_str: str) -> int:
    total = 0
    for _, dims in _SHAPE_RE.findall(type_str):
        n = 1
        for d in _dims(dims):
            n *= d
        total += n
    return total


def _count_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in _dims(dims):
            n *= d
        total += n * _DTYPE_BYTES[dt]
    return total


def _operand_section(line: str) -> str:
    i = line.find("(")
    if i < 0:
        return ""
    depth = 0
    for j in range(i, len(line)):
        if line[j] == "(":
            depth += 1
        elif line[j] == ")":
            depth -= 1
            if depth == 0:
                return line[i + 1:j]
    return line[i + 1:]


_OPERAND_NAME_RE = re.compile(r"%[\w.\-]+")


def _called(line: str) -> List[Tuple[str, str]]:
    out = []
    for key in ("calls=", "to_apply=", "condition=", "body=",
                "true_computation=", "false_computation="):
        for m in re.finditer(re.escape(key) + r"%?([\w.\-]+)", line):
            out.append((key[:-1], m.group(1)))
    return out


@dataclasses.dataclass
class CompCost:
    flops: float = 0.0
    bytes: float = 0.0


def split_computations(hlo: str) -> Tuple[Dict[str, List[str]], str]:
    comps: Dict[str, List[str]] = {}
    entry = ""
    current = None
    for raw in hlo.splitlines():
        stripped = raw.strip()
        if not stripped or stripped.startswith("//"):
            continue
        if stripped.endswith("{") and "->" in stripped and "=" not in \
                stripped.split("(")[0]:
            m = re.match(r"(ENTRY\s+)?%?([\w.\-]+)", stripped)
            if m:
                current = m.group(2)
                comps[current] = []
                if m.group(1):
                    entry = current
            continue
        if stripped == "}":
            current = None
            continue
        if current is not None:
            comps[current].append(stripped)
    return comps, entry


def analyze_hlo(hlo: str) -> CompCost:
    comps, entry = split_computations(hlo)
    memo: Dict[str, CompCost] = {}

    # symbol tables: computation -> {inst name -> result type str}
    symtabs: Dict[str, Dict[str, str]] = {}
    for cname, lines in comps.items():
        tab = {}
        for line in lines:
            m = _INST_RE.match(line)
            if m:
                tab[m.group("name")] = m.group("type")
        symtabs[cname] = tab

    def operand_bytes(cname: str, line: str) -> int:
        tab = symtabs[cname]
        total = 0
        for nm in _OPERAND_NAME_RE.findall(_operand_section(line)):
            total += _count_bytes(tab.get(nm, ""))
        return total

    def operand_elems(cname: str, line: str) -> int:
        tab = symtabs[cname]
        total = 0
        for nm in _OPERAND_NAME_RE.findall(_operand_section(line)):
            total += _count_elems(tab.get(nm, ""))
        return total

    def dot_flops(cname: str, line: str, rtype: str) -> float:
        tab = symtabs[cname]
        names = _OPERAND_NAME_RE.findall(_operand_section(line))
        if not names:
            return 0.0
        lhs_dims: List[int] = []
        for dt, dims in _SHAPE_RE.findall(tab.get(names[0], "")):
            lhs_dims = _dims(dims)
            break
        m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", line)
        contract = 1
        if m:
            for idx in _dims(m.group(1)):
                if idx < len(lhs_dims):
                    contract *= lhs_dims[idx]
        return 2.0 * _count_elems(rtype) * contract

    def cost_of(name: str, stack=()) -> CompCost:
        if name in memo:
            return memo[name]
        if name in stack or name not in comps:
            return CompCost()
        total = CompCost()
        for line in comps[name]:
            m = _INST_RE.match(line)
            if not m:
                continue
            rtype, op = m.group("type"), m.group("op")
            if op in _FREE_OPS:
                continue
            called = _called(line)

            if op == "fusion":
                for _, sub in called:
                    total.flops += cost_of(sub, stack + (name,)).flops
                total.bytes += operand_bytes(name, line) + _count_bytes(rtype)
                continue
            if op == "while":
                tm = _TRIP_RE.search(line)
                cond = body = None
                for kind, sub in called:
                    if kind == "condition":
                        cond = sub
                    elif kind == "body":
                        body = sub
                if tm:
                    trips = int(tm.group(1))
                else:
                    trips = 1
                    for cl in comps.get(cond, []):
                        for cm in re.finditer(r"constant\((\d+)\)", cl):
                            trips = max(trips, int(cm.group(1)))
                for sub in (cond, body):
                    if sub:
                        c = cost_of(sub, stack + (name,))
                        total.flops += c.flops * trips
                        total.bytes += c.bytes * trips
                continue
            if called:  # call / conditional / reduce / map / sort / scatter
                for _, sub in called:
                    c = cost_of(sub, stack + (name,))
                    total.flops += c.flops
                    total.bytes += c.bytes
                if op in ("reduce", "reduce-window", "scatter"):
                    total.flops += operand_elems(name, line)
                total.bytes += operand_bytes(name, line) + _count_bytes(rtype)
                continue

            if op == "dot":
                total.flops += dot_flops(name, line, rtype)
            elif op in ("convolution",):
                # not used by this framework's models (mamba conv is shifts)
                total.flops += 2.0 * _count_elems(rtype)
            elif op in _ELEMENTWISE:
                total.flops += _count_elems(rtype)
            total.bytes += operand_bytes(name, line) + _count_bytes(rtype)
        memo[name] = total
        return total

    return cost_of(entry)


# ---------------------------------------------------------------------------
# Kernel-launch accounting (jaxpr level)
# ---------------------------------------------------------------------------
# On CPU, interpret-mode pallas_call lowers to plain HLO, so launches are
# invisible in compiled HLO text; the stable place to count them is the
# jaxpr, where each launch is one `pallas_call` primitive regardless of
# target. This is the roofline check that a fused op really IS one launch —
# e.g. one fused MALI backward step must show exactly two (alf_bwd_pre +
# alf_bwd_post, one on each side of the f-eval linearization).

def _sub_jaxprs(params):
    """Yield every sub-jaxpr reachable from one eqn's params (pjit/closed
    jaxprs, scan bodies, cond branches — tuples/lists included)."""
    for val in params.values():
        stack = [val]
        while stack:
            v = stack.pop()
            if hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
                stack.append(v.jaxpr)
            elif hasattr(v, "eqns"):
                yield v
            elif isinstance(v, (tuple, list)):
                stack.extend(v)


def _count_pallas(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
        counts = [_count_pallas(sub) for sub in _sub_jaxprs(eqn.params)]
        if eqn.primitive.name == "cond":
            # one branch runs — e.g. the per-platform twins of every kernel
            # launch (repro.kernels.dispatch: interpreted on CPU, compiled
            # on TPU) are one launch, not two
            n += max(counts, default=0)
        else:
            n += sum(counts)
    return n


def count_pallas_launches(fn, *args) -> int:
    """Number of pallas_call launches in one trace of ``fn(*args)``
    (recursing through pjit/scan/cond sub-jaxprs; scan bodies count ONCE —
    this is launches per traced program region, i.e. per step for a
    per-step function)."""
    import jax  # lazy so the text-only cost model stays jax-free
    return _count_pallas(jax.make_jaxpr(fn)(*args).jaxpr)
