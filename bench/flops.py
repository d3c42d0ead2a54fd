"""Operations and bytes of the training step, computed from shapes.

FLOPs count multiply-adds as two. The *required* count is what the
forward and backward passes need, without recomputation: per token and
residual branch with ``P`` weights, a forward f-eval costs ``2P`` and its
vector-Jacobian product ``4P``; an ODE branch solved by ALF with ``n``
fixed steps makes ``n + 1`` f-evals (``v0 = f(z0)`` and one per step),
so it requires ``6 (n + 1) P`` (18P at n = 2), a discrete branch ``6P``.
What a block adds beside its branches' weights (attention scores, the
head) is its reference module's to count (``required_flops_per_token``
there, ``bench/spec.py``).

The *executed* count of an ODE branch under MALI adds what MALI
recomputes: the backward reconstructs each step by the ALF inverse (one
f-eval) and linearizes it (one f-eval and its VJP), and closes the
``v0`` VJP, ``2(n + 1) + 8n + 6`` units of P (28P at n = 2).

A device trace names each operation by its HLO instruction, shapes and
layouts included. A Mosaic kernel call must move its results and
operands once each, unpadded, through HBM, except those the compiler
placed in on-chip memory (``S(1)`` in the layout): its bytes are read
from those shapes. The ALF kernels are the Mosaic calls that are
elementwise over the flattened ODE state.
"""
from __future__ import annotations

import re
from typing import List, Tuple

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
                "s16": 2, "u16": 2, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "f32": 4, "s64": 8, "u64": 8, "f64": 8}
_SHAPE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES)
                    + r")\[([\d,]*)\](\{[^}]*\})?")
_SPACE = re.compile(r"S\((\d+)\)")
_CALL = re.compile(r'custom_call_target="tpu_custom_call"')


def branch_units(ode: bool, n_steps: int, executed: bool = False) -> float:
    """FLOPs per token of one residual branch, in units of its weights P."""
    if not ode:
        return 6.0
    if executed:
        return 2.0 * (n_steps + 1) + 8.0 * n_steps + 6.0
    return 6.0 * (n_steps + 1)


def evals_per_branch(ode: bool, n_steps: int) -> int:
    """Forward f-evals of one residual branch."""
    return n_steps + 1 if ode else 1


def hlo_shapes(instruction: str) -> List[Tuple[str, Tuple[int, ...], int]]:
    """(dtype, dims, memory space) of the result and operands of one HLO
    instruction as a device trace names it (the text up to its
    attributes). Space 0 is HBM; the compiler marks an array it keeps in
    on-chip memory with ``S(1)`` in its layout."""
    head = re.split(r", (?:custom_call_target|metadata|backend_config)=",
                    instruction)[0]
    out = []
    for d, dims, layout in _SHAPE.findall(head):
        space = _SPACE.search(layout)
        out.append((d, tuple(int(x) for x in dims.split(",") if x),
                    int(space.group(1)) if space else 0))
    return out


def hlo_call_bytes(instruction: str) -> int:
    """HBM bytes one call must move: every result and operand in HBM once,
    unpadded (an array in on-chip memory moves no HBM bytes)."""
    total = 0
    for dtype, dims, space in hlo_shapes(instruction):
        if space:
            continue
        n = 1
        for x in dims:
            n *= x
        total += n * _DTYPE_BYTES[dtype]
    return total


def is_alf_call(instruction: str, state_elems: int) -> bool:
    """A Mosaic kernel call over the flattened ODE state: every array it
    reads or writes is the state as [rows, 128], besides the step size."""
    if not _CALL.search(instruction):
        return False
    arrays = [d for _, d, _ in hlo_shapes(instruction) if d != (1, 1)]
    return bool(arrays) and all(
        len(d) == 2 and d[1] == 128 and d[0] * 128 == state_elems
        for d in arrays)


def alf_calls(ctx) -> List[Tuple[str, float, float]]:
    """(instruction, count, seconds) of the ALF kernel calls in a traced
    run's context (counts and seconds per chip)."""
    state = ctx["global_batch"] * ctx["seq_len"] * ctx["model"].d_model
    state //= ctx["chips"]
    return [(k, v["count"], v["seconds"])
            for k, v in (ctx.get("trace") or {}).get("ops", {}).items()
            if is_alf_call(k, state)]
