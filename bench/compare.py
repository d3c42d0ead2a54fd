"""The comparison that decides a training run's ``correct``.

Five numbers, each against a limit of its own (``bench/limits/<cell>.json``):

* ``loss_rel``: the largest relative gap between the program's loss and
  the reference's over the steps the reference follows;
* ``grad_leaf``: the first gradient as the optimizer gets it (clipped),
  leaf by leaf: the gap between the program's and the reference's norm of
  the leaf, over the larger of the reference's norm of that leaf and of
  the median leaf; the worst leaf counts;
* ``change_leaf``: the same for each leaf's change over those steps;
* ``grad_own``, ``change_own``: the same gaps, each over the reference's
  norm of that leaf alone, so that a small leaf (a norm's scale) that
  gets no gradient or no update reads 1 however small it is.

Leaves whose reference gradient is under a thousandth of the median
leaf's move under Adam by round-off alone: they are left out of
``change_leaf`` and of both ``_own`` numbers. Layer leaves count one per
layer. A leaf that one side lacks fails.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

ZERO_GRAD = 1e-3   # of the median leaf's first-gradient norm


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
              names: Sequence[str], own: bool = False) -> Dict[str, float]:
    """Each leaf's norm gap over ``names``: over the larger of its own and
    the median leaf's reference norm, or with ``own`` over its own."""
    med = 0.0 if own else float(np.median([ref[k] for k in names]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
            for k in names}


def leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
             names: Sequence[str], own: bool = False) -> Tuple[float, str]:
    """Worst per-leaf norm gap over ``names`` and the leaf it is at."""
    if set(prog) != set(ref):
        missing = sorted(set(prog) ^ set(ref))
        return float("inf"), f"leaves differ: {missing[:4]}"
    if not names:
        return float("inf"), "no leaves"
    worst, at = -1.0, ""
    for k, gap in leaf_gaps(prog, ref, names, own).items():
        if not np.isfinite(gap):
            return float("inf"), k
        if gap > worst:
            worst, at = gap, k
    return worst, at


def moving(ref: Dict) -> List[str]:
    """The leaves whose reference gradient is not nought to rounding."""
    names = sorted(ref["grad"])
    med = float(np.median([ref["grad"][k] for k in names]))
    return [k for k in names if ref["grad"][k] >= ZERO_GRAD * med]


def gaps(prog: Dict, ref: Dict) -> Dict[str, Tuple[float, str]]:
    """The compared numbers of one run: ``prog`` and ``ref`` each hold
    ``loss`` (per step), ``grad`` and ``change`` (norm per leaf)."""
    lp, lr = np.asarray(prog["loss"], float), np.asarray(ref["loss"], float)
    if lp.shape != lr.shape or not np.all(np.isfinite(lp)):
        loss = (float("inf"), "losses missing or not finite")
    else:
        rel = np.abs(lp - lr) / np.abs(lr)
        loss = (float(rel.max()), f"step {int(rel.argmax())}")
    move = moving(ref)
    return {"loss_rel": loss,
            "grad_leaf": leaf_gap(prog["grad"], ref["grad"],
                                  sorted(ref["grad"])),
            "change_leaf": leaf_gap(prog["change"], ref["change"], move),
            "grad_own": leaf_gap(prog["grad"], ref["grad"], move, own=True),
            "change_own": leaf_gap(prog["change"], ref["change"], move,
                                   own=True)}


def by_kind(prog: Dict, ref: Dict) -> Dict[str, Dict[str, float]]:
    """The worst own-scale gap of each kind of leaf (``L3.q_norm`` is of
    kind ``q_norm``), for the gradient and the change."""
    out: Dict[str, Dict[str, float]] = {}
    move = moving(ref)
    for what in ("grad", "change"):
        if set(prog[what]) != set(ref[what]):
            continue
        kinds: Dict[str, float] = {}
        for k, g in leaf_gaps(prog[what], ref[what], move, own=True).items():
            kind = k.split(".", 1)[-1]
            kinds[kind] = max(kinds.get(kind, 0.0), g)
        out[what] = kinds
    return out


def judge(found: Dict[str, Tuple[float, str]], limits: Dict[str, float]
          ) -> Tuple[bool, List[Dict]]:
    """(correct, [{name, value, limit, at}]) — every number within its
    limit; a number without a limit, or a limit without a number, fails."""
    checks, ok = [], set(found) == set(limits)
    for name in sorted(set(found) | set(limits)):
        value, at = found.get(name, (float("inf"), "not computed"))
        limit = limits.get(name, float("nan"))
        checks.append({"name": name, "value": value, "limit": limit,
                       "at": at})
        ok = ok and bool(value <= limit)
    return ok, checks
