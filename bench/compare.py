"""The numbers that decide ``correct`` for a training cell, from two sets
of readings (``{"losses", "grad1", "change"}``): the program's and the
reference's.

* ``loss_gap``: the largest relative gap of a step's loss.
* ``grad_gap``: by the worst leaf, the gap between the program's norm
  of the first gradient and the reference's, over the reference's norm
  of that leaf or of the median leaf, whichever is larger.
* ``change_gap``: the same for the change of the weights over the
  steps.  Leaves whose first gradient in the reference is under a
  thousandth of the median leaf's are left out: they move by rounding
  alone (a key bias under softmax has no gradient).

A leaf under ``blocks`` counts once per layer.
"""
from __future__ import annotations

import statistics
from typing import Dict, Iterable, List, Optional, Tuple

from bench.harness import Check

MOVING = 1e-3


def loss_gap(prog: List[float], ref: List[float]) -> float:
    if len(prog) != len(ref):
        return float("inf")
    return max(abs(p - r) / abs(r) for p, r in zip(prog, ref))


def moving_leaves(ref_grad1: Dict[str, float]) -> List[str]:
    med = statistics.median(ref_grad1.values())
    return [k for k, v in ref_grad1.items() if v >= MOVING * med]


def norm_gap(prog: Dict[str, float], ref: Dict[str, float],
             keys: Optional[Iterable[str]] = None) -> Tuple[float, str]:
    """(worst gap, its leaf)."""
    keys = list(ref if keys is None else keys)
    if set(prog) != set(ref):
        return float("inf"), "leaves differ"
    med = statistics.median(ref[k] for k in keys)
    return max((abs(prog[k] - ref[k]) / max(ref[k], med), k) for k in keys)


def train_checks(prog, ref, *, with_grad: bool = True) -> List[Check]:
    """Checks with their limits left to the cell's workload file."""
    out = [Check("loss_gap", loss_gap(prog["losses"], ref["losses"]), None)]
    if with_grad:
        out.append(Check("grad_gap",
                         norm_gap(prog["grad1"], ref["grad1"])[0], None))
    out.append(Check("change_gap", norm_gap(
        prog["change"], ref["change"], moving_leaves(ref["grad1"]))[0], None))
    return out
