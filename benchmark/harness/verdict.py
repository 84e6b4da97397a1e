"""The comparison that decides ``correct``.

Every number compared has a limit of its own, kept in the cell's file
with the readings it was set from (``PERF.md`` has them too). A run is
correct when every number is finite and at or under its limit.
"""

from __future__ import annotations

import math
import statistics
from typing import Any, Dict, List, Optional, Set, Tuple

ZERO_GRADIENT_SHARE = 1e-3  # of the median leaf's gradient norm
MATRIX_ELEMENTS = 4096      # a leaf of so many elements is no bias or gain
NOT_A_NUMBER = 1e30         # what a reading that is not finite is shown as


def leaf_gaps(program: Dict[str, float],
              reference: Dict[str, float]) -> Dict[str, float]:
    """Every leaf's gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf."""
    median = statistics.median(reference.values())
    return {leaf: abs(program[leaf] - ref) / max(ref, median, 1e-30)
            for leaf, ref in reference.items()}


def worst_leaf_gap(program: Dict[str, float], reference: Dict[str, float],
                   leave_out: Optional[Set[str]] = None
                   ) -> Tuple[float, str, float]:
    """The widest of the leaves' gaps, the leaf that has it, and the
    median leaf's gap."""
    if set(program) != set(reference):
        raise ValueError("the program's leaves are not the reference's: "
                         f"{sorted(set(program) ^ set(reference))[:6]}")
    gaps = {leaf: gap for leaf, gap in leaf_gaps(program, reference).items()
            if not (leave_out and leaf in leave_out)}
    worst, where = 0.0, ""
    for leaf, gap in gaps.items():
        if not gap <= worst:  # a NaN is the worst there is
            worst, where = gap, leaf
    finite = [g if math.isfinite(g) else math.inf for g in gaps.values()]
    return worst, where, statistics.median(finite)


def matrices_mean_gap(program: Dict[str, float], reference: Dict[str, float],
                      sizes: Dict[str, int]) -> float:
    """The mean, over the leaves that are matrices (``MATRIX_ELEMENTS``
    elements or more), of each leaf's gap once the program's norms are
    divided by the factor that all of them share (the ratio of the two
    sides' norms over all the matrices). In bf16 that factor differs from
    1 by a few thousandths from seed to seed (the loss's gradient starts
    from 1/N held in 8 bits) and would hide what the leaves do apart; and
    one small leaf's noise, which can own the worst gap (a two-element
    bias fed by 64 rows), does not move a mean over the matrices."""
    leaves = [leaf for leaf in reference if sizes[leaf] >= MATRIX_ELEMENTS]
    shared = (math.sqrt(sum(program[k] ** 2 for k in leaves))
              / math.sqrt(sum(reference[k] ** 2 for k in leaves)))
    if not shared > 0:  # no gradient at all, or one that is not a number
        return math.inf
    gaps = leaf_gaps({k: program[k] / shared for k in leaves},
                     {k: reference[k] for k in leaves})
    return sum(gaps.values()) / len(gaps)


def zero_gradient_leaves(reference_grad_norms: Dict[str, float]) -> Set[str]:
    """Leaves whose gradient is nought to rounding in the reference (a
    key's bias under softmax): under Adam they move by round-off alone,
    so the parameters' change is not compared on them."""
    median = statistics.median(reference_grad_norms.values())
    return {leaf for leaf, norm in reference_grad_norms.items()
            if norm < ZERO_GRADIENT_SHARE * median}


def training_numbers(program: Dict[str, Any], reference: Dict[str, Any],
                     sizes: Dict[str, int]) -> Dict[str, Dict[str, Any]]:
    """The numbers of a training cell: each step's loss, the first
    gradient's norms by the worst leaf and by the matrices' shares, and the
    norms of the parameters' change by the worst leaf and by the median
    leaf (steady from seed to seed where the worst is one small leaf's
    noise, so it takes a limit that a learning rate a tenth off fails).
    ``sizes`` is every leaf's count of elements."""
    loss_gap = max(abs(p - r) / abs(r) if math.isfinite(p) else math.inf
                   for p, r in zip(program["losses"], reference["losses"]))
    grad_gap, grad_leaf, _ = worst_leaf_gap(program["grad_norms"],
                                            reference["grad_norms"])
    change_gap, change_leaf, change_median = worst_leaf_gap(
        program["change_norms"], reference["change_norms"],
        leave_out=zero_gradient_leaves(reference["grad_norms"]))
    return {
        "loss_gap": {"value": loss_gap},
        "grad_norm_gap": {"value": grad_gap, "leaf": grad_leaf},
        "grad_share_gap": {"value": matrices_mean_gap(
            program["grad_norms"], reference["grad_norms"], sizes)},
        "change_norm_gap": {"value": change_gap, "leaf": change_leaf},
        "change_median_gap": {"value": change_median},
    }


def judge(numbers: Dict[str, Dict[str, Any]],
          limits: Dict[str, float]) -> Tuple[bool, List[Dict[str, Any]]]:
    """``correct`` and, for the record, each number beside its limit. A
    number with no limit in the cell's file is shown and not compared."""
    rows, correct = [], True
    for name, number in numbers.items():
        row = dict(name=name, **number)
        if not math.isfinite(row["value"]):
            row["value"] = NOT_A_NUMBER  # JSON has no infinity
        if name in limits:
            row["limit"] = limits[name]
            row["ok"] = bool(row["value"] <= limits[name])
            correct = correct and row["ok"]
        rows.append(row)
    missing = sorted(set(limits) - set(numbers))
    if missing:
        raise KeyError(f"limits for numbers that were not compared: {missing}")
    return correct, rows
