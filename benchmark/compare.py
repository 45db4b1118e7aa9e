"""The comparison that decides `correct` for a training cell.

What the timed path produced in its first steps (the program, driven through
the window's own call and feed) against the plain reference, which followed
the same steps from the same seed:

* `loss_gap`  — the widest relative gap between a step's loss and the
  reference's, over the checked steps.
* `grad_gap`  — by the worst leaf, the gap between the norm of the first
  gradient as the optimizer got it and the reference's norm of that leaf,
  measured against the reference's norm of that leaf or of the median leaf,
  whichever is larger (some gradients are all but zero).
* `delta_gap` — the same for the norm of each leaf's change over the checked
  steps. Leaves whose reference gradient is under a thousandth of the median
  leaf's are left out: they move by round-off alone.

`loss1_gap`, `grad_median_gap` and `delta_median_gap` are the first step's
loss alone and the median leaf's gap in place of the worst leaf's: steady from
seed to seed where the later steps and single small leaves are not.

A gap of norms, not the norm of a difference: it asks whether each leaf moved
as far as it should have, which a step that was skipped, doubled, averaged
over half the rows or computed in too low a precision does not.
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, Mapping, Optional, Sequence

NEGLIGIBLE_GRADIENT = 1e-3   # of the median leaf's norm


def _gap_by_worst_leaf(got: Mapping[str, float], want: Mapping[str, float],
                       keys: Sequence[str]):
    """(worst gap, its leaf, the median leaf's gap)."""
    floor = statistics.median(want[k] for k in keys)
    gaps = {}
    for k in keys:
        gap = abs(got[k] - want[k]) / max(want[k], floor, 1e-30)
        gaps[k] = gap if math.isfinite(gap) else math.inf
    worst_key = max(gaps, key=gaps.get)
    return gaps[worst_key], worst_key, statistics.median(gaps.values())


def first_step_gaps(program: Mapping, reference: Mapping) -> Dict:
    """`program` and `reference` are what `plain.first_steps` returns:
    losses, grad_norms and delta_norms by "layer/param"."""
    want_keys = sorted(reference["grad_norms"])
    if sorted(program["grad_norms"]) != want_keys or \
            sorted(program["delta_norms"]) != want_keys:
        raise ValueError("the program and the reference do not hold the "
                         "same leaves")
    if len(program["losses"]) != len(reference["losses"]):
        raise ValueError("the program and the reference did not take the "
                         "same number of steps")
    loss_gaps = [abs(a - b) / abs(b) if math.isfinite(a) else math.inf
                 for a, b in zip(program["losses"], reference["losses"])]
    grad_gap, grad_leaf, grad_median = _gap_by_worst_leaf(
        program["grad_norms"], reference["grad_norms"], want_keys)
    median = statistics.median(reference["grad_norms"].values())
    moved = [k for k in want_keys
             if reference["grad_norms"][k] >= NEGLIGIBLE_GRADIENT * median]
    delta_gap, delta_leaf, delta_median = _gap_by_worst_leaf(
        program["delta_norms"], reference["delta_norms"], moved)
    return {"loss_gap": max(loss_gaps), "loss1_gap": loss_gaps[0],
            "grad_gap": grad_gap, "grad_median_gap": grad_median,
            "delta_gap": delta_gap, "delta_median_gap": delta_median,
            "worst": {"grad_gap": grad_leaf, "delta_gap": delta_leaf},
            "leaves": len(want_keys),
            "leaves_left_out": len(want_keys) - len(moved)}


def judge(numbers: Mapping[str, float],
          limits: Mapping[str, Optional[float]]) -> Dict:
    """Each number beside its limit, and whether all hold. A limit of
    null means the number is printed and not compared."""
    checks, ok = {}, True
    for name, value in numbers.items():
        limit = limits.get(name)
        checks[name] = {"value": value, "limit": limit}
        if limit is not None and not (math.isfinite(value)
                                      and value <= limit):
            ok = False
    return {"correct": ok, "checks": checks}
