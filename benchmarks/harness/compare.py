"""The comparisons that decide `correct`, and how a run prints them.

Every number compared is a `Check`: a short plain name, the number, its
limit. A run is correct when every number is at or under its limit (a
number that is not finite fails). The limits live in the configuration
file's `limits` group, set from chip readings (PERF.md gives them).
"""

from __future__ import annotations

import math
import statistics
from typing import NamedTuple


class Check(NamedTuple):
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def all_ok(checks: list[Check]) -> bool:
    return bool(checks) and all(c.ok for c in checks)


def as_json(checks: list[Check]) -> dict:
    return {c.name: {"value": c.value, "limit": c.limit} for c in checks}


def leaf_gaps(prog: dict, ref: dict, skip=(), scale: dict | None = None) -> list:
    """(gap, leaf, program's norm, reference's norm), worst first: the gap
    between the program's norm of a leaf and the reference's (the gap of
    the norms, not the norm of a difference), against `scale`'s norm of
    that leaf (the reference's own where none is given) or of the median
    leaf, whichever is larger: some gradients are all but zero. A leaf the
    program lacks reads as infinite."""
    scale = ref if scale is None else scale
    median = statistics.median(scale.values())
    rows = []
    for name, r in ref.items():
        if name in skip:
            continue
        p = prog.get(name, math.inf)
        gap = abs(p - r) / max(scale[name], median, 1e-30)
        rows.append((gap if gap == gap else math.inf, name, p, r))
    return sorted(rows, reverse=True)


def idle_gradient_leaves(ref_grad_norms: dict) -> set:
    """Leaves whose gradient is nought to rounding in the reference (a
    key's bias under softmax): under Adam they move by round-off alone, so
    the change of the parameters is not compared on them. The rule is on
    the reference's own gradient, micro-batch by micro-batch: under a
    thousandth of the median leaf's."""
    median = statistics.median(ref_grad_norms.values())
    return {k for k, v in ref_grad_norms.items() if v < 1e-3 * median}


def training_checks(prog: dict, ref: dict, limits: dict):
    """`prog`/`ref`: `losses` (one per followed update), `grad1_norms`
    and `delta_norms` (per leaf); `ref` also `grad1_scale`. Returns the
    checks and notes on where the worst gaps sit."""
    checks = []
    if len(prog["losses"]) != len(ref["losses"]):
        checks.append(Check("losses_missing", math.inf, 0.0))
    loss_gaps = [abs(p - r) / abs(r)
                 for p, r in zip(prog["losses"], ref["losses"])]
    checks.append(Check("loss_gap", max(loss_gaps), limits["loss_gap"]))
    # The first gradient of a batch is a sum over rows whose shared part
    # cancels when the labels balance (PERF.md, Cells): its gap is taken
    # against what the leaf's norm is micro-batch by micro-batch, which
    # does not shrink with the luck of the labels.
    grads = leaf_gaps(prog["grad1_norms"], ref["grad1_norms"],
                      scale=ref["grad1_scale"])
    checks.append(Check("grad1_norm_gap", grads[0][0], limits["grad1_norm_gap"]))
    idle = idle_gradient_leaves(ref["grad1_scale"])
    deltas = leaf_gaps(prog["delta_norms"], ref["delta_norms"], skip=idle)
    checks.append(Check("delta_norm_gap", deltas[0][0], limits["delta_norm_gap"]))
    return checks, {
        "loss_gaps": loss_gaps, "idle_leaves": len(idle),
        "grad1_median_norm": statistics.median(ref["grad1_norms"].values()),
        "grad1_median_scale": statistics.median(ref["grad1_scale"].values()),
        "grad1_worst": grads[:3], "delta_worst": deltas[:3],
        "grad1_gap_by_own_norm": list(leaf_gaps(
            prog["grad1_norms"], ref["grad1_norms"])[0][:2]),
    }
