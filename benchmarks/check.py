"""The comparison that decides `correct` for a training cell.

Both sides are dictionaries of the same form, the program's read by the probe
from the timed path's own first calls and the reference's from
`reference/<config>.py`:

    {"losses": [...], "grad1_norms": {leaf: norm} or None, "change_norms": {leaf: norm}}

The reference may add `losses_followed` (how many leading losses it can follow:
with a randomised codec only the first comes before any draw), `change_stat`
("total": single leaves carry the codec's sampling noise and the worst of them
swings with it, so the change is compared by its norm over all leaves together
and by the median leaf's gap) and `msg_bytes` (the encoded message, counted
from shapes and compared exactly with the step's own count).

Norms are compared by the worst leaf: the gap between the program's norm and
the reference's (not the norm of their difference), against the reference's
norm of that leaf or of the median leaf, whichever is larger, since some
gradients are all but zero. Leaves whose first gradient is under a thousandth
of the median leaf's in the reference are left out of the change: they move
by round-off alone.
"""

from __future__ import annotations

import math
import statistics

DEAD_LEAF = 1e-3  # of the median leaf's gradient norm, in the reference


def leaf_gaps(prog: dict, ref: dict, skip=()) -> dict[str, float]:
    if set(prog) != set(ref):
        raise ValueError(
            f"leaves differ: {sorted(set(prog) ^ set(ref))[:6]} on one side only"
        )
    median = statistics.median(ref.values())
    return {
        leaf: abs(prog[leaf] - norm) / max(norm, median, 1e-30)
        for leaf, norm in ref.items() if leaf not in skip
    }


def worst_leaf_gap(prog: dict, ref: dict, skip=()) -> tuple[float, str]:
    worst, where = 0.0, ""
    for leaf, gap in leaf_gaps(prog, ref, skip).items():
        if not gap <= worst:  # also true for nan
            worst, where = gap, leaf
    return worst, where


def training_numbers(prog: dict, ref: dict) -> dict[str, dict]:
    """Each number compared, with the leaf or step it was worst at."""
    if len(prog["losses"]) != len(ref["losses"]):
        raise ValueError(
            f"{len(prog['losses'])} losses from the program, "
            f"{len(ref['losses'])} from the reference"
        )
    out = {}
    followed = ref.get("losses_followed", len(ref["losses"]))
    gaps = [
        abs(p - r) / max(abs(r), 1e-30)
        for p, r in list(zip(prog["losses"], ref["losses"]))[:followed]
    ]
    worst = max(range(len(gaps)), key=lambda i: (math.isnan(gaps[i]), gaps[i]))
    out["loss_gap"] = {"value": gaps[worst], "at": f"step {worst + 1}"}
    skip = ()
    if ref.get("grad1_norms"):
        median = statistics.median(ref["grad1_norms"].values())
        skip = tuple(k for k, v in ref["grad1_norms"].items() if v < DEAD_LEAF * median)
        if prog.get("grad1_norms"):
            gap, leaf = worst_leaf_gap(prog["grad1_norms"], ref["grad1_norms"])
            out["grad1_gap"] = {"value": gap, "at": leaf}
    if ref.get("change_stat", "worst_leaf") == "total":
        whole = lambda side: math.sqrt(sum(v * v for v in side["change_norms"].values()))  # noqa: E731
        gap, leaf = abs(whole(prog) - whole(ref)) / max(whole(ref), 1e-30), "all leaves"
        per_leaf = sorted(leaf_gaps(prog["change_norms"], ref["change_norms"], skip).items(),
                          key=lambda kv: (math.isnan(kv[1]), kv[1]))
        middle = per_leaf[len(per_leaf) // 2]
        out["median_leaf_change_gap"] = {"value": middle[1], "at": middle[0]}
    else:
        gap, leaf = worst_leaf_gap(prog["change_norms"], ref["change_norms"], skip)
    out["change_gap"] = {"value": gap, "at": leaf}
    if ref.get("msg_bytes") is not None:
        got = prog.get("msg_bytes")
        gap = math.nan if got is None else abs(got - ref["msg_bytes"]) / ref["msg_bytes"]
        out["msg_bytes_gap"] = {"value": gap, "at": f"{got} against {ref['msg_bytes']} bytes"}
    return out


def judge(numbers: dict[str, dict], limits: dict[str, float]) -> tuple[bool, dict]:
    """`correct`, and each number beside its limit. A number without a limit
    cannot be judged, which is an error of the benchmark and not a pass."""
    compared, correct = {}, True
    for name, got in numbers.items():
        if name not in limits:
            raise KeyError(f"no limit for the compared number {name!r}")
        value, limit = float(got["value"]), float(limits[name])
        ok = math.isfinite(value) and value <= limit
        correct = correct and ok
        compared[name] = {"value": value, "limit": limit, "at": got.get("at", "")}
    return correct, compared
