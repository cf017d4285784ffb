"""The comparison that decides ``correct``.

Three numbers, each against a limit of its own from the cell file:

``loss_gap``              the largest relative gap between the program's
                          and the reference's loss over the first three
                          steps;
``grad_norm_median_gap``  the median over the leaves of the gap between
                          the norms of the first step's clipped gradient,
                          as the optimizer got it (the program's read off
                          AdamW's first moment, ``m / (1 - b1)``), each
                          over the larger of that leaf's reference norm
                          and the median leaf's;
``change_gap``            the worst leaf's gap, measured the same way,
                          between the norms of each leaf's change after
                          the three steps.  Leaves whose reference
                          gradient is under a thousandth of the median
                          leaf's move by round-off alone under AdamW and
                          are left out.

A leaf is one parameter of one layer (``layers.<l>.attn.wq``).  The
worst leaf's gradient gap is read too (``grad_norm_gap``) but not
compared: it is always a router leaf, where bf16 rounding moves
near-tied tokens to another expert (PERF.md).
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

NUMBERS = ("loss_gap", "grad_norm_median_gap", "change_gap")
STILL = 1e-3        # a leaf whose gradient is under this share of the median's


def _median(xs: List[float]) -> float:
    xs = sorted(xs)
    n = len(xs)
    return xs[n // 2] if n % 2 else 0.5 * (xs[n // 2 - 1] + xs[n // 2])


def _gaps(got: Dict[str, float], want: Dict[str, float],
          leaves: List[str]) -> Dict[str, float]:
    """Each leaf's gap of norms over the larger of its reference norm and
    the median leaf's; a missing or non-finite norm reads infinite."""
    floor = _median([want[k] for k in leaves])
    out = {}
    for k in leaves:
        gap = abs(got.get(k, float("nan")) - want[k]) / max(want[k], floor,
                                                            1e-30)
        out[k] = gap if math.isfinite(gap) else float("inf")
    return out


def _worst(gaps: Dict[str, float]) -> Tuple[float, str]:
    name = max(gaps, key=gaps.get)
    return gaps[name], name


def readings(prog: Dict, ref: Dict) -> Dict[str, Dict]:
    """``prog`` and ``ref`` hold ``loss`` (a list, one per step),
    ``grad_norm`` and ``change`` (``{leaf: norm}``)."""
    if len(prog["loss"]) != len(ref["loss"]):
        raise ValueError("the program and the reference ran different steps")
    loss = max(abs(a - b) / max(abs(b), 1e-30) if math.isfinite(a)
               else float("inf") for a, b in zip(prog["loss"], ref["loss"]))
    leaves = sorted(ref["grad_norm"])
    grad = _gaps(prog["grad_norm"], ref["grad_norm"], leaves)
    g, g_leaf = _worst(grad)
    med = _median([ref["grad_norm"][k] for k in leaves])
    moving = [k for k in leaves if ref["grad_norm"][k] >= STILL * med]
    c, c_leaf = _worst(_gaps(prog["change"], ref["change"], moving))
    return {"loss_gap": {"value": loss},
            "grad_norm_median_gap": {"value": _median(list(grad.values()))},
            "grad_norm_gap": {"value": g, "leaf": g_leaf},
            "change_gap": {"value": c, "leaf": c_leaf,
                           "left_out": sorted(set(leaves) - set(moving))}}


def judge(read: Dict[str, Dict], limits: Dict[str, float]) -> bool:
    """True when every number is finite and within its limit.  A number
    without a limit cannot pass."""
    return all(n in limits and math.isfinite(read[n]["value"])
               and read[n]["value"] <= limits[n] for n in NUMBERS)


def lines(read: Dict[str, Dict], limits: Dict[str, float]) -> List[str]:
    """One line per number: its name, its reading and its limit."""
    out = []
    for n in NUMBERS:
        r = read[n]
        where = f" ({r['leaf']})" if r.get("leaf") else ""
        out.append(f"{n} {r['value']!r} limit {limits.get(n)!r}{where}")
    return out
