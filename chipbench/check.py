"""The comparison that decides ``correct``.

Four numbers, each against a limit of its own from the cell file:

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
                          are left out;
``embed_row_gap``         the 10th percentile (``ROW_QUANTILE``), over
                          the rows of the embedding table that the first
                          batch touches, of each row's relative vector
                          error ``|g[v] - g_ref[v]| / |g_ref[v]|``
                          between the first step's clipped gradients,
                          after the program's rows are scaled by the
                          median over rows of the ratio of the two sides'
                          row norms.

A leaf is one parameter of one layer (``layers.<l>.attn.wq``).  The
worst leaf's gradient gap is read too (``grad_norm_gap``) but not
compared: it is always a router leaf, where bf16 rounding moves
near-tied tokens to another expert (PERF.md).

Such a routing flip changes a few tokens a great deal; computing in a
lower precision changes every token a little.  A norm over a whole leaf
adds the two together, so on a path that computes many assignments it
cannot tell bf16 from float8.  ``embed_row_gap`` can: under Zipf traffic
most touched rows of the (untied) embedding hold one or two tokens, and
a dense error reaches every row.  A flip reaches the other rows too,
through attention, but unevenly, and only adds to a row's error; so the
rows it reaches least, at the low end of the rows' errors, keep the
precision's error and little else (the chip surveys behind the quantile
are in PERF.md).  Clipping multiplies each side by one factor of its
own, which flips move too; the median of the row-norm ratios is that
factor's ratio, untouched by the few rows that flips change a great
deal, so it is taken out.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import numpy as np

NUMBERS = ("loss_gap", "grad_norm_median_gap", "change_gap", "embed_row_gap")
STILL = 1e-3        # a leaf whose gradient is under this share of the median's
ROW_QUANTILE = 0.1  # the embedding rows' error that embed_row_gap reads


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


def row_gap(prog: Dict, ref: Dict) -> Dict:
    """``embed_row_gap`` with the scale taken out of the program's rows
    (``scale``) and the number of rows; rows that are missing, under other
    ids or of another shape, or a non-finite reading, read infinite."""
    ids, want = ref["embed_ids"], np.asarray(ref["embed_rows"], np.float64)
    got = prog.get("embed_rows")
    out = {"value": float("inf"), "scale": float("nan"), "rows": len(ids)}
    if got is None or np.shape(got) != want.shape or not np.array_equal(
            prog.get("embed_ids"), ids):
        return out
    got = np.asarray(got, np.float64)
    want_n = np.linalg.norm(want, axis=1)
    with np.errstate(divide="ignore", invalid="ignore"):
        scale = float(np.median(want_n / np.linalg.norm(got, axis=1)))
        gaps = np.linalg.norm(scale * got - want, axis=1) / np.maximum(
            want_n, 1e-30)
    value = float(np.quantile(gaps, ROW_QUANTILE))
    out["scale"] = scale
    if math.isfinite(value):
        out["value"] = value
    return out


def readings(prog: Dict, ref: Dict) -> Dict[str, Dict]:
    """``prog`` and ``ref`` hold ``loss`` (a list, one per step),
    ``grad_norm`` and ``change`` (``{leaf: norm}``), and ``embed_ids``
    (the sorted ids of the first batch's inputs) with ``embed_rows``
    (those rows of the first step's clipped gradient of the embedding
    table, one per id)."""
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
                           "left_out": sorted(set(leaves) - set(moving))},
            "embed_row_gap": row_gap(prog, ref)}


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
