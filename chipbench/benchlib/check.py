"""The comparison that decides ``correct`` for a training cell.

Three numbers, each against the limit of its own in the cell's limits file:

* ``loss_gap``: over the first rounds, the largest relative gap between
  the program's round loss and the reference's;
* ``g0_gap``: the first gradient the optimizer gets, g^0, taken by the
  worst leaf: | |g_prog| - |g_ref| | over the larger of the reference's
  norm of that leaf and of the median leaf;
* ``dx_gap``: the parameters' change over the check rounds as stored,
  x^k - x^0, by the worst leaf in the same way. Two rules on the
  reference leave leaves out: a g^0 under a thousandth of the median
  leaf's (its change is rounding alone), and a stored change whose norm
  departs by more than a tenth from the norm of the update that made it
  (lr * sum g^k: the update is under the stored dtype's resolution, so
  which coordinates move is rounding).

The gaps compare norms and not the vectors' difference: the program's
bf16 backward pass and the random rounding of a median of bucket means
scatter coordinates without moving a leaf's size, while a precision cut,
a missing share of the batch or a step that is not taken does move it.
"""
from __future__ import annotations

import math

NUMBERS = ("loss_gap", "g0_gap", "dx_gap")
SMALL_LEAF = 1e-3
STORED = 0.1


def _median(xs):
    s = sorted(xs)
    m = len(s)
    return s[m // 2] if m % 2 else 0.5 * (s[m // 2 - 1] + s[m // 2])


def _worst_leaf(got, ref, keep=None):
    med = _median(ref)
    worst = 0.0
    for i, (a, b) in enumerate(zip(got, ref)):
        if keep is not None and not keep[i]:
            continue
        gap = abs(a - b) / max(b, med, 1e-30)
        if not math.isfinite(gap):
            return math.inf
        worst = max(worst, gap)
    return worst


def gaps(prog: dict, ref: dict) -> dict:
    """prog/ref: {"losses": [...], "g0": [leaf norms], "dx": [leaf norms]},
    ref also "step": the leaf norms of lr * sum g^k."""
    loss = 0.0
    for a, b in zip(prog["losses"], ref["losses"]):
        g = abs(a - b) / max(abs(b), 1e-30)
        loss = max(loss, g if math.isfinite(g) else math.inf)
    med_g0 = _median(ref["g0"])
    keep = [g >= SMALL_LEAF * med_g0 and u > 0 and abs(x - u) <= STORED * u
            for g, x, u in zip(ref["g0"], ref["dx"], ref["step"])]
    return {"loss_gap": loss,
            "g0_gap": _worst_leaf(prog["g0"], ref["g0"]),
            "dx_gap": _worst_leaf(prog["dx"], ref["dx"], keep)}


def verdict(numbers: dict, limits: dict):
    """-> (correct, {name: {"value", "limit"}}). A number that is missing,
    not finite or above its limit makes the run incorrect."""
    checks, ok = {}, True
    for name in NUMBERS:
        v = numbers.get(name, math.inf)
        lim = limits[name]["limit"]
        checks[name] = {"value": v, "limit": lim}
        ok = ok and math.isfinite(v) and v <= lim
    return ok, checks
