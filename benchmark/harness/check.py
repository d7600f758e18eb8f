"""The comparison that decides ``correct``.

The program's first ``train()`` calls (made during set-up on the very
trainer object the window then drives, at the timed batch, length and
window) are followed by the plain reference.  Three numbers are compared,
each against a limit of its own from the cell's file:

- ``loss_gap``: the widest |program - reference| over the followed windows'
  mean losses, in nats;
- ``first_gap``: after the first call (one window: the sum of its steps'
  gradients as the optimizer got them, times the rate), the worst leaf's gap
  between the program's norm of the center's change and the reference's;
- ``change_gap``: the same after the last followed call;
- ``rare_gap``: after the first call, the gap of the sub-leaf ``wte.rare``
  (embedding rows of the tokens a batch holds once or never) over the
  reference's norm of it: the number that sees a row of the batch left out.

A leaf's gap is |program's norm - reference's norm| over the reference's
norm of that leaf or of the median leaf, whichever is larger.  Leaves whose
reference change is under a thousandth of the median leaf's are left out
(they move by round-off alone).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import numpy as np

QUIET_LEAF = 1e-3


def flatten_norms(norms: Dict[str, Any]) -> Dict[str, float]:
    """{"blocks.w_up": [n_layer]} -> {"blocks.w_up.3": x, ...}."""
    out = {}
    for name, v in norms.items():
        v = np.asarray(v, dtype=np.float64)
        if v.ndim == 0:
            out[name] = float(v)
        else:
            out.update({f"{name}.{i}": float(x) for i, x in enumerate(v)})
    return out


def worst_leaf_gap(program: Dict[str, Any], reference: Dict[str, Any]
                   ) -> Tuple[float, str]:
    prog, ref = flatten_norms(program), flatten_norms(reference)
    prog = {k: v for k, v in prog.items() if not k.endswith(".rare")}
    ref = {k: v for k, v in ref.items() if not k.endswith(".rare")}
    if set(prog) != set(ref):
        return math.inf, "leaf sets differ"
    median = float(np.median(list(ref.values())))
    worst, where = 0.0, ""
    for name, r in ref.items():
        if r < QUIET_LEAF * median:
            continue
        p = prog[name]
        gap = abs(p - r) / max(r, median) if math.isfinite(p) else math.inf
        if gap > worst:
            worst, where = gap, name
    return worst, where


def compare(program: List[Dict[str, Any]], reference: List[Dict[str, Any]]
            ) -> Dict[str, Any]:
    """``program`` / ``reference``: per followed call ``{"losses": [...],
    "norms": {...}}``.  Returns the numbers compared (no limits applied)."""
    loss_gap = 0.0
    for p, r in zip(program, reference):
        if len(p["losses"]) != len(r["losses"]):
            loss_gap = math.inf
            continue
        for a, b in zip(p["losses"], r["losses"]):
            gap = abs(float(a) - float(b))
            loss_gap = max(loss_gap, gap if math.isfinite(gap) else math.inf)
    first, first_leaf = worst_leaf_gap(program[0]["norms"], reference[0]["norms"])
    last, last_leaf = worst_leaf_gap(program[-1]["norms"], reference[-1]["norms"])
    out = {"loss_gap": loss_gap, "first_gap": first, "change_gap": last,
           "first_leaf": first_leaf, "change_leaf": last_leaf}
    try:
        gap = abs(float(program[0]["losses"][0]) - float(reference[0]["losses"][0]))
        out["loss_first"] = gap if math.isfinite(gap) else math.inf
    except (IndexError, KeyError):
        out["loss_first"] = math.inf
    if "wte.rare" in reference[0]["norms"]:
        r = float(reference[0]["norms"]["wte.rare"])
        p = float(program[0]["norms"].get("wte.rare", math.nan))
        out["rare_gap"] = abs(p - r) / r if math.isfinite(p) and r > 0 else math.inf
    return out


def verdict(numbers: Dict[str, Any], limits: Dict[str, float]
            ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """(correct, {name: {"value", "limit"}}): every limit of the cell's file
    must be there and hold; a number that is not finite fails."""
    compared, ok = {}, True
    for name, limit in limits.items():
        value = float(numbers[name])
        compared[name] = {"value": value, "limit": float(limit)}
        ok = ok and math.isfinite(value) and value <= float(limit)
    return ok, compared
