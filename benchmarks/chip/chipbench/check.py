"""The numbers that decide ``correct``, from the program's first steps and
the reference's.

- ``unmatched_rows``: rows the program fed to a client that are not rows of
  that client's data (exact: limit 0).
- ``loss_gap``: the largest relative gap between the program's and the
  reference's round loss (mean first-step client loss) over the compared
  rounds.
- ``first_update_gap`` and ``change_gap``: for the change of the parameters
  after the first step, and after the last compared step, the worst leaf's
  gap between the program's and the reference's change norm, over the
  reference's norm of that leaf or of the median leaf, whichever is larger.
  Leaves whose reference change is under a thousandth of the median leaf's
  (per element, root mean square) move by rounding alone and are left out.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

#: a leaf whose reference change, per element, is under this share of the
#: median leaf's moves by rounding alone
NEGLIGIBLE = 1e-3


def leaf_gap(prog: np.ndarray, ref: np.ndarray, sizes: np.ndarray) -> float:
    prog, ref = np.asarray(prog, float), np.asarray(ref, float)
    rms = ref / np.sqrt(np.maximum(sizes, 1))
    keep = rms >= NEGLIGIBLE * np.median(rms)
    base = np.maximum(ref, np.median(ref[keep]))
    return float(np.max(np.abs(prog - ref)[keep] / base[keep]))


def loss_gap(prog: Sequence[float], ref: Sequence[float]) -> float:
    p, r = np.asarray(prog, float), np.asarray(ref, float)
    if p.shape != r.shape or not np.all(np.isfinite(p)):
        return float("inf")
    return float(np.max(np.abs(p - r) / np.abs(r)))


def numbers(prog: Dict, ref: Dict, sizes: np.ndarray,
            unmatched_rows: int) -> Dict[str, float]:
    """``prog``/``ref``: {"losses": [...], "first": norms, "last": norms}."""
    return {
        "unmatched_rows": float(unmatched_rows),
        "loss_gap": loss_gap(prog["losses"], ref["losses"]),
        "first_update_gap": leaf_gap(prog["first"], ref["first"], sizes),
        "change_gap": leaf_gap(prog["last"], ref["last"], sizes),
    }


def judge(values: Dict[str, float], limits: Dict[str, float]
          ) -> List[Dict[str, object]]:
    """Each number beside its limit; a number over its limit (or not
    finite) fails."""
    out = []
    for name, v in values.items():
        lim = float(limits[name])
        ok = bool(np.isfinite(v) and v <= lim)
        out.append({"name": name, "value": v, "limit": lim, "ok": ok})
    return out
