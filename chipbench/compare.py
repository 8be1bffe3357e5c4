"""The numbers that decide `correct`, each a gap between the program and
the reference, and the check of each against its limit."""
from __future__ import annotations

import math
from typing import Dict, Iterable, Optional, Tuple

import numpy as np


def rel_gap(prog: Iterable[float], ref: Iterable[float]) -> float:
    """Largest |program - reference| / |reference| over paired values."""
    worst = 0.0
    for p, r in zip(prog, ref):
        g = abs(p - r) / abs(r) if math.isfinite(p) else math.inf
        worst = max(worst, g)
    return worst


def leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
             keep: Optional[Iterable[str]] = None) -> Tuple[float, str]:
    """Worst leaf of |norm_program - norm_reference|, measured against
    the reference's norm of that leaf or of the median leaf, whichever
    is larger. A leaf missing on the program's side counts as infinite."""
    names = sorted(ref if keep is None else keep)
    med = float(np.median([ref[k] for k in names]))
    worst, at = 0.0, ""
    for k in names:
        p = prog.get(k, math.nan)
        g = (abs(p - ref[k]) / max(ref[k], med, 1e-30)
             if math.isfinite(p) else math.inf)
        if g > worst or not at:
            worst, at = g, k
    return worst, at


def moved_leaves(ref_grad: Dict[str, float], share: float = 1e-3):
    """Leaves whose reference gradient is above `share` of the median
    leaf's: the others move under Adam by round-off alone."""
    med = float(np.median(list(ref_grad.values())))
    return [k for k, g in ref_grad.items() if g >= share * med]


def judge(numbers: Dict[str, float], limits: Dict[str, float]):
    """(correct, [{name, value, limit}]): every limit must be met by a
    finite number. A number the cell's limits do not name is reported
    and not compared."""
    rows, ok = [], True
    for name, limit in limits.items():
        v = numbers.get(name, math.nan)
        ok = ok and math.isfinite(v) and v <= limit
        rows.append({"name": name, "value": v, "limit": limit})
    return ok, rows
