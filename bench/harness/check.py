"""Decides ``correct``: the timed path's answers against the plain reference.

Every query that completed inside the window is compared with the
configuration's reference on the same data. Three numbers are read:

* ``wrong_shape``: answers whose columns or row count differ from the
  reference's (limit 0).
* ``failed``: window queries the engine ended as failed or cancelled other
  than by the window's deadline (limit 0).
* ``max_rel_err``: over all other answers and columns, the widest gap
  ``max|got - want| / max|want|`` of a column, rows taken in the answer's
  own order, so a wrong key, a wrong order or a wrong sum all read here.

The limits come from the configuration's ``limits``.
"""

from __future__ import annotations

from typing import Callable, Dict, List

import numpy as np


def column_gap(got: np.ndarray, want: np.ndarray) -> float:
    got = np.asarray(got, dtype=np.float64)
    want = np.asarray(want, dtype=np.float64)
    if len(want) == 0:
        return 0.0
    scale = float(np.max(np.abs(want)))
    diff = float(np.max(np.abs(got - want)))
    if diff == 0.0:
        return 0.0
    if np.isnan(diff) or scale == 0.0:  # a NaN or a value where zero is due
        return float("inf")
    return diff / scale


def readings(answers: List[Dict], answer_of: Callable[[str, Dict], Dict], n_failed: int) -> Dict:
    """``answers``: ``{"template", "params", "result"}`` per compared query;
    ``answer_of(template, params)`` gives the reference's answer. Equal
    instances are computed once."""
    cache: Dict = {}
    wrong_shape = 0
    worst = 0.0
    worst_at = ""
    for a in answers:
        key = (a["template"], tuple(sorted(a["params"].items())))
        if key not in cache:
            cache[key] = answer_of(a["template"], a["params"])
        want, got = cache[key], a["result"]
        if set(got) != set(want) or any(len(got[k]) != len(want[k]) for k in want):
            wrong_shape += 1
            continue
        for k in want:
            gap = column_gap(got[k], want[k])
            if gap > worst:
                worst, worst_at = gap, f"{a['template']}.{k}"
    return {
        "compared": len(answers),
        "distinct": len(cache),
        "wrong_shape": wrong_shape,
        "failed": n_failed,
        "max_rel_err": worst,
        "worst_column": worst_at,
    }


def verdict(read: Dict, limits: Dict) -> Dict[str, Dict[str, float]]:
    """Each number compared beside its limit; ``correct`` needs all within."""
    return {name: {"value": read[name], "limit": limits[name]} for name in limits}


def is_correct(checks: Dict[str, Dict[str, float]], compared: int) -> bool:
    return compared > 0 and all(c["value"] <= c["limit"] for c in checks.values())
