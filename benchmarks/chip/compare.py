"""The comparison that decides ``correct``.

Each answered OBB's verdict is held against the reference's separation
(:mod:`reference`): an OBB the program calls free must not penetrate the
scene, and one it calls colliding must not stand clear of it.  Two numbers,
in metres, each with its limit:

* ``missed_m`` — deepest reference penetration among OBBs the program
  called free (0 when there is none);
* ``false_hit_m`` — widest reference clearance among OBBs the program
  called colliding (0 when there is none);

and one count, ``unanswered``: answers that never came (limit 0).  A
request refused or failed with a typed error is not an answer that never
came; it counts as failed and as missing its latency.

``PERF.md`` gives the readings the limits were set from: the program's
sound runs read at most float32 rounding, a micrometre at these scene
sizes; the bfloat16 control reads tenths of a millimetre and more.

These are the collision loops' limits.  A loop of another kind keeps its
own beside it; whatever the loop, a run is correct when every value its
check returns is within its limit (:func:`within`).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

LIMITS = {"missed_m": 1e-5, "false_hit_m": 1e-5, "unanswered": 0}


def readings(verdict: np.ndarray, sep: np.ndarray) -> Dict[str, float]:
    """``verdict`` (N,) bool from the program, ``sep`` (N,) reference
    separations of the same OBBs."""
    v = np.asarray(verdict, bool)
    missed = -sep[~v & (sep <= 0)]
    false = sep[v & (sep > 0)]
    return {"missed_m": float(missed.max(initial=0.0)),
            "false_hit_m": float(false.max(initial=0.0))}


def within(checks: Dict[str, List[float]]) -> bool:
    """Every value at or under its limit (a NaN is not)."""
    return all(v <= lim for v, lim in checks.values())


def judge(values: Dict[str, float]) -> Tuple[bool, Dict[str, List[float]]]:
    """(correct, {name: [value, limit]}) in :data:`LIMITS` order."""
    checks = {k: [values[k], lim] for k, lim in LIMITS.items()}
    return within(checks), checks
