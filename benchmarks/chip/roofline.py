"""Peak table and the compulsory work of a traversal, kept with the benchmark.

``peaks.json`` holds each chip's published peaks, keyed by the
``device_kind`` JAX reports; a device missing from it is an error, never a
default.

A collision batch must at least read every occupied octree node's
metadata once (16 B: Morton code, full flag, first child, child mask),
read every query OBB (60 B: centre, half extents, rotation, float32) and
write its verdict (4 B).  :func:`compulsory_bytes` counts exactly that,
from the scene's level widths and the batch size alone, so every engine
arm is judged against the same work.  Traversal does no arithmetic worth
a compute bound; its roofline is the bytes bound.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Sequence

HERE = Path(__file__).resolve().parent

BYTES_PER_NODE = 16
BYTES_PER_OBB_IN = 60
BYTES_PER_VERDICT = 4


def peaks(device_kind: str) -> dict:
    with open(HERE / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{', '.join(sorted(table))}")
    return table[device_kind]


def compulsory_bytes(level_widths: Sequence[int], num_obbs: int) -> int:
    return (BYTES_PER_NODE * int(sum(level_widths))
            + (BYTES_PER_OBB_IN + BYTES_PER_VERDICT) * int(num_obbs))
