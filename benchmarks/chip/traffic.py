"""One generator for every traffic mix: reads ``traffic/<name>.json``.

A unit of work is ``unit_segments`` straight joint-space segments of
``waypoints`` waypoints each, turned into link OBBs by :mod:`fk` (7 per
waypoint), and sent as one flat query set.  A segment starts uniform in the
joint limits and ends per ``goal`` (see :func:`fk.segments`).

``loop`` names the module that drives the mix, ``loops/<loop>.py``:
``closed`` (one client sends a unit, waits for its verdicts, sends the
next, cycling a pool of ``pool`` units) or ``open`` (units arrive on a
schedule, ``rate_per_s`` of them a second, whatever the service does).
Open-loop arrivals are Poisson arrivals conditioned on their count:
``round(rate_per_s * seconds)`` times uniform over the window, so every
seed offers the same load in another order.

With ``fixed_work_seed`` the units are drawn from that seed and the run's
seed only orders them: every seed then sends the same work.  A pool of a
few hundred segments drawn afresh does not: one segment through a shelf
outweighs dozens that miss it, and the traversal work of 200 trajectories
on cubby differs by 10% (quartile spread over the median) from one draw
to the next, and that of its heaviest batch of 25 by 7%.  Without it,
every segment is drawn from the run's seed.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

import fk

HERE = Path(__file__).resolve().parent


def load(name: str) -> dict:
    with open(HERE / "traffic" / f"{name}.json") as f:
        return json.load(f)


def unit_obbs(traffic: dict) -> int:
    return traffic["unit_segments"] * traffic["waypoints"] * fk.NUM_LINKS


def units(traffic: dict, n: int, rng: np.random.Generator):
    """``n`` units -> (center (n, U, 3), half (n, U, 3), rot (n, U, 3, 3))
    float32, U = :func:`unit_obbs`."""
    s = traffic["unit_segments"]
    fixed = "fixed_work_seed" in traffic
    draw = np.random.default_rng(traffic["fixed_work_seed"]) if fixed else rng
    start, end = fk.segments(draw, n * s, traffic["goal"])
    c, h, r = fk.link_obbs(fk.waypoints(start, end, traffic["waypoints"]))
    u = unit_obbs(traffic)
    c, h, r = c.reshape(n, u, 3), h.reshape(n, u, 3), r.reshape(n, u, 3, 3)
    if fixed:
        order = rng.permutation(n)
        c, h, r = c[order], h[order], r[order]
    return c, h, r


def arrivals(traffic: dict, seconds: float, rng: np.random.Generator
             ) -> np.ndarray:
    """Sorted due times (s from the window's start) of an open loop."""
    if traffic["arrivals"] != "poisson":
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    n = int(round(traffic["rate_per_s"] * seconds))
    return np.sort(rng.uniform(0.0, seconds, n))
