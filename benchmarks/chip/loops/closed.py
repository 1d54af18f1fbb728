"""Closed loop: one client sends a unit, waits for its verdicts, sends the
next, back to back, cycling a pool of ``pool`` units drawn at set-up.

Set-up builds the scene and engine from the seed and runs every unit of
the pool once, so the window meets no shape, and no frontier capacity,
that set-up has not compiled.  Every call of the window is checked.
"""
from __future__ import annotations

import time

import jax
import numpy as np

import collision
import compare
import traffic


def pool(mix: dict, rng: np.random.Generator):
    """The units the client cycles, drawn from the traffic stream."""
    return traffic.units(mix, mix["pool"], rng)


def closed_loop(engine, units, seconds: float):
    """One client, back to back, cycling ``units``.  Returns the calls'
    (unit, t0, t1, verdict, counters) and the window's length."""
    c, h, r = units
    calls = []
    k = 0
    t_begin = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            i = k % len(c)
            with jax.profiler.TraceAnnotation("bench.execute"):
                t0 = time.perf_counter()
                verdict, counters = engine.execute(
                    collision.plan(c[i], h[i], r[i]))
                t1 = time.perf_counter()
            calls.append((i, t0, t1, verdict, counters))
            k += 1
            if t1 - t_begin >= seconds:
                break
    return calls, calls[-1][2] - t_begin


class Loop:
    def __init__(self, run, config: dict, mix: dict, seed: int,
                 seconds: float, mark):
        rng_scene, rng_traffic, _, _ = collision.streams(seed)
        self.engine, self.vox, tree = collision.build(config, rng_scene)
        mark("scene")
        run.level_widths = [len(level.codes) for level in tree.levels]
        run.unit_obbs = traffic.unit_obbs(mix)
        self.pool = pool(mix, rng_traffic)
        mark("traffic")
        c, h, r = self.pool
        for i in range(mix["pool"]):
            self.engine.execute(collision.plan(c[i], h[i], r[i]))

    def window(self, run, seconds: float) -> dict:
        self.calls, run.window_s = closed_loop(self.engine, self.pool,
                                               seconds)
        run.calls = [(t0, t1) for _, t0, t1, _, _ in self.calls]
        return {"attempted": len(self.calls), "failed": 0,
                "engine": collision.engine_totals(
                    [c for *_, c in self.calls])}

    def release(self):
        del self.engine

    def check(self) -> dict:
        """Every call's verdicts against the reference's separations of
        its unit."""
        c, h, r = self.pool
        u = c.shape[1]
        sep = self.vox.separation(c.reshape(-1, 3), h.reshape(-1, 3),
                                  r.reshape(-1, 3, 3)).reshape(-1, u)
        by_unit: dict = {}
        for i, _, _, v, _ in self.calls:
            by_unit.setdefault(i, []).append(np.asarray(v))
        vals = {"missed_m": 0.0, "false_hit_m": 0.0}
        for i, vs in by_unit.items():
            got = compare.readings(np.stack(vs).reshape(-1),
                                   np.tile(sep[i], len(vs)))
            vals = {k: max(vals[k], got[k]) for k in vals}
        vals["unanswered"] = 0
        return compare.judge(vals)[1]
