"""A whole run of each cell at a small size on the CPU, past the look for
a chip: sound, it comes out correct; with the timed path broken underneath
(half of each launch left unchecked, answers altered where the engine
produces them) it comes out not correct."""
import chipbench_paths  # noqa: F401  (first: the import path)
import copy
import time

import numpy as np
import pytest

import run
import traffic
from repro.engine import CollisionEngine

CELLS = ("cubby_t3.traj_batch", "dresser_t3.traj_batch",
         "dresser_t3.edge_serve")


def _run(cell_name):
    bench = run.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == cell_name)
    config = copy.deepcopy(run.load_config(bench, cell["config"]))
    config["num_points"], config["depth"] = 30000, 5
    mix = traffic.load(cell["traffic"])
    if mix["loop"] == "closed":
        mix["unit_segments"], mix["pool"] = 4, 2
    else:
        mix["rate_per_s"], mix["sample_units"] = 60, 60
        config["service"]["max_batch"] = 128
    return run.run_cell(bench, cell, config, mix, seed=2**31 + 7,
                        seconds=1.0, trace=False,
                        t_process=time.perf_counter())


def _broken(monkeypatch, damage):
    execute = CollisionEngine.execute

    def wrapped(self, plan, **kw):
        verdict, counters = execute(self, plan, **kw)
        return damage(np.array(verdict, copy=True)), counters

    monkeypatch.setattr(CollisionEngine, "execute", wrapped)


def _half_unchecked(v):
    v[len(v) // 2:] = False
    return v


def _altered(v):
    v[::7] = ~v[::7]
    return v


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    out = _run(cell)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


@pytest.mark.parametrize("damage", [_half_unchecked, _altered],
                         ids=["half_unchecked", "answers_altered"])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_path_is_not_correct(cell, damage, monkeypatch):
    _broken(monkeypatch, damage)
    out = _run(cell)
    assert not out["correct"], out["checks"]
