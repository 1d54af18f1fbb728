"""The comparison fails its control: the reference computed in bfloat16,
the precision below the float32 the configurations state, in the
program's place.  The same reference in float32 passes.  Run at a size a
test holds; ``control.py`` runs it at the cells' own sizes."""
import chipbench_paths  # noqa: F401  (first: the import path)
import copy

import numpy as np
import pytest

import compare
import control
import reference
import run
import traffic

SEEDS = (101, 202, 303)


def _small(cell_name):
    bench = run.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == cell_name)
    config = copy.deepcopy(run.load_config(bench, cell["config"]))
    config["num_points"], config["depth"] = 100000, 6
    mix = traffic.load(cell["traffic"])
    if mix["loop"] == "closed":
        mix["pool"] = 2
    else:
        mix["sample_units"] = 400
    return config, mix


@pytest.mark.parametrize("cell", ["cubby_t3.traj_batch",
                                  "dresser_t3.traj_batch",
                                  "dresser_t3.edge_serve"])
@pytest.mark.parametrize("seed", SEEDS)
def test_bfloat16_control_is_not_correct(cell, seed):
    config, mix = _small(cell)
    out = control.control(config, mix, seed, seconds=20.0)
    assert not out["correct"], out


@pytest.mark.parametrize("seed", SEEDS)
def test_float32_reference_is_correct(seed):
    config, mix = _small("cubby_t3.traj_batch")
    points, obbs = control.compared_obbs(config, mix, seed, 20.0)
    vox = reference.VoxelScene(points, config["depth"])
    sep = vox.separation(*obbs)
    f32 = vox.separation(*obbs, dtype=np.float32)
    ok, checks = compare.judge(dict(compare.readings(f32 <= 0, sep),
                                    unanswered=0))
    assert ok, checks
