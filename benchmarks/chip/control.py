#!/usr/bin/env python3
"""The control of ``correct``: the reference in bfloat16, in the program's
place, on the answers a run of the cell compares.

    python3 benchmarks/chip/control.py --workload cubby_t3.traj_batch \
        --seeds 11,12,13 --seconds 20

The configurations state float32 geometry; bfloat16 is the precision
below.  For each seed this draws what a run of the cell draws (the cloud,
the batches or requests, the sample compared), gives every compared OBB
the verdict of the bfloat16 separation (:mod:`reference`), and prints the
numbers :mod:`compare` reads from it beside their limits.  The control has
to come out not correct.  It needs no chip: it runs the reference, not the
program.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import ml_dtypes
import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import compare  # noqa: E402
import reference  # noqa: E402
import scenes  # noqa: E402
import traffic  # noqa: E402


def compared_obbs(config: dict, mix: dict, seed: int, seconds: float):
    """The cloud and the (center, half, rot) of every OBB a run of this
    seed compares, drawn as :func:`run.run_cell` draws them (every sampled
    request taken as answered)."""
    seq = np.random.SeedSequence(seed % 2**64)
    rng_scene, rng_traffic, rng_sample, _ = (np.random.default_rng(s)
                                             for s in seq.spawn(4))
    points = scenes.surface_points(config["boxes"], config["num_points"],
                                   rng_scene)
    if mix["loop"] == "closed":
        c, h, r = traffic.units(mix, mix["pool"], rng_traffic)
    else:
        n = len(traffic.arrivals(mix, seconds, rng_traffic))
        c, h, r = traffic.units(mix, n, rng_traffic)
        pick = np.sort(rng_sample.choice(n, min(mix["sample_units"], n),
                                         replace=False))
        c, h, r = c[pick], h[pick], r[pick]
    return points, (c.reshape(-1, 3), h.reshape(-1, 3), r.reshape(-1, 3, 3))


def control(config: dict, mix: dict, seed: int, seconds: float) -> dict:
    points, obbs = compared_obbs(config, mix, seed, seconds)
    vox = reference.VoxelScene(points, config["depth"])
    sep = vox.separation(*obbs)
    low = vox.separation(*obbs, dtype=ml_dtypes.bfloat16)
    vals = compare.readings(low <= 0, sep)
    vals["unanswered"] = 0
    correct, checks = compare.judge(vals)
    return {"seed": seed, "obbs": len(sep), "flipped": int(
        ((low <= 0) != (sep <= 0)).sum()), "correct": correct,
        "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    root = HERE.parents[1]
    with open(root / "BENCHMARK.json") as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(root / entry["file"]) as f:
        config = json.load(f)
    mix = traffic.load(cell["traffic"])
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(control(config, mix, seed, args.seconds)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
