#!/usr/bin/env python3
"""The control of the planning cell's ``correct``: the program run with its
matmuls in the chip's default precision, one bfloat16 pass each, in place
of the float32-accurate precision the configuration states.

    python3 benchmarks/chip/planner_control.py \
        --workload mpinets_cubby.plan_batch --seeds 11,12,13 --seconds 5

Each seed is one run of the cell (``run.run_cell``) with the configuration's
``model.precision`` set to ``default``; it prints the run's checks beside
their limits as one JSON line.  The control has to come out not correct
(``policy_err`` over its limit) where the sound runs come out correct.  It
needs the chip: on the CPU the default precision is float32.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
import time

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    bench = run.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    config = copy.deepcopy(run.load_config(bench, cell["config"]))
    config["model"]["precision"] = "default"
    mix = run.traffic.load(cell["traffic"])
    if not run.on_chip(cell):
        return 3
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.run_cell(bench, cell, config, mix, seed, args.seconds,
                           False, time.perf_counter())
        print(json.dumps({"seed": seed, "precision": "default",
                          "correct": out["correct"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
