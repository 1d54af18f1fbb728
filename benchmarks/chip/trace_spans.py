#!/usr/bin/env python3
"""One traced run of a cell, as ``run.py --trace 1`` makes it, that also
reduces the program's own spans (:mod:`program_spans`) and writes them to
a JSON file:

    python3 benchmarks/chip/trace_spans.py --out spans.json \
        --workload cubby_t3.traj_batch --seed 7 --seconds 20

The run is ``run.py``'s own (:func:`run.run_cell`, traced), and its
result line is printed as ``run.py`` prints it.  The file holds that
result, the program's spans with the device busy time inside each,
``idle_by_phase``,
the readings of :func:`program_spans.stage_ms`, ``sync_ms`` and
``launch_host_ms``, the three slowest launches and, for the host events of
most time, where that time lies among the program's phases.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import program_spans  # noqa: E402
import trace_reduce  # noqa: E402

def _reducer(reduce_events, into: dict):
    """``trace_reduce.reduce_events`` that also reduces the program's spans
    of the same events into ``into``."""
    def reduce(device, host, kernels=None):
        out = reduce_events(device, host, kernels)
        got = program_spans.reduce_program(device, host)
        spans = got["spans"]
        into.update(
            got, stage_ms=program_spans.stage_ms(spans),
            sync_ms=program_spans.sync_ms(spans),
            launch_host_ms=program_spans.launch_host_ms(spans),
            slowest_launches=program_spans.slowest_launches(device, host),
            host_activity=program_spans.host_activity_by_phase(host))
        return out
    return reduce


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    reduced: dict = {}
    trace_reduce.reduce_events = _reducer(trace_reduce.reduce_events,
                                          reduced)
    import run
    bench = run.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    config = run.load_config(bench, cell["config"])
    mix = run.traffic.load(cell["traffic"])
    if not run.on_chip(cell):
        return 3
    result = run.run_cell(bench, cell, config, mix, args.seed, args.seconds,
                          True, run.T_PROCESS)
    print(json.dumps(result), flush=True)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"result": result, **reduced}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
