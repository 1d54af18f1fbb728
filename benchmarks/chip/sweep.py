#!/usr/bin/env python3
"""Find the highest rate an open-loop cell's service sustains.

    python3 benchmarks/chip/sweep.py --workload dresser_t3.edge_serve \
        --seed 5 --seconds 10 --rates 500,1000,2000,4000 --out sweep.json

One set-up (scene, engine, warmed batcher), then one open-loop window per
rate, lowest first, each with fresh requests.  Per rate it prints the
offered and answered request rates, latency p50/p95/p99 from the due
time, the load generator's lag, the batcher's queue wait and requests per
launch, and the backlog trend: the median latency of the last fifth of
the window's requests over that of the first fifth.  A rate is sustained
when every request is answered and the trend stays under 2; the cell's
traffic runs at about four fifths of the highest sustained rate, a number
written into its traffic file.  Needs a TPU, as a run does.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import run  # first: it puts the program's sources on the import path
import collision  # noqa: E402
import traffic  # noqa: E402

OPEN = run.load_loop("open")


def window_row(batcher, mix: dict, rate: float, seconds: float,
               rng: np.random.Generator) -> dict:
    mix = dict(mix, rate_per_s=rate)
    offsets, reqs = OPEN.requests(mix, seconds, rng)
    launches0 = batcher.num_launches
    counter = run.compile_counter()
    counter.active, counter.count = True, 0
    loop = OPEN.open_loop(batcher, reqs, offsets, seconds)
    counter.active = False
    ok = loop.answered
    lat = loop.latency_s() * 1e3
    fifth = max(1, len(lat) // 5)
    answered_by = max((loop.sent + loop.total_s)[ok].max() - loop.due[0],
                      seconds)
    return {
        "rate_per_s": rate, "requests": len(offsets),
        "answered": int(ok.sum()), "unanswered": int(loop.unanswered.sum()),
        "answered_per_s": float(ok.sum() / answered_by),
        "p50_ms": float(np.percentile(lat, 50)),
        "p95_ms": float(np.percentile(lat, 95)),
        "p99_ms": float(np.percentile(lat, 99)),
        "lag_p95_ms": float(np.percentile(loop.sent - loop.due, 95) * 1e3),
        "wait_p95_ms": float(np.percentile(loop.wait_s[ok], 95) * 1e3),
        "requests_per_launch": float(
            ok.sum() / max(1, batcher.num_launches - launches0)),
        "trend": float(np.median(lat[-fifth:]) / np.median(lat[:fifth])),
        "window_compiles": counter.count,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated request rates per second")
    ap.add_argument("--out", default=None, help="write the rows here")
    args = ap.parse_args(argv)

    bench = run.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    config = run.load_config(bench, cell["config"])
    mix = traffic.load(cell["traffic"])
    if mix["loop"] != "open":
        print(f"{args.workload} is not an open-loop cell", file=sys.stderr)
        return 2
    if not run.on_chip(cell):
        return 3
    seq = np.random.SeedSequence(args.seed % 2**64)
    rng_scene, rng_traffic, rng_warm = (np.random.default_rng(s)
                                        for s in seq.spawn(3))
    t0 = time.perf_counter()
    engine, vox, _ = collision.build(config, rng_scene)
    batcher = OPEN.warm_service(engine, vox, config, mix, rng_warm)
    print(f"set-up {time.perf_counter() - t0:.1f} s", flush=True)
    rows = []
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            row = window_row(batcher, mix, rate, args.seconds, rng_traffic)
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        batcher.close()
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "seed": args.seed,
                       "seconds": args.seconds, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
