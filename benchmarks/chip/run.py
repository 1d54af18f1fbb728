#!/usr/bin/env python3
"""Chip benchmark of the collision engine: one run of one cell.

    python3 benchmarks/chip/run.py --workload cubby_t3.traj_batch \
        --seed 7 --seconds 20 --trace 0

A cell of ``BENCHMARK.json`` names a configuration (``configs/<name>.json``:
the scene, the engine settings, the service settings) and a traffic mix
(``traffic/<name>.json``, read by :mod:`traffic`).  Every metric is read by
its own reader, ``metrics/<metric name>.py``, from the run's record; with
``--trace 0`` the cell's end-to-end metrics are printed, with ``--trace 1``
the run is traced and its per-layer metrics are printed.

A run builds the scene and its octree from the seed, builds the engine,
warms every shape its traffic uses (set-up, ``setup_s``), runs the window
for ``--seconds``, reads the device's memory peak, and then checks every
answer it keeps against the plain reference (:mod:`reference`,
:mod:`compare`).  The last line of standard output is one JSON object;
the last lines of standard error are the numbers compared, each beside its
limit.  Without a TPU, or with fewer chips than the cell asks for, the run
prints no result and exits with code 3.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import List, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

import compare  # noqa: E402
import reference  # noqa: E402
import roofline  # noqa: E402
import scenes  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402
from repro.core.geometry import OBBs  # noqa: E402
from repro.core.octree import build_octree  # noqa: E402
from repro.engine import (CollisionEngine, EngineConfig,  # noqa: E402
                          RequestBatcher, ServiceError, plan_queries)
from repro.engine.plan import PlanValidationError  # noqa: E402

#: Seconds past the window's close that an open loop waits for answers.
DRAIN_S = 60.0
#: Engine counters a result reports, summed over the window (the batcher's
#: totals include its warm-up request).
ENGINE_FIELDS = ("nodes_traversed", "escalations", "ref_arm_fallbacks",
                 "frontier_overflow", "pad_queries", "rejected")
#: Kernels whose device time the trace reduction sums, by a substring of
#: their operation's HLO text.  The program's Pallas calls carry no name
#: yet, so the megakernel is found as the one TPU custom call of the
#: persistent engine's traversal; no other Pallas kernel runs in a cell.
KERNELS = {"persist": 'custom_call_target="tpu_custom_call"'}


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def load_config(bench: dict, name: str, root: Path = ROOT) -> dict:
    entry = next(c for c in bench["configs"] if c["name"] == name)
    with open(root / entry["file"]) as f:
        return json.load(f)


def cell_metrics(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The metrics a run of ``cell`` prints: end-to-end without a trace,
    per-layer with one.  A metric without ``workloads`` belongs to every
    cell that reports the end-to-end metric it moves (or, end-to-end, to
    every cell)."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class _CompileCount:
    """Programs lowered while ``active``: each is a compile or a fetch from
    the persistent cache, and none may happen inside the window."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration_secs, **kwargs):
        if self.active and event == self.EVENT:
            self.count += 1


_COMPILES: Optional[_CompileCount] = None


def compile_counter() -> _CompileCount:
    global _COMPILES
    if _COMPILES is None:
        _COMPILES = _CompileCount()
    return _COMPILES


@dataclasses.dataclass
class Run:
    """What one run recorded; the metric readers read it."""

    setup_s: float
    unit_obbs: int
    level_widths: List[int]
    device_kind: str
    window_s: float = 0.0
    calls: list = dataclasses.field(default_factory=list)  # (t0, t1)
    latency_s: Optional[np.ndarray] = None    # every request, failed ones
    #                                           at the drain bound
    lag_s: Optional[np.ndarray] = None        # send time - due time
    wait_s: Optional[np.ndarray] = None       # batcher queue wait, answered
    answered: int = 0
    launches: int = 0
    trace: Optional[dict] = None


def _plan(c, h, r):
    return plan_queries(OBBs(center=c, half=h, rot=r))


def _start_trace(trace_dir: str):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def closed_loop(engine, pool, seconds: float):
    """One client, back to back, cycling the pool.  Returns the calls'
    (unit, t0, t1, verdict, counters) and the window's length."""
    c, h, r = pool
    calls = []
    k = 0
    t_begin = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            i = k % len(c)
            with jax.profiler.TraceAnnotation("bench.execute"):
                t0 = time.perf_counter()
                verdict, counters = engine.execute(
                    _plan(c[i], h[i], r[i]))
                t1 = time.perf_counter()
            calls.append((i, t0, t1, verdict, counters))
            k += 1
            if t1 - t_begin >= seconds:
                break
    return calls, calls[-1][2] - t_begin


@dataclasses.dataclass
class OpenLoop:
    """Per request of an open loop: when it was due and sent, its latency
    parts (NaN unless answered), whether it failed typed or never got an
    answer; the verdicts of the requests kept; the drain bound."""

    due: np.ndarray
    sent: np.ndarray
    total_s: np.ndarray
    wait_s: np.ndarray
    failed: np.ndarray
    unanswered: np.ndarray
    verdicts: dict
    bound: float

    @property
    def answered(self) -> np.ndarray:
        return ~(self.failed | self.unanswered)

    def latency_s(self) -> np.ndarray:
        """From due to answer; a request without one counts at the
        drain bound."""
        return np.where(self.answered, self.sent - self.due + self.total_s,
                        self.bound - self.due)


def open_loop(batcher, reqs, offsets, seconds: float, keep=()) -> OpenLoop:
    """Send request ``i`` (OBB arrays ``reqs[k][i]``) when it is due,
    whatever the service does; then wait for every answer, up to
    :data:`DRAIN_S` past the window's close.  Answers are taken as they
    come, as a client would, keeping only their timings and, for the
    indices in ``keep``, their verdicts: a load generator that held every
    request and ticket to the end would double the interpreter's heap and
    with it the garbage collector's pauses in the service under test."""
    n = len(offsets)
    c, h, r = reqs
    nan = np.full(n, np.nan)
    out = OpenLoop(due=np.zeros(n), sent=np.zeros(n), total_s=nan,
                   wait_s=nan.copy(), failed=np.zeros(n, bool),
                   unanswered=np.zeros(n, bool), verdicts={}, bound=0.0)
    keep = set(int(i) for i in keep)
    pending: collections.deque = collections.deque()

    def settle(i, ticket, timeout):
        try:
            verdict, stats = ticket.result(timeout=timeout)
        except TimeoutError:
            out.unanswered[i] = True
            return
        except ServiceError:
            out.failed[i] = True
            return
        out.total_s[i], out.wait_s[i] = stats.total_s, stats.wait_s
        if i in keep:
            out.verdicts[i] = verdict

    with jax.profiler.TraceAnnotation("bench.window"):
        t_begin = time.perf_counter()
        out.due[:] = t_begin + offsets
        for i in range(n):
            while pending and pending[0][1].done():
                settle(*pending.popleft(), 0.0)
            delay = out.due[i] - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            out.sent[i] = time.perf_counter()
            try:
                pending.append((i, batcher.submit(_plan(c[i], h[i], r[i]))))
            except (ServiceError, PlanValidationError):
                out.failed[i] = True
        out.bound = t_begin + seconds + DRAIN_S
        while pending:
            i, ticket = pending.popleft()
            settle(i, ticket, max(0.0, out.bound - time.perf_counter()))
    return out


def build(config: dict, rng: np.random.Generator):
    """The scene's cloud from the seed, its octree and engine, and the
    reference's voxel grid of the same cloud."""
    points = scenes.surface_points(config["boxes"], config["num_points"],
                                   rng)
    tree = build_octree(points, depth=config["depth"])
    engine = CollisionEngine(tree, EngineConfig(**config["engine"]))
    return engine, reference.VoxelScene(points, config["depth"]), tree


def warm_service(engine, vox, config: dict, mix: dict,
                 rng: np.random.Generator) -> RequestBatcher:
    """Warm every pool width the batcher can launch and start it.

    The widths are the pow2 buckets from 64 up to the one holding
    ``max_batch`` plus one more request.  Each is warmed with whole pools
    of this traffic: first one of the heaviest requests of a draw of
    ``warm_draw``, by the voxels near their OBBs, then ``warm_pools``
    drawn at random.  The engine keeps, per width, the largest frontier
    capacity a launch needed; this brings it to what the window's
    heaviest launches ask, so that none of them grows the frontier, and
    compiles, in the window.
    """
    svc = config["service"]
    u = traffic.unit_obbs(mix)
    widths = [64]
    while widths[-1] < svc["max_batch"] + u - 1:
        widths.append(widths[-1] * 2)
    k = -(-widths[-1] // u)
    warm = traffic.units(mix, mix["warm_draw"], rng)
    heavy = np.argsort(-vox.near_voxels(
        *(a.reshape(-1, *a.shape[2:]) for a in warm)
    ).reshape(len(warm[0]), u).sum(1), kind="stable")
    picks = [heavy[:k]] + [rng.choice(len(warm[0]), k, replace=False)
                           for _ in range(mix["warm_pools"])]
    for w in widths:
        for sel in picks:
            c, h, r = (a[sel].reshape(-1, *a.shape[2:])[:w] for a in warm)
            engine.execute(_plan(c, h, r))
    return RequestBatcher(engine, **svc)


def open_requests(mix: dict, seconds: float, rng: np.random.Generator):
    """Due times and OBB arrays of an open loop's requests."""
    offsets = traffic.arrivals(mix, seconds, rng)
    return offsets, traffic.units(mix, len(offsets), rng)


def run_cell(bench: dict, cell: dict, config: dict, mix: dict, seed: int,
             seconds: float, trace: bool, t_process: float) -> dict:
    """Run one cell once; returns the result object (see module doc)."""
    counter = compile_counter()
    seq = np.random.SeedSequence(seed % 2**64)
    rng_scene, rng_traffic, rng_sample, rng_warm = (
        np.random.default_rng(s) for s in seq.spawn(4))

    # -- set-up: scene, engine, the traffic's own shapes -------------------
    marks = [("start", t_process), ("jax", time.perf_counter())]
    engine, vox, tree = build(config, rng_scene)
    marks.append(("scene", time.perf_counter()))
    widths = [len(level.codes) for level in tree.levels]
    u = traffic.unit_obbs(mix)
    dev = jax.devices()
    run = Run(setup_s=0.0, unit_obbs=u,
              level_widths=widths, device_kind=dev[0].device_kind)
    closed = mix["loop"] == "closed"
    batcher = None
    if closed:
        pool = traffic.units(mix, mix["pool"], rng_traffic)
        marks.append(("traffic", time.perf_counter()))
        for i in range(mix["pool"]):
            engine.execute(_plan(pool[0][i], pool[1][i], pool[2][i]))
    elif mix["loop"] == "open":
        offsets, reqs = open_requests(mix, seconds, rng_traffic)
        n = len(offsets)
        sample = np.sort(rng_sample.choice(n, min(mix["sample_units"], n),
                                           replace=False))
        marks.append(("traffic", time.perf_counter()))
        batcher = warm_service(engine, vox, config, mix, rng_warm)
        batcher.submit(_plan(reqs[0][0], reqs[1][0], reqs[2][0])).result(
            timeout=600)
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")

    # -- the window -----------------------------------------------------------
    gc.collect()            # the window inherits none of set-up's garbage
    tmp = None
    if trace:
        tmp = tempfile.mkdtemp(prefix="bench_trace_")
        _start_trace(tmp)
    counter.active = True
    run.setup_s = time.perf_counter() - t_process
    marks.append(("warm", t_process + run.setup_s))
    if closed:
        calls, run.window_s = closed_loop(engine, pool, seconds)
        run.calls = [(t0, t1) for _, t0, t1, _, _ in calls]
        attempted, failed = len(calls), 0
        work = [c for *_, c in calls]
    else:
        launches0 = batcher.num_launches
        loop = open_loop(batcher, reqs, offsets, seconds, sample)
        run.window_s = seconds
        run.launches = batcher.num_launches - launches0
        ok = loop.answered
        run.latency_s = loop.latency_s()
        run.lag_s = loop.sent - loop.due
        run.wait_s = loop.wait_s[ok]
        run.answered = int(ok.sum())
        attempted, failed = n, int((~ok).sum())
        work = [batcher.totals]
    counter.active = False
    if trace:
        jax.profiler.stop_trace()
    mem = [d.memory_stats() or {} for d in dev]
    peak = max(m.get("peak_bytes_in_use", 0) for m in mem)
    if batcher is not None:
        batcher.close()
    del engine, batcher

    # -- per-layer: the trace ------------------------------------------------
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev), "memory_peak_bytes": int(peak)}
    breakdown = None
    if trace:
        path = trace_reduce.find_xplane(tmp)
        ops, host = trace_reduce.load(path)
        run.trace = trace_reduce.reduce_events(ops, host, KERNELS)
        shutil.rmtree(tmp, ignore_errors=True)
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        breakdown = {"device_ops": run.trace["device_ops"],
                     "idle_gaps": run.trace["idle_gaps"]}

    metrics = {}
    for m in cell_metrics(bench, cell["name"], trace):
        value = reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        elif not trace:
            raise RuntimeError(f"end-to-end metric {m['name']} read nothing")

    # -- correct: every kept answer against the reference --------------------
    if closed:
        sep = vox.separation(pool[0].reshape(-1, 3), pool[1].reshape(-1, 3),
                             pool[2].reshape(-1, 3, 3)).reshape(-1, u)
        by_unit: dict = {}
        for i, _, _, v, _ in calls:
            by_unit.setdefault(i, []).append(np.asarray(v))
        vals = {"missed_m": 0.0, "false_hit_m": 0.0}
        for i, vs in by_unit.items():
            got = compare.readings(np.stack(vs).reshape(-1),
                                   np.tile(sep[i], len(vs)))
            vals = {k: max(vals[k], got[k]) for k in vals}
        vals["unanswered"] = 0
    else:
        idx = np.array(sorted(loop.verdicts), dtype=np.int64)
        sep = vox.separation(reqs[0][idx].reshape(-1, 3),
                             reqs[1][idx].reshape(-1, 3),
                             reqs[2][idx].reshape(-1, 3, 3))
        got = np.concatenate([np.asarray(loop.verdicts[i]).reshape(-1)
                              for i in idx] or [np.zeros(0, bool)])
        vals = compare.readings(got, sep)
        vals["unanswered"] = int(loop.unanswered.sum())
    correct, checks = compare.judge(vals)

    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device,
              "window_compiles": counter.count,
              "setup_phases": {b[0]: b[1] - a[1]
                               for a, b in zip(marks, marks[1:])},
              "engine": {
                  f: sum(getattr(c, f) for c in work) for f in ENGINE_FIELDS}}
    counter.count = 0
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def on_chip(cell: dict) -> bool:
    """Turn on the persistent compile cache (every program, however quick
    to compile) and check for the cell's chips; False, said on standard
    error, without a TPU or with too few chips."""
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX sees {devices[0].platform} devices",
              file=sys.stderr)
        return False
    if len(devices) < cell["chips"]:
        print(f"cell needs {cell['chips']} chips, JAX sees {len(devices)}",
              file=sys.stderr)
        return False
    roofline.peaks(devices[0].device_kind)      # unknown chip: an error
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    config = load_config(bench, cell["config"])
    mix = traffic.load(cell["traffic"])

    if not on_chip(cell):
        return 3
    result = run_cell(bench, cell, config, mix, args.seed, args.seconds,
                      bool(args.trace), T_PROCESS)
    print(f"window compiles: {result['window_compiles']}, engine "
          f"{json.dumps(result['engine'])}", file=sys.stderr)
    for name, (value, limit) in result["checks"].items():
        print(f"check {name} {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
