#!/usr/bin/env python3
"""Chip benchmark of the collision engine: one run of one cell.

    python3 benchmarks/chip/run.py --workload cubby_t3.traj_batch \
        --seed 7 --seconds 20 --trace 0

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix; with
``--trace 0`` the run prints the cell's end-to-end metrics, with
``--trace 1`` it is traced and prints its per-layer metrics.  Everything
that belongs to one cell is a file of its own, found by name, so a new
cell comes in as new files and new entries of ``BENCHMARK.json``:

* ``configs/<config>.json``: the configuration as it is run;
* ``traffic/<traffic>.json``: the mix's parameters; its ``loop`` names
  the loop that drives it;
* ``loops/<loop>.py``: the loop, a class ``Loop``.  ``Loop(run, config,
  mix, seed, seconds, mark)`` is the set-up: it builds from the
  configuration and the seed, warms every shape the window will use, and
  may call ``mark(phase)`` to time its phases.  ``window(run, seconds)``
  drives the timed window, inside a ``bench.window`` span, records into
  the :class:`Run` whatever its readers read (any attribute), and returns
  ``{"attempted": n, "failed": n}`` plus any further keys for the result.
  ``release()`` frees the program's state; ``check()`` compares every
  answer it kept with a plain reference and returns ``{name: [value,
  limit]}``, its limits in a file of its own (the collision loops':
  :mod:`compare`).  A run is correct when every value is within its limit;
* ``metrics/<metric>.py``: one reader per metric, ``read(run)``, which
  returns the number or ``None`` where it finds nothing to read;
* for a kernel with a roofline, its operations and bytes in a module of
  its own beside this file, which its ``<kernel>_roofline`` reader imports
  beside :func:`roofline.peaks`.

The trace reduction (:mod:`trace_reduce`) gives every Pallas kernel's
device time and event count under the kernel's name (``run.trace
["kernel_s"][name]``, ``["kernel_events"][name]``), the ``bench.*`` spans
and the device's busy time, so a new kernel's or span's reader needs no
edit here.

This file keeps what every cell shares: the process clock and
``setup_s`` (process start to the window), the count of programs lowered
inside the window (there must be none), the trace, the device's memory
peak (read before the loop releases its state and checks), the metric
readers, the result and the checks.  The last line of standard output is
one JSON object; the last lines of standard error are the numbers
compared, each beside its limit.  Without a TPU, or with fewer chips than
the cell asks for, the run prints no result and exits with code 3.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
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
import roofline  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402

LOOPS = HERE / "loops"
METRICS = HERE / "metrics"
#: Kernels the readers know by a short name, and the name of the Pallas
#: call each stands for; every other kernel goes by its own name.
KERNELS = {"persist": "persist_traverse"}
#: Keys of a result that this file writes; the loop's own follow them.
_RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device",
                "window_compiles", "setup_phases", "breakdown", "checks")


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


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def reader(name: str):
    return _module(METRICS / f"{name}.py",
                   "metric_" + name.replace(".", "_")).read


def load_loop(name: str):
    """The module ``loops/<name>.py``; an unknown loop is an error that
    names the file looked for."""
    path = LOOPS / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"unknown loop {name!r}: no file {path}")
    return _module(path, "loop_" + name)


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
    """What one run recorded; the metric readers read it.  This file sets
    ``setup_s``, ``device_kind`` and ``trace``; the loop sets the rest,
    and may add attributes of its own for its own readers."""

    setup_s: float = 0.0
    device_kind: str = ""
    window_s: float = 0.0
    trace: Optional[dict] = None
    # Set by the collision loops:
    unit_obbs: int = 0                        # OBBs a unit
    level_widths: List[int] = dataclasses.field(default_factory=list)
    calls: list = dataclasses.field(default_factory=list)  # (t0, t1)
    latency_s: Optional[np.ndarray] = None    # every request, failed ones
    #                                           at the drain bound
    lag_s: Optional[np.ndarray] = None        # send time - due time
    wait_s: Optional[np.ndarray] = None       # batcher queue wait, answered
    answered: int = 0
    launches: int = 0


def _start_trace(trace_dir: str):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def run_cell(bench: dict, cell: dict, config: dict, mix: dict, seed: int,
             seconds: float, trace: bool, t_process: float) -> dict:
    """Run one cell once; returns the result object (see module doc)."""
    counter = compile_counter()
    dev = jax.devices()
    run = Run(device_kind=dev[0].device_kind)
    marks = [("start", t_process), ("jax", time.perf_counter())]

    def mark(phase: str):
        marks.append((phase, time.perf_counter()))

    # -- set-up: what the cell's loop builds and warms ----------------------
    loop = load_loop(mix["loop"]).Loop(run, config, mix, seed, seconds, mark)

    # -- the window -----------------------------------------------------------
    gc.collect()            # the window inherits none of set-up's garbage
    tmp = None
    if trace:
        tmp = tempfile.mkdtemp(prefix="bench_trace_")
        _start_trace(tmp)
    counter.active = True
    run.setup_s = time.perf_counter() - t_process
    marks.append(("warm", t_process + run.setup_s))
    done = dict(loop.window(run, seconds))
    counter.active = False
    if trace:
        jax.profiler.stop_trace()
    mem = [d.memory_stats() or {} for d in dev]
    peak = max(m.get("peak_bytes_in_use", 0) for m in mem)
    loop.release()

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
    checks = loop.check()

    result = {"correct": compare.within(checks),
              "attempted": int(done.pop("attempted")),
              "failed": int(done.pop("failed")), "metrics": metrics,
              "device": device, "window_compiles": counter.count,
              "setup_phases": {b[0]: b[1] - a[1]
                               for a, b in zip(marks, marks[1:])},
              **done}
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
    notes = {k: v for k, v in result.items() if k not in _RESULT_KEYS}
    print(f"window compiles: {result['window_compiles']}"
          + "".join(f", {k} {json.dumps(v)}" for k, v in notes.items()),
          file=sys.stderr)
    for name, (value, limit) in result["checks"].items():
        print(f"check {name} {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
