"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy time, kernel
time, host spans and the host's activity in the device's idle gaps.

Device operations are the events of each TPU plane's "XLA Ops" line.
Host spans are the benchmark's own ``jax.profiler.TraceAnnotation`` events
(named ``bench.*``) on the host plane, which shares the device events'
clock.  Everything is reduced inside the ``bench.window`` span.

A kernel is known by its operation's name: the HLO instruction's name
without its ``.N`` suffix, which for a Pallas call is the ``name=`` it
was given (``%persist_traverse.1 = ... custom-call(...)`` is
``persist_traverse``).  Every Pallas call (custom-call target
``tpu_custom_call``) in the window gets its device time and event count
under that name, with no list of kernels to keep.

:func:`reduce_events` works on plain ``(name, text, start_ns, end_ns)``
tuples, so ``tests/test_trace_reduce.py`` checks it on a trace recorded on
the CPU, whose XLA operations appear as host events carrying ``hlo_op``.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: Host span that bounds the traced window.
WINDOW_SPAN = "bench.window"
#: Prefix of every host span the benchmark records.
SPAN_PREFIX = "bench."
#: Idle gaps attributed to host activity, longest first.
MAX_GAPS = 4000
#: What marks a device operation as a Pallas call on the TPU.
PALLAS_CALL = 'custom_call_target="tpu_custom_call"'

Event = Tuple[str, str, float, float]     # name, searchable text, start, end


def find_xplane(log_dir: str) -> str:
    """The newest trace under ``log_dir``."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def short_name(name: str) -> str:
    """A device operation's event name is its whole HLO instruction; keep
    the instruction's name, its opcode and any custom-call target."""
    lhs, eq, rhs = name.partition(" = ")
    if not eq:
        return name
    op = re.search(r"\s([a-z][a-z0-9_\-]*)\(", " " + rhs)
    target = re.search(r'custom_call_target="([^"]+)"', rhs)
    return " ".join([lhs] + ([op.group(1)] if op else [])
                    + ([target.group(1)] if target else []))


def op_name(name: str) -> str:
    """The instruction's name of a device operation's event name, without
    its ``%`` and ``.N`` suffix."""
    head = (name.split() or [""])[0]
    return re.sub(r"\.\d+$", "", head.lstrip("%"))


def _text(ev) -> str:
    parts = [ev.name]
    for k, v in ev.stats:
        if isinstance(v, str):
            parts.append(f"{k}={v}")
    return " ".join(parts)


def load(path: str):
    """(device events per TPU plane, host events per host thread)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device: List[List[Event]] = []
    host: List[List[Event]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device.append([(short_name(e.name), _text(e), e.start_ns,
                                    e.start_ns + e.duration_ns)
                                   for e in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.append([(e.name, e.name, e.start_ns,
                              e.start_ns + e.duration_ns)
                             for e in line.events if e.duration_ns > 0])
    return [d for d in device if d], host


def cpu_ops(path: str) -> List[Event]:
    """XLA operations of a CPU trace (host events that carry ``hlo_op``);
    for checking the reducer without a chip."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if any(k == "hlo_op" for k, _ in e.stats):
                    out.append((e.name, _text(e), e.start_ns,
                                e.start_ns + e.duration_ns))
    return out


def merge(intervals: Iterable[Tuple[float, float]], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """Union of intervals clipped to [lo, hi], sorted and disjoint."""
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged: Sequence[Tuple[float, float]], lo: float, hi: float
            ) -> float:
    """Length of [lo, hi] covered by disjoint sorted ``merged``."""
    i = bisect.bisect_right(merged, (lo, float("inf"))) - 1
    i = max(i, 0)
    tot = 0.0
    while i < len(merged) and merged[i][0] < hi:
        s, e = merged[i]
        tot += max(0.0, min(e, hi) - max(s, lo))
        i += 1
    return tot


class _HostIndex:
    """Innermost host event containing a time, over every host thread."""

    def __init__(self, host: Sequence[Sequence[Event]]):
        self.lines = []
        for evs in host:
            evs = sorted((s, e, n) for n, _, s, e in evs
                         if not n.startswith(WINDOW_SPAN))
            if evs:
                self.lines.append(([s for s, _, _ in evs], evs))

    def at(self, t: float) -> str:
        best = None
        for starts, evs in self.lines:
            i = bisect.bisect_right(starts, t) - 1
            for k in range(i, max(i - 64, -1), -1):
                s, e, n = evs[k]
                if e >= t:
                    if best is None or e - s < best[0]:
                        best = (e - s, n)
                    break
        return "no host activity" if best is None else best[1]


def reduce_events(device: Sequence[Sequence[Event]],
                  host: Sequence[Sequence[Event]],
                  kernels: Optional[Dict[str, str]] = None) -> dict:
    """Reduce one traced window.

    ``device`` holds one event list per chip; ``kernels`` maps a short
    name a reader uses to the name of the operation it stands for
    (:func:`op_name`).  Returns seconds: ``window_s``, ``busy_s`` (mean
    over chips), ``kernel_s`` and ``kernel_events`` of every Pallas call
    by its name and of every name in ``kernels``, ``spans`` ({name:
    [(start, end, device busy inside)]}), ``device_ops`` and
    ``idle_gaps`` (top 10).
    """
    spans: Dict[str, List[Tuple[float, float]]] = {}
    for evs in host:
        for n, _, s, e in evs:
            if n.startswith(SPAN_PREFIX):
                spans.setdefault(n, []).append((s, e))
    if WINDOW_SPAN not in spans:
        raise RuntimeError(f"no {WINDOW_SPAN} span in the trace")
    lo, hi = spans.pop(WINDOW_SPAN)[0]
    if not device or not any(device):
        raise RuntimeError("no device operations in the trace")
    merged = [merge(((s, e) for _, _, s, e in evs), lo, hi)
              for evs in device]
    busy = sum(sum(e - s for s, e in m) for m in merged) / len(merged)
    ops: Dict[str, float] = {}
    kernels = kernels or {}
    ktime = {k: 0.0 for k in kernels}
    kcount = {k: 0 for k in kernels}
    for evs in device:
        for name, text, s, e in evs:
            d = min(e, hi) - max(s, lo)
            if d <= 0:
                continue
            ops[name] = ops.get(name, 0.0) + d
            op = op_name(name)
            keys = [k for k, n in kernels.items() if n == op]
            if PALLAS_CALL in text and op not in kernels:
                keys.append(op)
            for k in keys:
                ktime[k] = ktime.get(k, 0.0) + d
                kcount[k] = kcount.get(k, 0) + 1
    first = merged[0]
    span_busy = {n: [(s, e, covered(first, s, e)) for s, e in v]
                 for n, v in spans.items()}
    gaps = []
    prev = lo
    for s, e in first + [(hi, hi)]:
        if s > prev:
            gaps.append((s - prev, prev, s))
        prev = max(prev, e)
    gaps.sort(reverse=True)
    index = _HostIndex(host)
    by_activity: Dict[str, float] = {}
    for d, s, e in gaps[:MAX_GAPS]:
        name = index.at((s + e) / 2)
        by_activity[name] = by_activity.get(name, 0.0) + d
    rest = sum(d for d, _, _ in gaps[MAX_GAPS:])
    if rest:
        by_activity["shorter gaps"] = rest
    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": busy * ns,
        "kernel_s": {k: v * ns for k, v in ktime.items()},
        "kernel_events": kcount,
        "spans": {n: [(s * ns, e * ns, b * ns) for s, e, b in v]
                  for n, v in span_busy.items()},
        "device_ops": [[n, v * ns] for n, v in
                       sorted(ops.items(), key=lambda x: -x[1])[:10]],
        "idle_gaps": [[n, v * ns] for n, v in
                      sorted(by_activity.items(), key=lambda x: -x[1])[:10]],
    }
