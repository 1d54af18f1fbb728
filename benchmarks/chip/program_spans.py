"""Reduce the program's own profiler spans in a traced window.

The engine and the batcher mark their phases with
``jax.profiler.TraceAnnotation`` spans (:data:`PROGRAM_SPANS`) on the host
plane, whose clock the device trace shares.  This module lays them
against the device's busy time, on the same ``(name, text, start_ns,
end_ns)`` event lists :func:`trace_reduce.load` returns, and inside the
same ``bench.window``:

- :func:`reduce_program`: each program span with the device busy time
  inside it (``spans``, in seconds, shaped as the ``spans`` of
  :func:`trace_reduce.reduce_events`), and ``idle_by_phase``: the
  window's idle device time put down to the deepest program span covering
  each moment of it, with :data:`OUTSIDE` for the rest;
- :func:`stage_ms`, :func:`sync_ms`, :func:`launch_host_ms`: the per-layer
  readings those spans give;
- :func:`by_phase` and :func:`slowest_launches`: where any host activity
  (a runtime event such as ``Transpose``) or a launch's time lies among
  the program's phases.

:mod:`trace_spans` runs a cell through ``run.py`` and writes all of it.
"""
from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Sequence, Tuple

import trace_reduce as tr

#: The engine's and the batcher's span names.
PROGRAM_SPANS = ("engine.execute", "executor.stage", "executor.dispatch",
                 "executor.sync", "batcher.submit", "batcher.coalesce",
                 "batcher.launch", "batcher.pool", "batcher.resolve")
#: Label of time no program span covers.
OUTSIDE = "outside program spans"

Span = Tuple[str, float, float, int, int]    # name, start, end, line, depth


def window(host: Sequence[Sequence[tr.Event]]) -> Tuple[float, float]:
    """Start and end of the ``bench.window`` span."""
    for evs in host:
        for n, _, s, e in evs:
            if n == tr.WINDOW_SPAN:
                return s, e
    raise RuntimeError(f"no {tr.WINDOW_SPAN} span in the trace")


def program_spans(host: Sequence[Sequence[tr.Event]], lo: float, hi: float
                  ) -> List[Span]:
    """Every program span that lies inside [lo, hi], with its host thread
    (line) and its nesting depth among the program spans of that line."""
    out: List[Span] = []
    for k, evs in enumerate(host):
        mine = sorted(((s, -e, n) for n, _, s, e in evs
                       if n in PROGRAM_SPANS and lo <= s and e <= hi))
        stack: List[float] = []
        for s, neg_e, n in mine:
            while stack and stack[-1] <= s:
                stack.pop()
            out.append((n, s, -neg_e, k, len(stack)))
            stack.append(-neg_e)
    return out


def by_phase(targets: Sequence[Tuple[float, float]], spans: Sequence[Span],
             lo: float, hi: float) -> Dict[str, float]:
    """Length of the disjoint sorted ``targets`` inside [lo, hi] put down,
    moment by moment, to the deepest program span covering it (ties to
    the shortest), else to :data:`OUTSIDE`."""
    points = sorted({lo, hi}.union(
        t for _, s, e, _, _ in spans for t in (s, e) if lo <= t <= hi))
    starts: Dict[float, List[int]] = {}
    ends: Dict[float, List[int]] = {}
    for i, (_, s, e, _, _) in enumerate(spans):
        starts.setdefault(s, []).append(i)
        ends.setdefault(e, []).append(i)
    active: Dict[int, Tuple[int, float]] = {}
    out: Dict[str, float] = {}
    for t0, t1 in zip(points, points[1:]):
        for i in ends.get(t0, ()):
            active.pop(i, None)
        for i in starts.get(t0, ()):
            n, s, e, _, d = spans[i]
            if e > t0:
                active[i] = (d, s - e)
        got = tr.covered(targets, t0, t1)
        if got <= 0:
            continue
        label = (spans[max(active, key=active.get)][0] if active
                 else OUTSIDE)
        out[label] = out.get(label, 0.0) + got
    return out


def reduce_program(device: Sequence[Sequence[tr.Event]],
                   host: Sequence[Sequence[tr.Event]]) -> dict:
    """``spans`` ({name: [(start, end, device busy inside)]}, seconds) and
    ``idle_by_phase`` ([[label, seconds]], largest first) of the window;
    device busy is the first chip's, as in
    :func:`trace_reduce.reduce_events`."""
    lo, hi = window(host)
    busy = tr.merge(((s, e) for _, _, s, e in device[0]), lo, hi)
    spans = program_spans(host, lo, hi)
    ns = 1e-9
    out: Dict[str, list] = {}
    for n, s, e, _, _ in sorted(spans, key=lambda x: x[1]):
        out.setdefault(n, []).append(
            (s * ns, e * ns, tr.covered(busy, s, e) * ns))
    idle = []
    prev = lo
    for s, e in busy + [(hi, hi)]:
        if s > prev:
            idle.append((prev, s))
        prev = max(prev, e)
    phases = by_phase(idle, spans, lo, hi)
    return {"spans": out,
            "idle_by_phase": [[n, v * ns] for n, v in
                              sorted(phases.items(), key=lambda x: -x[1])]}


def _calls(spans: dict) -> int:
    return len(spans.get("engine.execute", ()))


def stage_ms(spans: dict) -> Optional[float]:
    """Summed ``executor.stage`` time per ``engine.execute``."""
    if not _calls(spans) or "executor.stage" not in spans:
        return None
    return sum(e - s for s, e, _ in spans["executor.stage"]) / \
        _calls(spans) * 1e3


def sync_ms(spans: dict) -> Optional[float]:
    """``executor.sync`` time the device is not busy in, per
    ``engine.execute``: the device-to-host round trips after the kernel
    ends."""
    if not _calls(spans) or "executor.sync" not in spans:
        return None
    return sum((e - s) - b for s, e, b in spans["executor.sync"]) / \
        _calls(spans) * 1e3


def launch_host_ms(spans: dict) -> Optional[float]:
    """Median over ``batcher.launch`` spans of their time less the device's
    busy time inside: the host cost of one coalesced launch."""
    launches = spans.get("batcher.launch")
    if not launches:
        return None
    return statistics.median((e - s) - b for s, e, b in launches) * 1e3


def _host_time(host: Sequence[Sequence[tr.Event]], lo: float, hi: float
               ) -> Dict[str, List[Tuple[float, float]]]:
    """Per host event name (the program's and the benchmark's spans
    aside), the union of its events' intervals inside [lo, hi]."""
    events: Dict[str, List[Tuple[float, float]]] = {}
    for evs in host:
        for n, _, s, e in evs:
            if (e > lo and s < hi and n not in PROGRAM_SPANS
                    and not n.startswith(tr.SPAN_PREFIX)):
                events.setdefault(n, []).append((s, e))
    return {n: tr.merge(v, lo, hi) for n, v in events.items()}


def _longest(merged: Dict[str, List[Tuple[float, float]]], top: int
             ) -> List[str]:
    total = {n: sum(e - s for s, e in m) for n, m in merged.items()}
    return sorted(total, key=lambda x: -total[x])[:top]


def slowest_launches(device: Sequence[Sequence[tr.Event]],
                     host: Sequence[Sequence[tr.Event]], k: int = 3,
                     top: int = 6) -> List[dict]:
    """The ``k`` longest outermost ``batcher.launch`` spans: each one's
    length, device busy time, its time put down to the deepest program
    span of its own thread, and the ``top`` host event names of most time
    inside it, on any thread (seconds)."""
    lo, hi = window(host)
    busy = tr.merge(((s, e) for _, _, s, e in device[0]), lo, hi)
    spans = program_spans(host, lo, hi)
    launches = sorted(
        (x for x in spans if x[0] == "batcher.launch" and x[4] == 0),
        key=lambda x: x[1] - x[2])[:k]
    ns = 1e-9
    out = []
    for _, s, e, line, _ in launches:
        mine = [x for x in spans if x[3] == line and s <= x[1] and x[2] <= e]
        phases = by_phase([(s, e)], mine, s, e)
        merged = _host_time(host, s, e)
        out.append({"start_s": s * ns, "length_s": (e - s) * ns,
                    "busy_s": tr.covered(busy, s, e) * ns,
                    "by_phase": {n: v * ns for n, v in phases.items()},
                    "host": {n: sum(b - a for a, b in merged[n]) * ns
                             for n in _longest(merged, top)}})
    return out


def host_activity_by_phase(host: Sequence[Sequence[tr.Event]],
                           top: int = 8) -> Dict[str, Dict[str, float]]:
    """For the ``top`` host event names of most time in the window (the
    program's and the benchmark's spans aside), where that time lies among
    the program's phases (seconds)."""
    lo, hi = window(host)
    spans = program_spans(host, lo, hi)
    merged = _host_time(host, lo, hi)
    ns = 1e-9
    return {n: {p: v * ns for p, v in
                by_phase(merged[n], spans, lo, hi).items()}
            for n in _longest(merged, top)}
