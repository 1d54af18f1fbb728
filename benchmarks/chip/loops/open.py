"""Open loop: units arrive on a schedule, ``rate_per_s`` of them a
second, whatever the service does, and go through the engine's batcher.

Set-up builds the scene and engine from the seed, draws every request of
the window and the sample of them the check keeps, warms every pool width
the batcher can launch (:func:`warm_service`) and sends one request
through the started batcher.  The window sends each request when it is
due and then waits up to :data:`DRAIN_S` past its close for the answers.
"""
from __future__ import annotations

import collections
import dataclasses
import time

import jax
import numpy as np

import collision
import compare
import traffic
from repro.engine import RequestBatcher, ServiceError
from repro.engine.plan import PlanValidationError

#: Seconds past the window's close that an open loop waits for answers.
DRAIN_S = 60.0


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
                pending.append((i, batcher.submit(
                    collision.plan(c[i], h[i], r[i]))))
            except (ServiceError, PlanValidationError):
                out.failed[i] = True
        out.bound = t_begin + seconds + DRAIN_S
        while pending:
            i, ticket = pending.popleft()
            settle(i, ticket, max(0.0, out.bound - time.perf_counter()))
    return out


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
            engine.execute(collision.plan(c, h, r))
    return RequestBatcher(engine, **svc)


def requests(mix: dict, seconds: float, rng: np.random.Generator):
    """Due times and OBB arrays of an open loop's requests."""
    offsets = traffic.arrivals(mix, seconds, rng)
    return offsets, traffic.units(mix, len(offsets), rng)


def sample(mix: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """Sorted indices of the requests whose verdicts the check keeps."""
    return np.sort(rng.choice(n, min(mix["sample_units"], n), replace=False))


class Loop:
    def __init__(self, run, config: dict, mix: dict, seed: int,
                 seconds: float, mark):
        rng_scene, rng_traffic, rng_sample, rng_warm = collision.streams(seed)
        self.engine, self.vox, tree = collision.build(config, rng_scene)
        mark("scene")
        run.level_widths = [len(level.codes) for level in tree.levels]
        run.unit_obbs = traffic.unit_obbs(mix)
        self.offsets, self.reqs = requests(mix, seconds, rng_traffic)
        self.sample = sample(mix, len(self.offsets), rng_sample)
        mark("traffic")
        self.batcher = warm_service(self.engine, self.vox, config, mix,
                                    rng_warm)
        c, h, r = self.reqs
        self.batcher.submit(collision.plan(c[0], h[0], r[0])).result(
            timeout=600)

    def window(self, run, seconds: float) -> dict:
        launches0 = self.batcher.num_launches
        self.loop = open_loop(self.batcher, self.reqs, self.offsets, seconds,
                              self.sample)
        run.window_s = seconds
        run.launches = self.batcher.num_launches - launches0
        ok = self.loop.answered
        run.latency_s = self.loop.latency_s()
        run.lag_s = self.loop.sent - self.loop.due
        run.wait_s = self.loop.wait_s[ok]
        run.answered = int(ok.sum())
        return {"attempted": len(self.offsets), "failed": int((~ok).sum()),
                "engine": collision.engine_totals([self.batcher.totals])}

    def release(self):
        self.batcher.close()
        del self.engine, self.batcher

    def check(self) -> dict:
        """The sampled requests' verdicts against the reference, and the
        requests never answered."""
        idx = np.array(sorted(self.loop.verdicts), dtype=np.int64)
        c, h, r = self.reqs
        sep = self.vox.separation(c[idx].reshape(-1, 3),
                                  h[idx].reshape(-1, 3),
                                  r[idx].reshape(-1, 3, 3))
        got = np.concatenate([np.asarray(self.loop.verdicts[i]).reshape(-1)
                              for i in idx] or [np.zeros(0, bool)])
        vals = compare.readings(got, sep)
        vals["unanswered"] = int(self.loop.unanswered.sum())
        return compare.judge(vals)[1]
