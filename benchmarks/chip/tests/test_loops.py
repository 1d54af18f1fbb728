"""The loop of a cell is found by the name its traffic gives, and a new
loop, with its own check, limits, ``Run`` fields and readers, runs through
``run.run_cell`` as a file of its own; the collision loops draw from the
run's seed exactly what they drew before they moved into ``loops/``."""
import chipbench_paths  # noqa: F401  (first: the import path)
import json
import textwrap
import time

import numpy as np
import pytest

import collision
import run
import traffic

SEED = 2**31 + 12345

TOY_LOOP = '''
"""A toy loop: sums each unit of ``n`` numbers drawn from the seed."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

LIMITS = json.loads((Path(__file__).parent / "toy_limits.json").read_text())


class Loop:
    def __init__(self, run, config, mix, seed, seconds, mark):
        rng = np.random.default_rng(seed)
        self.units = rng.standard_normal((mix["units"], config["n"]))
        self.f = jax.jit(lambda x: x.sum())
        self.f(jnp.asarray(self.units[0])).block_until_ready()
        self.damage = mix.get("damage", 0.0)
        mark("warm")

    def window(self, run, seconds):
        with jax.profiler.TraceAnnotation("bench.window"):
            self.sums = [float(self.f(jnp.asarray(u))) + self.damage
                         for u in self.units]
        run.window_s = seconds
        run.toy_units = len(self.sums)
        return {"attempted": len(self.sums), "failed": 0,
                "toy": {"units": len(self.sums)}}

    def release(self):
        del self.f

    def check(self):
        gap = max(abs(s - u.sum()) for s, u in zip(self.sums, self.units))
        return {"toy_gap": [gap, LIMITS["toy_gap"]]}
'''

TOY_READER = '''
def read(run):
    return run.toy_units / run.window_s
'''


@pytest.fixture
def toy(tmp_path, monkeypatch):
    (tmp_path / "loops").mkdir()
    (tmp_path / "metrics").mkdir()
    (tmp_path / "loops" / "toy.py").write_text(textwrap.dedent(TOY_LOOP))
    (tmp_path / "loops" / "toy_limits.json").write_text(
        json.dumps({"toy_gap": 1e-4}))
    (tmp_path / "metrics" / "toy_units_per_s.py").write_text(TOY_READER)
    monkeypatch.setattr(run, "LOOPS", tmp_path / "loops")
    monkeypatch.setattr(run, "METRICS", tmp_path / "metrics")
    bench = {"end_to_end": [{"name": "toy_units_per_s", "unit": "unit/s",
                             "workloads": ["toy.cell"]}],
             "per_layer": []}
    return bench, {"name": "toy.cell", "chips": 1}


def test_loop_found_by_name():
    for name in ("closed", "open"):
        assert hasattr(run.load_loop(name), "Loop")
    assert run.load_loop("closed").__file__ == str(run.LOOPS / "closed.py")


def test_unknown_loop_names_the_file(toy):
    bench, cell = toy
    with pytest.raises(FileNotFoundError, match=str(run.LOOPS / "nope.py")):
        run.run_cell(bench, cell, {"n": 4}, {"loop": "nope"}, SEED, 1.0,
                     False, time.perf_counter())


@pytest.mark.parametrize("damage,correct", [(0.0, True), (1e-2, False)],
                         ids=["sound", "answers_altered"])
def test_toy_loop_runs_through_run_cell(toy, damage, correct):
    bench, cell = toy
    mix = {"loop": "toy", "units": 5, "damage": damage}
    out = run.run_cell(bench, cell, {"n": 16}, mix, SEED, 2.0, False,
                       time.perf_counter())
    assert out["correct"] is correct
    assert out["attempted"] == 5 and out["failed"] == 0
    assert out["metrics"] == {"toy_units_per_s": {"value": 2.5,
                                                  "unit": "unit/s"}}
    assert list(out["checks"]) == ["toy_gap"]
    assert out["checks"]["toy_gap"][1] == 1e-4
    assert out["toy"] == {"units": 5}
    assert list(out)[-1] == "checks"
    assert "warm" in out["setup_phases"]


# Drawn by the harness before its loops moved into ``loops/``, from
# SeedSequence(SEED).spawn(4): scene, traffic, sample, warm-up.
CLOSED_CENTER_SUMS = [5836.001949371544, 5178.2728105925635,
                      4949.453918997615, 5374.3005626783015,
                      4827.659525103953, 5303.00913771763,
                      5060.539130782525, 5539.811619687837]
CLOSED_ROT_SUMS = [4146.546727732713, 3363.2091456004528, 5299.161392432125,
                   3746.2659593387543, 3313.454621845707, 3124.1801406634404,
                   2297.9978389500484, 4757.044656665308]
OPEN_SECONDS = 2.0
OPEN_FIRST_DUE = [0.0004015979357649968, 0.006729804390512628,
                  0.008254835450131504]
OPEN_DUE_SUM = 2224.773575381939
OPEN_FIRST_SAMPLE = [0, 1, 2, 4, 5, 6, 8, 12, 13, 15]
OPEN_SAMPLE_SUM = 1666962
OPEN_FIRST_CENTER_SUMS = [-2.286207653582096, 43.20046750083566,
                          37.442650601267815]
OPEN_CENTER_SUM = 53735.477994704466


def test_closed_loop_pool_is_pinned():
    _, rng_traffic, _, _ = collision.streams(SEED)
    mix = traffic.load("traj_batch")
    c, h, r = run.load_loop("closed").pool(mix, rng_traffic)
    assert c.shape == (8, 10500, 3)
    np.testing.assert_allclose(c.astype(np.float64).sum(axis=(1, 2)),
                               CLOSED_CENTER_SUMS, rtol=1e-12)
    np.testing.assert_allclose(r.astype(np.float64).sum(axis=(1, 2, 3)),
                               CLOSED_ROT_SUMS, rtol=1e-12)


def test_open_loop_due_times_and_sample_are_pinned():
    _, rng_traffic, rng_sample, _ = collision.streams(SEED)
    mix = traffic.load("edge_serve")
    loop = run.load_loop("open")
    offsets, reqs = loop.requests(mix, OPEN_SECONDS, rng_traffic)
    sample = loop.sample(mix, len(offsets), rng_sample)
    assert len(offsets) == 2200 and len(sample) == 1500
    np.testing.assert_allclose(offsets[:3], OPEN_FIRST_DUE, rtol=1e-15)
    assert float(offsets.sum()) == pytest.approx(OPEN_DUE_SUM, rel=1e-14)
    assert sample[:10].tolist() == OPEN_FIRST_SAMPLE
    assert int(sample.sum()) == OPEN_SAMPLE_SUM
    c = reqs[0].astype(np.float64)
    np.testing.assert_allclose(c[:3].sum(axis=(1, 2)),
                               OPEN_FIRST_CENTER_SUMS, rtol=1e-12)
    assert float(c.sum()) == pytest.approx(OPEN_CENTER_SUM, rel=1e-12)
