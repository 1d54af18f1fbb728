"""The MPiNets planning cell's pieces on the CPU: the cost model pinned at
the published widths, the new readers on a hand-made reduced trace, and
the ``plan`` loop's check at a small size, sound and with one planned
step damaged."""
import chipbench_paths  # noqa: F401  (first: the import path)
import copy
import dataclasses
import json

import pytest

import planner_cost
import run
import trace_reduce as tr
from repro.models.planner import MPINETS, SMALL

SEED = 2**31 + 4242
CONFIG = json.loads((run.HERE / "configs" / "mpinets_cubby.json").read_text())
MODEL = CONFIG["model"]


def test_config_is_the_published_preset():
    """The cell's configuration runs the program's published preset."""
    assert MODEL == json.loads(json.dumps(dataclasses.asdict(MPINETS)))
    assert CONFIG["reduced"] == []


def test_planner_cost_pins_published_macs():
    """1,635,159,136 MACs a problem-step at the published widths, by part
    as the published widths give them."""
    parts = planner_cost.macs_by_part(MODEL)
    assert planner_cost.macs_per_step(MODEL) == 1_635_159_136
    assert parts == {"sa1": 553_648_128, "sa2": 945_815_552,
                     "sa_global": 117_637_120, "fc": 16_777_216,
                     "policy": 1_281_120}
    assert planner_cost.flops_per_call(MODEL, 8) == \
        2 * 1_635_159_136 * 8 * 69
    # FPS: 6,272 then 512 points read, 512 then 128 picks written.
    assert planner_cost.fps_bytes_per_call(MODEL, 1) == \
        69 * (6272 * 12 + 512 * 4 + 512 * 12 + 128 * 4)
    assert planner_cost.ballquery_bytes_per_call(MODEL, 1) == \
        69 * ((6272 + 512) * 12 + 512 * 128 * 4
              + (512 + 128) * 12 + 128 * 128 * 4)


def _pallas(name, s, e):
    hlo = (f"%{name} = s32[8]{{0}} custom-call(f32[8]{{0}} %p0), "
           f'custom_call_target="tpu_custom_call"')
    return (tr.short_name(hlo), hlo, float(s), float(e))


def _traced_run():
    """Two planning calls of 1,000 ns each: FPS 300 ns, ball query 200 ns,
    the gate's megakernel 100 ns in all."""
    device = [[_pallas("fps_sample.1", 10, 160), _pallas("ball_query.2",
                                                         160, 260),
               _pallas("persist_traverse.3", 300, 350),
               _pallas("fps_sample.1", 1010, 1160),
               _pallas("ball_query.2", 1160, 1260),
               _pallas("persist_traverse.3", 1300, 1350)]]
    host = [[("bench.window", "bench.window", 0.0, 2000.0),
             ("bench.execute", "bench.execute", 0.0, 1000.0),
             ("bench.execute", "bench.execute", 1000.0, 2000.0)]]
    r = run.Run(device_kind="TPU v5 lite")
    r.trace = tr.reduce_events(device, host, run.KERNELS)
    r.planner_model, r.planner_problems = MODEL, 8
    return r


@pytest.mark.parametrize("metric", [
    "fps.kernel_ms", "ballquery.kernel_ms", "persist.kernel_ms",
    "fps_roofline", "ballquery_roofline", "planner.step_mfu"])
def test_readers_on_reduced_trace(metric):
    r = _traced_run()
    peaks = json.loads((run.HERE / "peaks.json").read_text())["TPU v5 lite"]
    call_s = 1000e-9
    want = {
        "fps.kernel_ms": 150e-9 * 1e3,
        "ballquery.kernel_ms": 100e-9 * 1e3,
        "persist.kernel_ms": 50e-9 * 1e3,
        "fps_roofline": 100 * planner_cost.fps_bytes_per_call(MODEL, 8)
        / peaks["hbm_bytes_per_s"] / 150e-9,
        "ballquery_roofline": 100 * planner_cost.ballquery_bytes_per_call(
            MODEL, 8) / peaks["hbm_bytes_per_s"] / 100e-9,
        "planner.step_mfu": 100 * planner_cost.flops_per_call(MODEL, 8)
        / (call_s * peaks["bf16_flops_per_s"]),
    }[metric]
    assert run.reader(metric)(r) == pytest.approx(want, rel=1e-9)
    # A run of another loop, or one whose trace holds no such kernel, reads
    # nothing, and raises nothing.
    bare = run.Run(device_kind="TPU v5 lite")
    bare.trace = tr.reduce_events(
        [[_pallas("persist_traverse.3", 300, 350)]],
        [[("bench.window", "bench.window", 0.0, 2000.0),
          ("bench.execute", "bench.execute", 0.0, 1000.0)]], run.KERNELS)
    if metric != "persist.kernel_ms":
        assert run.reader(metric)(bare) is None


def _small_config():
    config = copy.deepcopy(CONFIG)
    config["model"] = json.loads(json.dumps(dataclasses.asdict(SMALL)))
    config["num_points"], config["depth"] = 8192, 4
    return config


@pytest.fixture(scope="module")
def small_loop():
    """The plan loop at the small preset, one call in its window."""
    mix = dict(json.loads((run.HERE / "traffic" / "plan_batch.json")
                          .read_text()), problems=3, pool=2, check_samples=6)
    r = run.Run(device_kind="cpu")
    loop = run.load_loop("plan").Loop(r, _small_config(), mix, SEED, 0.0,
                                      lambda phase: None)
    done = loop.window(r, 0.0)
    loop.release()
    return loop, r, done


def test_plan_loop_runs_and_its_check_holds(small_loop):
    loop, r, done = small_loop
    assert done["attempted"] == len(r.calls) == 1 and done["failed"] == 0
    assert r.unit_obbs == 3 * (SMALL.steps + 1) * 7
    assert done["planner"]["fps_picks"] == SMALL.fps_picks
    assert done["engine"]["ref_arm_fallbacks"] == 0
    checks = loop.check()
    assert list(checks) == ["missed_m", "false_hit_m", "policy_err",
                            "unanswered"]
    assert all(v <= lim for v, lim in checks.values()), checks
    assert checks["policy_err"][0] < 1e-4


def test_plan_loop_check_flags_damaged_step(small_loop):
    """One planned q_{t+1} moved by a hundredth, at a sampled step: the
    check's policy_err reads the damage and the run is not correct."""
    loop, _, _ = small_loop
    c, p, t = loop.samples[0]
    kept = loop.calls[c]
    damaged = kept[3].normalized.copy()
    damaged[p, t + 1, 3] += 0.01
    try:
        loop.calls[c] = kept[:3] + (dataclasses.replace(
            kept[3], normalized=damaged),)
        checks = loop.check()
    finally:
        loop.calls[c] = kept
    assert checks["policy_err"][0] == pytest.approx(0.01, abs=1e-4)
    assert checks["policy_err"][0] > checks["policy_err"][1]
    assert not all(v <= lim for v, lim in checks.values())
