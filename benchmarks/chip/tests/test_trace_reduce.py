"""The trace reduction, on hand-made events and on a trace recorded on the
CPU (whose XLA operations stand in for a chip's)."""
import chipbench_paths  # noqa: F401  (first: the import path)
import jax
import jax.numpy as jnp
import pytest

import trace_reduce as tr


def _ev(name, s, e, text=None):
    return (name, text or name, float(s), float(e))


def _pallas(hlo, s, e):
    """A Pallas call's device event as :func:`trace_reduce.load` gives it:
    the short name, then the whole HLO instruction as its text."""
    return _ev(tr.short_name(hlo), s, e, hlo)


def _hlo(name, op="custom-call", operand="%p0"):
    target = (', custom_call_target="tpu_custom_call"'
              if op == "custom-call" else "")
    return f"%{name} = f32[8]{{0}} {op}(f32[8]{{0}} {operand}){target}"


def test_reduce_hand_made_window():
    device = [[_ev("a", 10, 20), _ev("b", 15, 30), _ev("c", 50, 60),
               _pallas(_hlo("persist_traverse.1"), 70, 80),
               _ev("late", 95, 120)]]
    host = [[_ev("bench.window", 0, 100), _ev("bench.execute", 5, 35),
             _ev("bench.execute", 45, 85)],
            [_ev("work", 30, 50), _ev("inner", 32, 48)]]
    out = tr.reduce_events(device, host, {"persist": "persist_traverse"})
    ns = 1e-9
    assert out["window_s"] == pytest.approx(100 * ns)
    # union: [10, 30] + [50, 60] + [70, 80] + [95, 100]
    assert out["busy_s"] == pytest.approx(45 * ns)
    assert out["kernel_s"]["persist"] == pytest.approx(10 * ns)
    assert out["kernel_events"]["persist"] == 1
    assert [b for _, _, b in out["spans"]["bench.execute"]] == \
        pytest.approx([20 * ns, 20 * ns])
    gaps = dict(out["idle_gaps"])
    # [30, 50] falls in "inner" (innermost), the rest in no host event
    # but the execute spans around them.
    assert gaps["inner"] == pytest.approx(20 * ns)
    assert sum(gaps.values()) == pytest.approx(55 * ns)
    assert out["device_ops"][0][0] in (
        "b", "a", "c", "%persist_traverse.1 custom-call tpu_custom_call")


def test_each_pallas_kernel_by_its_name():
    """Two named Pallas kernels each get their own time and count; the
    ``persist`` short name takes ``persist_traverse`` alone, not the other
    kernel, nor an operation that reads its output, nor one whose name
    only starts alike."""
    device = [[_pallas(_hlo("persist_traverse.1"), 10, 20),
               _pallas(_hlo("fps_pick.2"), 20, 23),
               _pallas(_hlo("persist_traverse.1"), 30, 45),
               _pallas(_hlo("reshape.5", "reshape", "%persist_traverse.1"),
                       45, 47),
               _pallas(_hlo("persist_traverse_bf16.3"), 50, 52),
               _pallas(_hlo("fps_pick.2"), 60, 64),
               _pallas(_hlo("fps_pick.2"), 99, 130)]]
    host = [[_ev("bench.window", 0, 100)]]
    out = tr.reduce_events(device, host, {"persist": "persist_traverse"})
    ns = 1e-9
    k, n = out["kernel_s"], out["kernel_events"]
    assert k["persist"] == k["persist_traverse"] == pytest.approx(25 * ns)
    assert n["persist"] == n["persist_traverse"] == 2
    assert k["fps_pick"] == pytest.approx(8 * ns) and n["fps_pick"] == 3
    assert k["persist_traverse_bf16"] == pytest.approx(2 * ns)
    assert n["persist_traverse_bf16"] == 1
    assert "reshape" not in k
    assert set(k) == set(n) == {"persist", "persist_traverse", "fps_pick",
                                "persist_traverse_bf16"}


def test_op_name():
    assert tr.op_name("%persist_traverse.1 custom-call tpu_custom_call") \
        == "persist_traverse"
    assert tr.op_name(_hlo("fps_pick.12")) == "fps_pick"
    assert tr.op_name("dot_general.1") == "dot_general"
    assert tr.op_name("fusion") == "fusion"
    assert tr.op_name("") == ""


def test_merge_and_covered():
    m = tr.merge([(5, 8), (1, 3), (2, 4), (7, 9), (20, 30)], 0, 25)
    assert m == [(1, 4), (5, 9), (20, 25)]
    assert tr.covered(m, 3, 21) == pytest.approx(1 + 4 + 1)


def test_reduce_cpu_trace(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x.T).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.execute"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = tr.find_xplane(str(tmp_path))
    _, host = tr.load(path)
    ops = tr.cpu_ops(path)
    assert ops, "the CPU trace holds no XLA operations"
    out = tr.reduce_events([ops], host, {"dot": "dot_general"})
    assert 0 < out["busy_s"] <= out["window_s"]
    calls = out["spans"]["bench.execute"]
    assert len(calls) == 3
    for s, e, busy in calls:
        assert 0 < busy <= e - s
    assert out["kernel_events"]["dot"] >= 3
    assert out["kernel_s"]["dot"] <= out["busy_s"]
    assert sum(v for _, v in out["idle_gaps"]) == pytest.approx(
        out["window_s"] - out["busy_s"], rel=1e-6)
