"""Substrate tests: optimizer, data pipeline, roofline analysis, MCL,
planner training, checkpoint basics (single-device parts)."""
import os
import tempfile

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.base import ShapeSpec, get_smoke_config
from repro.train import optimizer as opt_mod


def test_adamw_matches_reference_update():
    cfg = opt_mod.OptConfig(lr=1e-2, beta1=0.9, beta2=0.999, eps=1e-8,
                            weight_decay=0.0, clip_norm=1e9,
                            warmup_steps=0, total_steps=1, min_lr_frac=1.0)
    params = {"w": jnp.asarray([1.0, -2.0, 3.0])}
    grads = {"w": jnp.asarray([0.1, 0.2, -0.3])}
    state = opt_mod.init_opt_state(params, cfg)
    new_p, new_s, m = opt_mod.adamw_update(params, grads, state, cfg)
    # step 1 with bias correction: mhat = g, vhat = g^2
    g = np.asarray([0.1, 0.2, -0.3])
    expect = np.asarray([1.0, -2.0, 3.0]) - 1e-2 * g / (np.abs(g) + 1e-8)
    np.testing.assert_allclose(np.asarray(new_p["w"]), expect, rtol=1e-5)
    assert int(new_s["step"]) == 1


def test_grad_clipping():
    g = {"w": jnp.full((10,), 100.0)}
    clipped, norm = opt_mod.clip_by_global_norm(g, 1.0)
    assert float(norm) > 100
    assert abs(float(opt_mod.global_norm(clipped)) - 1.0) < 1e-5


def test_schedule_warmup_and_cosine():
    cfg = opt_mod.OptConfig(lr=1.0, warmup_steps=10, total_steps=110,
                            min_lr_frac=0.1)
    assert float(opt_mod.schedule(jnp.asarray(0), cfg)) == 0.0
    assert abs(float(opt_mod.schedule(jnp.asarray(10), cfg)) - 1.0) < 1e-6
    end = float(opt_mod.schedule(jnp.asarray(110), cfg))
    assert abs(end - 0.1) < 1e-6


def test_bf16_optimizer_states():
    cfg = opt_mod.OptConfig(state_dtype="bfloat16")
    params = {"w": jnp.ones((4, 4))}
    st = opt_mod.init_opt_state(params, cfg)
    assert st["m"]["w"].dtype == jnp.bfloat16
    new_p, new_s, _ = opt_mod.adamw_update(
        params, {"w": jnp.ones((4, 4)) * 0.1}, st, cfg)
    assert new_s["v"]["w"].dtype == jnp.bfloat16
    assert np.isfinite(np.asarray(new_p["w"], np.float32)).all()


def test_data_pipeline_determinism_and_host_sharding():
    from repro.data.pipeline import synth_batch
    cfg = get_smoke_config("glm4_9b")
    shape = ShapeSpec("t", 32, 8, "train")
    a = synth_batch(cfg, shape, step=3)
    b = synth_batch(cfg, shape, step=3)
    assert (a["tokens"] == b["tokens"]).all()
    c = synth_batch(cfg, shape, step=4)
    assert not (a["tokens"] == c["tokens"]).all()
    # host sharding: 2 hosts each get half the batch, different data
    h0 = synth_batch(cfg, shape, step=3, host_index=0, host_count=2)
    h1 = synth_batch(cfg, shape, step=3, host_index=1, host_count=2)
    assert h0["tokens"].shape[0] == 4
    assert not (h0["tokens"] == h1["tokens"]).all()
    assert (a["labels"][:, :-1] == a["tokens"][:, 1:]).all()


def test_collective_parser_loop_aware():
    """A psum inside a scan must be multiplied by the trip count."""
    import subprocess
    import sys
    import textwrap
    script = textwrap.dedent(f"""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(
        0, {os.path.join(os.path.dirname(__file__), '..', 'src')!r})
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.launch.mesh import use_mesh
    from repro.roofline.analysis import parse_collective_bytes
    mesh = jax.make_mesh((8,), ("model",))
    def f(x, w):
        def body(c, _):
            # contraction over the sharded dim -> psum inside the loop
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=12)
        return jnp.sum(y)
    with use_mesh(mesh):
        c = jax.jit(f, in_shardings=(NamedSharding(mesh, P(None, None)),
                                     NamedSharding(mesh, P("model", None)))
                    ).lower(jax.ShapeDtypeStruct((4, 64), jnp.float32),
                            jax.ShapeDtypeStruct((64, 64), jnp.float32)
                    ).compile()
    coll = parse_collective_bytes(c.as_text())
    total = sum(coll.values())
    print("COLL", coll, total)
    # one f32[4,64] all-reduce per iteration x 12 iterations (+ final sum)
    assert total >= 12 * 4 * 64 * 4, coll
    """)
    res = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "COLL" in res.stdout


def test_jaxpr_cost_scan_multiplication():
    from repro.roofline.jaxpr_cost import trace_cost
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y
    cost = trace_cost(f, jax.ShapeDtypeStruct((128, 128), jnp.float32),
                      jax.ShapeDtypeStruct((128, 128), jnp.float32))
    expect = 2 * 128 * 128 * 128 * 10
    assert abs(cost.flops - expect) / expect < 0.05
    assert cost.bytes_major >= 10 * 128 * 128 * 4   # carries + dots


def test_mcl_engines_agree_and_converge():
    from repro.core.mcl import (make_corridor_world, ray_cast_compacted, ray_cast_dense)
    grid = make_corridor_world(jax.random.PRNGKey(0), size=96)
    rs = np.random.RandomState(2)
    org = jnp.asarray(rs.uniform(0.5, 4.0, (50, 2)).astype(np.float32))
    ang = jnp.asarray(rs.uniform(-np.pi, np.pi, 50).astype(np.float32))
    r1, c1 = ray_cast_dense(grid, org, ang, 4.0)
    r2, c2 = ray_cast_compacted(grid, org, ang, 4.0)
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r2), atol=1e-6)
    assert c2 <= c1          # compaction never traverses more cells


def test_planner_bc_loss_decreases():
    """Behaviour cloning of the MPiNets policy (small preset) lowers the
    loss on a fixed batch."""
    from repro.models.planner import SMALL, bc_loss, init_mpinets
    from repro.train.optimizer import OptConfig, adamw_update, init_opt_state
    rs = np.random.RandomState(0)
    B = 8
    model = init_mpinets(SMALL, seed=0)
    obstacles = jnp.asarray(rs.uniform(-1, 1, (B, SMALL.obstacle_points, 3)
                                       ).astype(np.float32))
    target = jnp.asarray(rs.uniform(-1, 1, (B, SMALL.target_points, 3)
                                    ).astype(np.float32))
    q = jnp.asarray(rs.uniform(-1, 1, (B, 7)).astype(np.float32))
    expert = jnp.asarray(rs.uniform(-0.1, 0.1, (B, 7)).astype(np.float32))
    params = model.params
    cfg = OptConfig(lr=3e-3, warmup_steps=0, total_steps=30)
    st = init_opt_state(params, cfg)
    lg = jax.jit(jax.value_and_grad(
        lambda p: bc_loss(model, p, obstacles, target, q, expert)))
    losses = []
    for i in range(15):
        loss, g = lg(params)
        params, st, _ = adamw_update(params, g, st, cfg)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.8, losses


def test_checkpoint_commit_protocol():
    from repro.train import checkpoint as ck
    tree = {"a": jnp.ones((4,)), "nested": {"b": jnp.zeros((2, 2))}}
    with tempfile.TemporaryDirectory() as d:
        ck.save_checkpoint(d, 1, tree, async_save=False)
        # a partial (uncommitted) checkpoint must be ignored
        os.makedirs(os.path.join(d, "step_00000007"), exist_ok=True)
        assert ck.latest_steps(d) == [1]
        restored, step = ck.restore_checkpoint(d, tree)
        assert step == 1
        assert (restored["a"] == tree["a"]).all()
