"""Distributed correctness on 8 fake CPU devices (subprocess-isolated so the
rest of the suite keeps a single device).

Covers: sharded train step == single-device step (FSDP+TP numerics),
decode with seq-sharded KV == unsharded decode, compressed DP all-reduce,
GPipe pipeline == sequential stages, elastic checkpoint reshard.
"""
import os
import subprocess
import sys
import textwrap


ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def run_devices(body: str, n: int = 8) -> str:
    script = textwrap.dedent(f"""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
    import sys
    sys.path.insert(0, {os.path.join(ROOT, 'src')!r})
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.launch.mesh import use_mesh
    """) + textwrap.dedent(body)
    res = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stdout + "\n" + res.stderr
    return res.stdout


def test_sharded_train_step_matches_single_device():
    out = run_devices("""
    from repro.configs.base import get_smoke_config, ShapeSpec
    from repro.models import api
    from repro.parallel import sharding as shd
    from repro.train import optimizer as opt_mod, train_loop
    from repro.data.pipeline import synth_batch

    cfg = get_smoke_config("glm4_9b")
    shape = ShapeSpec("t", 32, 8, "train")
    opt_cfg = opt_mod.OptConfig(lr=1e-3)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    opt_state = opt_mod.init_opt_state(params, opt_cfg)
    batch = {k: jnp.asarray(v) for k, v in
             synth_batch(cfg, shape, 0).items()}

    # single-device reference
    ref_step = train_loop.make_train_step(cfg, opt_cfg)
    p1, o1, m1 = jax.jit(ref_step)(params, opt_state, batch)

    mesh = jax.make_mesh((4, 2), ("data", "model"))
    with use_mesh(mesh):
        step, pspecs, ospecs, bspecs = train_loop.make_sharded_train_step(
            cfg, mesh, opt_cfg, shape)
        pp = jax.device_put(params, shd.named(mesh, pspecs))
        oo = jax.device_put(opt_state, shd.named(mesh, ospecs))
        bb = jax.device_put(batch, shd.named(mesh, bspecs))
        p2, o2, m2 = step(pp, oo, bb)
    print("LOSS", float(m1["loss"]), float(m2["loss"]))
    d = jax.tree.reduce(max, jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))),
        p1, jax.device_get(p2)))
    print("MAXDIFF", d)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 2e-4
    assert d < 2e-4
    """)
    assert "MAXDIFF" in out


def test_decode_seq_sharded_kv_matches_unsharded():
    out = run_devices("""
    from repro.configs.base import get_smoke_config, ShapeSpec
    from repro.models import api
    from repro.parallel import sharding as shd
    from repro.train import train_loop
    from repro.models import transformer as tfm

    cfg = get_smoke_config("qwen1_5_110b")
    B, T = 8, 64
    shape = ShapeSpec("d", T, B, "decode")
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    caches = tfm.init_decode_caches(cfg, B, T)
    # fill the cache with fake history at positions < pos
    caches = jax.tree.map(
        lambda x: (jax.random.normal(jax.random.PRNGKey(1), x.shape,
                                     x.dtype) * 0.1
                   if x.dtype != jnp.int32 else x), caches)
    tok = jnp.arange(B, dtype=jnp.int32) % cfg.vocab_size
    pos = jnp.asarray(T - 1, jnp.int32)
    decode = api.make_decode_fn(cfg)
    ref_logits, _ = jax.jit(decode)(params, tok, pos, caches)

    mesh = jax.make_mesh((2, 4), ("data", "model"))
    with use_mesh(mesh):
        fn, pspecs, cspecs = train_loop.make_sharded_decode(cfg, mesh, shape)
        pp = jax.device_put(params, shd.named(mesh, pspecs))
        cc = jax.device_put(caches, shd.named(mesh, cspecs))
        logits, _ = fn(pp, jax.device_put(tok), jax.device_put(pos), cc)
    d = float(jnp.max(jnp.abs(ref_logits - jax.device_get(logits))))
    print("MAXDIFF", d)
    assert d < 2e-3
    """)
    assert "MAXDIFF" in out


def test_compressed_psum_error_feedback():
    out = run_devices("""
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from repro.parallel.compress import (compressed_psum_tree,
                                         init_residuals, quantize_int8,
                                         dequantize_int8)
    mesh = jax.make_mesh((8,), ("data",))
    g_local = jax.random.normal(jax.random.PRNGKey(0), (8, 64)) * 0.01

    def body(g, r):
        mean, new_r = compressed_psum_tree({"w": g[0]}, {"w": r[0]}, "data")
        return mean["w"], new_r["w"]

    from jax import shard_map
    sm = shard_map(body, mesh=mesh, in_specs=(P("data"), P("data")),
                       out_specs=(P(), P("data")))
    r = jnp.zeros((8, 64))
    mean, r2 = sm(g_local, r)
    exact = jnp.mean(g_local, 0)
    err1 = float(jnp.max(jnp.abs(mean - exact)))
    # error feedback: applying twice with residual carried reduces bias
    mean2, _ = sm(g_local, r2)
    two_step = (mean + mean2) / 2
    err2 = float(jnp.max(jnp.abs(two_step - exact)))
    print("ERR1", err1, "ERR2", err2)
    assert err1 < 5e-4            # int8 quantization error bound
    # error feedback keeps the two-step error the same order as one step
    # (it bounds accumulated error; per-step wobble of a few percent is
    # expected, growth by multiples is divergence)
    assert err2 <= 2 * err1
    """)
    assert "ERR1" in out


def test_pipeline_parallel_matches_sequential():
    out = run_devices("""
    from repro.parallel.pipeline_par import run_pipelined
    n_stages, n_micro, mb, d = 4, 8, 4, 16
    mesh = jax.make_mesh((4,), ("stage",))
    ks = jax.random.split(jax.random.PRNGKey(0), n_stages)
    Ws = jnp.stack([jax.random.normal(k, (d, d)) * 0.3 for k in ks])

    micro = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, d))
    # sequential reference
    ref = micro
    for i in range(n_stages):
        ref = jnp.tanh(ref @ Ws[i])
    out = run_pipelined(mesh, "stage", lambda w, x: jnp.tanh(x @ w),
                        Ws, micro, n_stages)
    d_ = float(jnp.max(jnp.abs(out - ref)))
    print("MAXDIFF", d_)
    assert d_ < 1e-5
    """)
    assert "MAXDIFF" in out


def test_elastic_checkpoint_reshard():
    out = run_devices("""
    import tempfile
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.train import checkpoint as ck

    tree = {"w": jax.random.normal(jax.random.PRNGKey(0), (16, 8)),
            "b": jnp.arange(8.0)}
    mesh8 = jax.make_mesh((8,), ("data",))
    tree8 = jax.device_put(tree, NamedSharding(mesh8, P("data")))
    d = tempfile.mkdtemp()
    t = ck.save_checkpoint(d, 5, tree8, async_save=True)
    t.join()
    # restore under a DIFFERENT mesh shape (elastic restart 8 -> 4)
    mesh4 = jax.make_mesh((4, 2), ("data", "model"))
    sh = {"w": NamedSharding(mesh4, P("data", "model")),
          "b": NamedSharding(mesh4, P(None))}
    restored, step = ck.restore_checkpoint(d, tree, shardings=sh)
    assert step == 5
    ok = bool(jnp.all(restored["w"] == tree["w"]))
    print("RESHARD_OK", ok, restored["w"].sharding.spec)
    assert ok
    # keep-last-k GC
    for s in (6, 7, 8, 9):
        ck.save_checkpoint(d, s, tree8, async_save=False, keep_last_k=2)
    print("STEPS", ck.latest_steps(d))
    assert ck.latest_steps(d) == [8, 9]
    """)
    assert "RESHARD_OK True" in out


def test_straggler_skip_and_preemption():
    from repro.train import ft
    import time

    def slow_iter():
        yield 1
        yield 2
        time.sleep(5.0)
        yield 3

    loader = ft.PrefetchingLoader(slow_iter(), depth=1)
    assert loader.next_batch(deadline_s=5) == 1
    assert loader.next_batch(deadline_s=5) == 2
    b = loader.next_batch(deadline_s=0.2)      # producer is straggling
    assert b == 2 and loader.skipped == 1      # reused last good batch

    guard = ft.PreemptionGuard()
    assert not guard.should_checkpoint
    guard.trigger()
    assert guard.should_checkpoint
