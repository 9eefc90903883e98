"""Multi-device behaviour, each case in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the rest of the suite
keeps seeing ONE device (per the assignment's dry-run isolation rule)."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

SRC = str(Path(__file__).resolve().parents[1] / "src")


def run_sub(body: str):
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_scheduler_sync_async_equivalence():
    out = run_sub("""
        from repro.core import scheduler
        mesh = jax.make_mesh((4, 2), ("pool", "x"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        k = jax.random.PRNGKey(0)
        stacked = {"w": jax.random.normal(k, (4, 16, 16))}
        x = jax.random.normal(jax.random.fold_in(k, 1), (8, 16))
        fn = lambda p, v: jnp.tanh(v @ p["w"])
        y_sync = scheduler.run_sync(fn, stacked, x)
        y_async = scheduler.run_async(fn, stacked, x, mesh=mesh)
        y_hybrid = scheduler.hybrid_pools(fn, stacked, x, mesh=mesh)
        np.testing.assert_allclose(np.asarray(y_sync), np.asarray(y_async),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(y_sync), np.asarray(y_hybrid),
                                   rtol=1e-5, atol=1e-5)
        print("OK")
    """)
    assert "OK" in out


def test_sharded_train_step_matches_single_device():
    """The same train step, unsharded vs (data=2, model=4)-sharded, must
    produce the same loss/metrics (SPMD is numerics-preserving modulo
    reduction order)."""
    out = run_sub("""
        import dataclasses
        from repro.configs import get_config, reduced
        from repro.core import tuner
        from repro.launch import build as B
        from repro.models import forward_train, model_defs
        from repro.models import module as m
        from repro.parallel import sharding as sh

        cfg = reduced(get_config("dbrx-132b"), layers=2, d_model=64,
                      experts=4)
        defs = model_defs(cfg)
        params = m.init_params(defs, jax.random.PRNGKey(0), jnp.float32)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                    cfg.vocab_size)
        batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, 1)}
        loss0, _ = jax.jit(lambda p, b: forward_train(p, cfg, b))(params,
                                                                  batch)

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        plan = tuner.Plan(name="t", data=2, pools=4, intra=1, fsdp=True,
                          seq_shard=False)
        rules = tuner.make_rules(plan, mesh)
        with mesh, sh.axis_rules(rules):
            loss1, _ = jax.jit(lambda p, b: forward_train(p, cfg, b))(
                params, batch)
        np.testing.assert_allclose(float(loss0), float(loss1), rtol=5e-4)
        print("OK", float(loss0), float(loss1))
    """)
    assert "OK" in out


def test_make_production_mesh_shapes():
    out = run_sub("""
        from repro.launch.mesh import make_production_mesh, make_tuned_mesh
        # 8 host devices: use tuned mesh factors that fit
        m = make_tuned_mesh(2, model_axis=4, data_axis=2)
        assert dict(m.shape) == {"data": 2, "pool": 2, "intra": 2}
        print("OK")
    """)
    assert "OK" in out


def test_checkpoint_elastic_restore_across_meshes():
    out = run_sub("""
        import tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.train import checkpoint as ck
        mesh1 = jax.make_mesh((8,), ("model",),
                              axis_types=(jax.sharding.AxisType.Auto,))
        mesh2 = jax.make_mesh((2, 4), ("data", "model"),
                              axis_types=(jax.sharding.AxisType.Auto,)*2)
        w = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                           NamedSharding(mesh1, P("model", None)))
        with tempfile.TemporaryDirectory() as d:
            ck.save(d, {"w": w}, 1)
            target = {"w": jax.ShapeDtypeStruct(
                (8, 8), jnp.float32,
                sharding=NamedSharding(mesh2, P("data", "model")))}
            restored, _ = ck.restore(d, target)
        assert restored["w"].sharding.spec == P("data", "model")
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(w))
        print("OK")
    """)
    assert "OK" in out


def test_sp_boundary_grad_correctness():
    out = run_sub("""
        from repro.core import tuner
        from repro.parallel import sharding as sh
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        plan = tuner.Plan(name="t", data=2, pools=1, intra=4,
                          seq_shard=True)
        rules = tuner.make_rules(plan, mesh)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16))

        def f(x):
            return jnp.sum(jnp.sin(sh.sp_boundary(x)) ** 2)

        g_plain = jax.grad(lambda x: jnp.sum(jnp.sin(x) ** 2))(x)
        with mesh, sh.axis_rules(rules):
            g = jax.jit(jax.grad(f))(x)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_plain),
                                   rtol=1e-5)
        print("OK")
    """)
    assert "OK" in out
