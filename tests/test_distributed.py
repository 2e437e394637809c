"""Distributed tests that need >1 device: run in subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (NOT set globally — the
rest of the suite must see 1 device; see tests/mesh_utils.py)."""
import pytest

from mesh_utils import run_py

pytestmark = pytest.mark.mesh


def test_compressed_grad_sync_matches_exact_psum():
    r = run_py("""
        import jax, jax.numpy as jnp, numpy as np, functools
        from jax.sharding import PartitionSpec as P
        from repro.distributed.shard_fused import shard_map
        import repro
        from repro.launch.mesh import make_mesh
        from repro.distributed import compression

        mesh = make_mesh((8,), ("dp",))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 64)) * 0.01

        @functools.partial(shard_map, mesh=mesh, in_specs=P("dp"), out_specs=P())
        def sync(gs):
            mean, res = compression.compressed_psum_leaf(gs[0], "dp")
            return mean

        @functools.partial(shard_map, mesh=mesh, in_specs=P("dp"), out_specs=P())
        def exact(gs):
            return jax.lax.pmean(gs[0], "dp")

        approx = sync(g)
        true = exact(g)
        err = float(jnp.max(jnp.abs(approx - true)))
        scale = float(jnp.max(jnp.abs(g))) / 127
        assert err <= scale + 1e-7, (err, scale)
        print("OK", err, scale)
    """)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


def test_error_feedback_reduces_bias_over_steps():
    r = run_py("""
        import jax, jax.numpy as jnp, numpy as np, functools
        from jax.sharding import PartitionSpec as P
        from repro.distributed.shard_fused import shard_map
        import repro
        from repro.launch.mesh import make_mesh
        from repro.distributed import compression

        mesh = make_mesh((8,), ("dp",))
        # constant per-worker gradients: EF must recover the exact mean in sum
        g = jax.random.normal(jax.random.PRNGKey(1), (8, 32))

        @functools.partial(shard_map, mesh=mesh, in_specs=(P("dp"), P("dp")), out_specs=(P(), P("dp")))
        def step(gs, res):
            mean, new_res = compression.compressed_psum_leaf(gs[0] + res[0], "dp")
            return mean, new_res[None]

        res = jnp.zeros_like(g)
        acc = jnp.zeros(32)
        true_mean = jnp.mean(g, 0)
        for i in range(20):
            m, res = step(g, res)
            acc = acc + m
        # averaged compressed estimate converges to the true mean (EF property)
        err = float(jnp.max(jnp.abs(acc / 20 - true_mean)))
        assert err < 2e-3, err
        print("OK", err)
    """)
    assert r.returncode == 0, r.stderr[-2000:]


def test_host_mesh_train_dp2_tp2():
    """4 fake devices: (data=2, model=2) mesh runs a real sharded train step."""
    r = run_py("""
        import jax, jax.numpy as jnp
        import repro
        from repro.configs import get_reduced_config
        from repro.launch.mesh import make_host_mesh
        from repro.launch.steps import build_train_step
        from repro.models import Model, ShapeCell
        from repro.optim import adamw

        cfg = get_reduced_config("qwen2.5-32b", act_impl="jnp")
        mesh = make_host_mesh(model=2)
        cell = ShapeCell("t", 64, 4, "train")
        fn, in_sh, out_sh, structs, extra = build_train_step(cfg, mesh, cell, microbatches=2)
        jstep = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                        donate_argnums=extra["donate_argnums"])
        model = Model(cfg)
        state = adamw.init_state(model.init(jax.random.PRNGKey(0)))
        batch = {
            "tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0, cfg.vocab_size),
            "targets": jax.random.randint(jax.random.PRNGKey(2), (4, 64), 0, cfg.vocab_size),
        }
        losses = []
        for _ in range(3):
            state, metrics = jstep(state, batch)
            losses.append(float(metrics["loss"]))
        assert all(jnp.isfinite(jnp.asarray(losses))), losses
        assert losses[-1] < losses[0], losses
        print("OK", losses)
    """, devices=4)
    assert r.returncode == 0, r.stderr[-2000:]


def test_moe_expert_parallel_2dev():
    """MoE layer under a 2-way expert-parallel mesh matches single-device."""
    r = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        import repro
        from repro.launch.mesh import make_mesh
        from repro.configs import get_reduced_config
        from repro.models import Model
        from repro.distributed.sharding import make_rules, use_rules

        import jax.numpy as _jnp
        cfg = get_reduced_config("olmoe-1b-7b", act_impl="exact", capacity_factor=8.0,
                                 dtype=_jnp.float32)
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)}
        ref, _ = model.forward(params, batch)

        mesh = make_mesh((1, 2), ("data", "model"))
        rules = make_rules(cfg, mesh)
        def fwd(p, b):
            with use_rules(rules):
                return model.forward(p, b)[0]
        out = jax.jit(fwd)(params, batch)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=3e-2, atol=3e-2)
        print("OK")
    """, devices=2)
    assert r.returncode == 0, r.stderr[-2000:]
