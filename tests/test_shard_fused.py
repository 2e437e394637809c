"""Per-shard fused dispatch (ISSUE 7 acceptance): every PWL Pallas kernel
runs *inside* shard_map under a multi-device mesh — zero fused-fallback
warnings on a 2x2 (data x model) host mesh for a train step and a paged
serve session, with per-shard outputs matching the single-device fused
reference.

Multi-device scenarios run in subprocesses (tests/mesh_utils.py) so the
rest of the suite keeps seeing one device; in-process tests cover the
1-device-mesh predicate and the sanitize_spec warn-once lifecycle.
"""
import textwrap
import warnings

import pytest

import repro  # noqa: F401
from repro import sfu
from repro.distributed import shard_fused, sharding
from repro.launch.mesh import make_mesh

from mesh_utils import run_py

pytestmark = pytest.mark.mesh


# --------------------------------------------------------------------------
# acceptance: 2x2 mesh, warnings-as-errors, fused end to end
# --------------------------------------------------------------------------

def test_train_step_2x2_mesh_zero_fallbacks():
    """One fused-everything train step on a 2x2 (data x model) mesh with
    fallback warnings promoted to errors: the per-shard dispatch must keep
    every fused-planned site on its Pallas kernel."""
    r = run_py("""
        import warnings
        # the acceptance bar: a single fused fallback anywhere is an ERROR
        warnings.filterwarnings("error", message=".*falling back.*")
        import jax, jax.numpy as jnp
        import repro
        from repro.configs import get_reduced_config
        from repro.launch.mesh import make_host_mesh
        from repro.launch.steps import build_train_step
        from repro.models import Model, ShapeCell
        from repro.optim import adamw

        cfg = get_reduced_config("repro-100m", act_impl="fused",
                                 pwl_softmax=True, force_dp_only=False)
        mesh = make_host_mesh(model=2)   # (data=2, model=2)
        cell = ShapeCell("t", 64, 4, "train")
        fn, in_sh, out_sh, structs, extra = build_train_step(
            cfg, mesh, cell, microbatches=1)
        jstep = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                        donate_argnums=extra["donate_argnums"])
        model = Model(cfg)
        state = adamw.init_state(model.init(jax.random.PRNGKey(0)))
        batch = {
            "tokens": jax.random.randint(
                jax.random.PRNGKey(1), (4, 64), 0, cfg.vocab_size),
            "targets": jax.random.randint(
                jax.random.PRNGKey(2), (4, 64), 0, cfg.vocab_size),
        }
        state, metrics = jstep(state, batch)
        loss = float(metrics["loss"])
        assert jnp.isfinite(loss), loss
        print("OK", loss)
    """, devices=4)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


# One train step on a 2x2 mesh under warnings-as-errors, with the fused
# Pallas backward (impl_bwd="fused", the default) or the jnp recompute
# oracle; ``one_step`` returns the AdamW state after it and the loss.
_GRAD_PARITY_PRELUDE = """
    import warnings
    warnings.filterwarnings("error", message=".*falling back.*")
    import jax, jax.numpy as jnp, numpy as np
    import repro
    from repro.configs import get_reduced_config
    from repro.kernels import fused
    from repro.launch.mesh import make_host_mesh, make_mesh
    from repro.launch.steps import build_train_step
    from repro.models import Model, ShapeCell
    from repro.optim import adamw

    cfg = get_reduced_config("repro-100m", act_impl="fused",
                             pwl_softmax=True, force_dp_only=False,
                             dtype=jnp.DTYPE)
    cell = ShapeCell("t", 64, 4, "train")
    model = Model(cfg)
    batch = {
        "tokens": jax.random.randint(
            jax.random.PRNGKey(1), (4, 64), 0, cfg.vocab_size),
        "targets": jax.random.randint(
            jax.random.PRNGKey(2), (4, 64), 0, cfg.vocab_size),
    }

    def one_step(mesh, impl_bwd):
        fn, in_sh, out_sh, structs, extra = build_train_step(
            cfg, mesh, cell, microbatches=1)
        # use_impl_bwd is read at TRACE time: wrap the jit execution
        with fused.use_impl_bwd(impl_bwd):
            jstep = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
            state = adamw.init_state(model.init(jax.random.PRNGKey(0)))
            state, metrics = jstep(state, batch)
        return jax.device_get(state), float(metrics["loss"])

    def grad_rel(s, s_oracle):
        # The gradient is read from AdamW's first moment after step one
        # (0.1 * g); the updated parameters would not do, since step one
        # moves each by ~lr * sign(g) and a rounding-level difference flips
        # the sign of a near-zero g.  Per leaf, relative to its largest
        # gradient.
        return max(
            float(np.max(np.abs(np.asarray(x, np.float32)
                                - np.asarray(y, np.float32)))
                  / np.max(np.abs(np.asarray(y, np.float32))))
            for x, y in zip(jax.tree_util.tree_leaves(s["mu"]),
                            jax.tree_util.tree_leaves(s_oracle["mu"]))
            if np.max(np.abs(y)) > 0)
"""


def _run_grad_parity(dtype: str, checks: str):
    code = (textwrap.dedent(_GRAD_PARITY_PRELUDE).replace("DTYPE", dtype)
            + textwrap.dedent(checks))
    return run_py(code, devices=4)


def test_train_step_2x2_mesh_fused_backward_grad_parity():
    """ISSUE 9 mesh acceptance: a full train step whose gradients flow
    through the fused Pallas BACKWARD kernels (impl_bwd="fused" is the
    default) on a 2x2 mesh, in the config's bf16, warnings-as-errors — zero
    fallbacks — and (a) the updated parameters match the same step on a
    1-device mesh, (b) the gradients match the jnp-recompute backward
    oracle on the same 2x2 mesh to bf16 rounding."""
    r = _run_grad_parity("bfloat16", """
        s_ref, l_ref = one_step(make_mesh((1, 1), ("data", "model")),
                                "fused")
        s_mesh, l_mesh = one_step(make_host_mesh(model=2), "fused")
        s_rec, l_rec = one_step(make_host_mesh(model=2), "recompute")
        p_ref, p_mesh = s_ref["params"], s_mesh["params"]

        def maxdiff(a, b):
            return max(
                float(np.max(np.abs(np.asarray(x, np.float32)
                                    - np.asarray(y, np.float32))))
                for x, y in zip(jax.tree_util.tree_leaves(a),
                                jax.tree_util.tree_leaves(b)))

        assert jnp.isfinite(l_mesh), l_mesh
        # mesh vs no-mesh: sharded reductions reorder f32 sums (~1e-6 on
        # the updated params; measured 6e-6)
        assert abs(l_mesh - l_ref) < 1e-3 * abs(l_ref), (l_mesh, l_ref)
        d_mesh = maxdiff(p_mesh, p_ref)
        assert d_mesh < 1e-4, d_mesh
        # fused vs recompute backward on the SAME mesh: the same forward,
        # and gradients from the same bf16 activations.  The kernels and
        # the oracle round intermediates to bf16 (2^-8 relative) at other
        # points, so they differ by a few bf16 roundings of the leaf's
        # largest gradient (measured 5.7e-3); a wrong slope or block moves a
        # gradient by its own scale.
        assert l_mesh == l_rec, (l_mesh, l_rec)
        d_bwd = grad_rel(s_mesh, s_rec)
        assert d_bwd < 2e-2, d_bwd
        print("OK", l_mesh, d_mesh, d_bwd)
    """)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_train_step_2x2_mesh_fused_backward_grad_parity_f32():
    """The fused-vs-recompute gradient check of the test above in f32,
    where no bf16 rounding hides a fault: on a 2x2 mesh the fused Pallas
    backward and the jnp oracle agree to f32 rounding."""
    r = _run_grad_parity("float32", """
        s_mesh, l_mesh = one_step(make_host_mesh(model=2), "fused")
        s_rec, l_rec = one_step(make_host_mesh(model=2), "recompute")
        assert jnp.isfinite(l_mesh), l_mesh
        # the same forward; the kernels and the oracle sum in other orders,
        # so the gradients agree to f32 rounding (measured 3e-7)
        assert l_mesh == l_rec, (l_mesh, l_rec)
        d_bwd = grad_rel(s_mesh, s_rec)
        assert d_bwd < 1e-5, d_bwd
        print("OK", l_mesh, d_bwd)
    """)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_paged_serve_2x2_mesh_zero_fallbacks_and_token_parity():
    """A full paged serve session on a 2x2 mesh: zero fused fallbacks
    (warnings-as-errors) and EXACT token parity with the no-mesh engine —
    per-shard page writes, flash prefill, and split-KV decode all agree."""
    r = run_py("""
        import warnings
        warnings.filterwarnings("error", message=".*falling back.*")
        import numpy as np
        import jax
        import repro
        from repro.configs import get_reduced_config
        from repro.distributed.sharding import make_rules
        from repro.launch.mesh import make_host_mesh
        from repro.models import Model
        from repro.serving import GenRequest, PagedServingEngine

        cfg = get_reduced_config("repro-100m", act_impl="fused",
                                 pwl_softmax=True, force_dp_only=False)
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(2)
        def reqs():
            return [
                GenRequest(f"r{i}", rng.integers(1, 500, size=n).tolist(),
                           max_new_tokens=m)
                for i, (n, m) in enumerate([(11, 6), (30, 3), (5, 8)])
            ]
        rng = np.random.default_rng(2)
        ref_reqs = reqs()
        eng0 = PagedServingEngine(model, params, max_slots=2, page_size=16,
                                  max_context=64)
        ref = {x.request_id: x.tokens for x in eng0.run(ref_reqs)}

        mesh = make_host_mesh(model=2)
        rules = make_rules(cfg, mesh)
        rng = np.random.default_rng(2)
        eng1 = PagedServingEngine(model, params, max_slots=2, page_size=16,
                                  max_context=64, rules=rules)
        got = {x.request_id: x.tokens for x in eng1.run(reqs())}
        assert got == ref, (got, ref)
        print("OK", sum(len(t) for t in got.values()), "tokens")
    """, devices=4)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_moe_expert_parallel_fused_parity():
    """Fused MoE expert GLU kernel inside the expert-parallel shard_map
    body: (1,2) and (2,2) meshes match the single-device fused forward."""
    r = run_py("""
        import warnings
        warnings.filterwarnings("error", message=".*falling back.*")
        import jax, jax.numpy as jnp, numpy as np
        import repro
        from repro.launch.mesh import make_mesh
        from repro.configs import get_reduced_config
        from repro.distributed.sharding import make_rules, use_rules
        from repro.models import Model

        cfg = get_reduced_config("olmoe-1b-7b", act_impl="fused",
                                 dtype=jnp.float32)
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        batch = {"tokens": jax.random.randint(
            jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)}
        ref, _ = model.forward(params, batch)

        for shape in ((1, 2), (2, 2)):
            mesh = make_mesh(shape, ("data", "model"))
            rules = make_rules(cfg, mesh)
            def fwd(p, b):
                with use_rules(rules):
                    return model.forward(p, b)[0]
            out = jax.jit(fwd)(params, batch)
            np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                       rtol=3e-2, atol=3e-2)
            print("OK", shape,
                  float(jnp.max(jnp.abs(out - ref))))
    """, devices=4)
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.count("OK") == 2, r.stdout


def test_fused_glu_grad_parity_under_shard_map():
    """Gradients flow through the per-shard fused GLU — including the
    transpose of a replicated-in (FSDP-style) weight, where shard_map's
    psum insertion must reproduce the unfused reduction."""
    r = run_py("""
        import functools
        import jax, jax.numpy as jnp, numpy as np
        import repro
        from repro.launch.mesh import make_mesh
        from repro import sfu
        from repro.core import pwl
        from repro.kernels import fused
        from repro.distributed import shard_fused as shf
        from repro.distributed.sharding import make_rules

        class _Cfg:  # make_rules only reads head counts
            n_heads = 4
            n_kv_heads = 4
        mesh = make_mesh((2, 2), ("data", "model"))
        rules = make_rules(_Cfg, mesh)
        table = sfu.get_store().get(fn="silu", n_breakpoints=32)

        B, S, D, F = 4, 8, 16, 32
        x = jax.random.normal(jax.random.PRNGKey(0), (B, S, D))
        wg = jax.random.normal(jax.random.PRNGKey(1), (D, F)) * 0.1
        wu = jax.random.normal(jax.random.PRNGKey(2), (D, F)) * 0.1

        f = shf.dim_entry(rules, "mlp", F)
        b = shf.batch_entry(rules, B)

        @shf.sharded_call(
            rules,
            in_specs=(shf.P(b, None, None), shf.P(None, f), shf.P(None, f)),
            out_specs=shf.P(b, None, f),
        )
        def run(x_l, wg_l, wu_l):
            return fused.fused_glu(x_l, wg_l, wu_l, table=table)

        def loss_sh(x, wg, wu):
            return jnp.sum(jax.jit(run)(x, wg, wu) ** 2)

        def loss_ref(x, wg, wu):
            h = pwl.eval_coeff(x @ wg, table) * (x @ wu)
            return jnp.sum(h ** 2)

        g_sh = jax.grad(loss_sh, argnums=(0, 1, 2))(x, wg, wu)
        g_rf = jax.grad(loss_ref, argnums=(0, 1, 2))(x, wg, wu)
        for name, a, r in zip("x wg wu".split(), g_sh, g_rf):
            err = float(jnp.max(jnp.abs(a - r)))
            assert err < 1e-4, (name, err)
            print("OK", name, err)
    """, devices=4)
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.count("OK") == 3, r.stdout


def test_fused_rmsnorm_per_shard():
    """The RMSNorm+activation epilogue kernel runs per-shard through
    shard_fused.sharded_call and matches the single-device kernel."""
    r = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        import repro
        from repro.launch.mesh import make_mesh
        from repro import sfu
        from repro.kernels import fused
        from repro.distributed import shard_fused as shf
        from repro.distributed.sharding import make_rules

        class _Cfg:
            n_heads = 4
            n_kv_heads = 4
        mesh = make_mesh((2, 2), ("data", "model"))
        rules = make_rules(_Cfg, mesh)
        table = sfu.get_store().get(fn="silu", n_breakpoints=32)

        B, S, D = 4, 8, 32
        x = jax.random.normal(jax.random.PRNGKey(0), (B, S, D))
        scale = jax.random.normal(jax.random.PRNGKey(1), (D,)) * 0.1
        b = shf.batch_entry(rules, B)

        @shf.sharded_call(rules,
                          in_specs=(shf.P(b, None, None), shf.P(None)),
                          out_specs=shf.P(b, None, None))
        def run(x_l, s_l):
            return fused.fused_rmsnorm(x_l, s_l, table=table)

        y = jax.jit(run)(x, scale)
        ref = fused.fused_rmsnorm(x, scale, table=table)
        err = float(jnp.max(jnp.abs(y - ref)))
        assert err < 1e-5, err
        print("OK", err)
    """, devices=4)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


# --------------------------------------------------------------------------
# warn lifecycle: the fallbacks that remain must fire once per session
# --------------------------------------------------------------------------

def test_engine_session_warns_once_per_session_on_seq_sharded_cache():
    """Sequence-parallel attention rules (heads don't divide the model
    extent) shard the KV cache over "cache_seq" — the one decode case that
    still falls back.  Each engine.run() session must report it exactly
    once: run() resets the warn-once state, so a SECOND session warns
    again instead of staying silent."""
    r = run_py("""
        import warnings
        import numpy as np
        import jax
        import repro
        from repro.launch.mesh import make_mesh
        from repro.configs import get_reduced_config
        from repro.distributed.sharding import make_rules
        from repro.models import Model
        from repro.serving import GenRequest, PagedServingEngine

        cfg = get_reduced_config("repro-100m", act_impl="fused",
                                 pwl_softmax=True, force_dp_only=False)
        mesh = make_mesh((2, 3), ("data", "model"))
        rules = make_rules(cfg, mesh)
        # heads (4) don't divide model (3): seq-parallel rules, cache_seq
        # sharded over "model"
        assert rules.table["cache_seq"] == "model", rules.table
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        def session():
            # fresh engine = fresh jitted closures: the decode path
            # RETRACES, which is when the fallback warning fires.  Without
            # run()'s reset the first session would poison warn-once for
            # every later engine in the process.
            engine = PagedServingEngine(model, params, max_slots=2,
                                        page_size=16, max_context=64,
                                        rules=rules)
            reqs = [GenRequest("r0", rng.integers(1, 500, size=9).tolist(),
                               max_new_tokens=4)]
            with warnings.catch_warnings(record=True) as rec:
                warnings.simplefilter("always")
                engine.run(reqs)
            return [str(w.message) for w in rec
                    if "falling back" in str(w.message)]
        first = session()
        second = session()
        assert len(first) == 1, first
        assert len(second) == 1, second
        assert "sequence axis" in first[0], first[0]
        print("OK")
    """, devices=6)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


# --------------------------------------------------------------------------
# in-process: predicate + sanitize_spec lifecycle (1 device is enough)
# --------------------------------------------------------------------------

def test_active_mesh_rules_is_none_without_multi_device_mesh():
    """The dispatch predicate: None without rules, None for a mesh-less
    Rules, None for a 1-device mesh — fused kernels run direct in all
    three."""
    assert sharding.active_mesh_rules() is None
    bare = sharding.Rules(table={}, mesh_axes=("data",), mesh=None)
    with sharding.use_rules(bare):
        assert sharding.active_rules() is bare
        assert sharding.active_mesh_rules() is None
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = sharding.Rules(table={"batch": ("data",)},
                           mesh_axes=("data", "model"), mesh=mesh)
    with sharding.use_rules(rules):
        assert sharding.active_mesh_rules() is None


def test_shard_spec_replicates_non_dividing_dims():
    """dim_entry/shard_spec: shard when the mesh extent divides the dim,
    replicate otherwise — the same escape hatch sanitize_spec applies to
    the unfused path (no warning, no error)."""
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = sharding.Rules(
        table={"batch": ("data",), "mlp": "model", "act_heads": "model"},
        mesh_axes=("data", "model"), mesh=mesh)
    # extents are 1 on a 1x1 mesh: everything divides, axes pass through
    assert shard_fused.dim_entry(rules, "mlp", 7) == "model"
    assert shard_fused.dim_entry(rules, None, 8) is None
    spec = shard_fused.shard_spec(rules, ("batch", None, "mlp"), (4, 8, 16))
    assert tuple(spec) == ("data", None, "model")


def test_sanitize_spec_warns_once_and_skips_trivial_dims():
    """Dropping a spec entry replicates the array — report it once per
    (entry, shape), and never for size-1 dims (B=1 prefill noise)."""
    from types import SimpleNamespace

    mesh = SimpleNamespace(shape={"model": 2})
    sharding.reset_sanitize_warnings()
    try:
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            s1 = sharding.sanitize_spec(mesh, sharding.P("model"), (5, 3))
            s2 = sharding.sanitize_spec(mesh, sharding.P("model"), (5, 3))
            s3 = sharding.sanitize_spec(mesh, sharding.P("model"), (1,))
            s4 = sharding.sanitize_spec(mesh, sharding.P("model"), (6,))
        assert tuple(s1) == (None, None)
        assert tuple(s2) == (None, None)
        assert tuple(s3) == (None,)      # dropped silently: dim 1
        assert tuple(s4) == ("model",)   # divides: kept, no warning
        msgs = [str(w.message) for w in rec]
        assert len(msgs) == 1, msgs
        assert "does not divide" in msgs[0] and "replicating" in msgs[0]
        # deliberately does NOT say "fused": serve's fallback counter and
        # the warnings-as-errors acceptance filter must not match it
        assert "falling back" not in msgs[0] and "fused" not in msgs[0]
    finally:
        sharding.reset_sanitize_warnings()


def test_plan_no_longer_exports_mesh_blocks_fused():
    """The blanket mesh>1 predicate is gone — dispatch points must use
    sharding.active_mesh_rules() instead."""
    assert not hasattr(sfu, "mesh_blocks_fused")


def test_fused_fallback_reset_per_session():
    """reset_fused_fallback_warnings() re-arms warn-once (what
    PagedServingEngine.run() calls at session start)."""
    sfu.reset_fused_fallback_warnings()
    key = sfu.site_key(sfu.SITE_SOFTMAX, "exp")
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        sfu.warn_fused_fallback(key, "test reason")
        sfu.warn_fused_fallback(key, "test reason")  # deduped
        sfu.reset_fused_fallback_warnings()
        sfu.warn_fused_fallback(key, "test reason")  # re-armed
    msgs = [w for w in rec if "falling back" in str(w.message)]
    assert len(msgs) == 2, [str(w.message) for w in rec]
    sfu.reset_fused_fallback_warnings()
