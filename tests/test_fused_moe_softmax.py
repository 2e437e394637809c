"""Fused MoE-expert GLU and PWL-exp softmax kernels (ISSUE 4).

Covers the acceptance criteria: the two new fused kernels match their
unfused PWL references (all table dtypes), their custom VJPs match autodiff
of the unfused formulation, the plan-driven model paths (``moe_layer``,
``attention_layer`` prefill/decode) run fused with NO unfused-fallback
warning on a single device and match the unfused PWL path within
table-dtype tolerance, and fallback edges warn exactly once (not per call).
Also covers the ``act_site_specs`` explicit-plan config migration.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro  # noqa: F401
from repro import sfu
from repro.configs import get_config, get_reduced_config
from repro.core import pwl
from repro.kernels import fused
from repro.models import layers, moe as moe_mod
from repro.models.common import ModelConfig

BLK = (16, 32, 16)  # small blocks: multi-step grids in every dimension

# fused-vs-f32-table bounds per storage format (same as test_sfu_plan)
BOUNDS = {"f32": 1e-5, "bf16": 0.08, "f16": 0.02}


def _rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(jax.random.PRNGKey(key), shape) * scale).astype(dtype)


def _table(fn="silu", n_bp=32, dtype="f32"):
    return sfu.get_store().get(fn=fn, n_breakpoints=n_bp, dtype=dtype)


@pytest.fixture(autouse=True)
def _fresh_fallback_state():
    sfu.reset_fused_fallback_warnings()
    yield
    sfu.reset_fused_fallback_warnings()


# ---------------------------------------------------------------------------
# fused_moe_glu kernel: rows grouped by expert, each group a multiple of the
# row tile; rows past the last group come back zero

TILE = 16


def _grouped(key, e, c, d, f, dtype=jnp.float32, scale=2.0):
    """x (M, d) grouped over ``e`` experts of about ``c`` rows (uneven,
    some groups empty, each padded to the row tile) with trailing rows
    past the last group, the weights, the group sizes, and each row's
    expert (-1 past the groups)."""
    sizes = [-(-max(0, c - 7 * i) // TILE) * TILE for i in range(e)]
    m = sum(sizes) + 2 * TILE
    x = _rand(key, (m, d), dtype, scale)
    wg = _rand(key + 1, (e, d, f), dtype, 0.2)
    wu = _rand(key + 2, (e, d, f), dtype, 0.2)
    eid = np.concatenate([np.full(n, i) for i, n in enumerate(sizes)]
                         + [np.full(2 * TILE, -1)]).astype(np.int32)
    return x, wg, wu, jnp.asarray(sizes, jnp.int32), eid


def _per_row(x, w, eid):
    """Each row times its own expert's matrix; zero past the groups."""
    xf, wf = x.astype(jnp.float32), w.astype(jnp.float32)
    y = jnp.einsum("md,mdf->mf", xf, wf[np.maximum(eid, 0)])
    return jnp.where((eid >= 0)[:, None], y, 0.0)


@pytest.mark.parametrize(
    "e,c,d,f", [(2, 16, 32, 16), (3, 37, 65, 30), (1, 7, 9, 5), (4, 40, 48, 96)]
)
def test_fused_moe_glu_matches_ref_shapes(e, c, d, f):
    table = _table()
    x, wg, wu, sizes, eid = _grouped(0, e, c, d, f)
    y = fused.fused_moe_glu(x, wg, wu, sizes, row_tile=TILE, table=table)
    ref = pwl.eval_coeff(_per_row(x, wg, eid), table) * _per_row(x, wu, eid)
    np.testing.assert_allclose(y, ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_moe_glu_dtypes(dtype):
    table = _table()
    x, wg, wu, sizes, eid = _grouped(0, 2, 24, 48, 56, dtype)
    y = fused.fused_moe_glu(x, wg, wu, sizes, row_tile=TILE, table=table)
    assert y.dtype == dtype and y.shape == (x.shape[0], 56)
    ref = pwl.eval_coeff(_per_row(x, wg, eid), table) * _per_row(x, wu, eid)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(y.astype(jnp.float32), ref, atol=tol, rtol=tol)


@pytest.mark.parametrize("tdtype", ["bf16", "f16"])
def test_fused_moe_glu_table_dtype_bound(tdtype):
    x, wg, wu, sizes, _ = _grouped(0, 2, 24, 32, 48)
    y32 = fused.fused_moe_glu(x, wg, wu, sizes, row_tile=TILE, table=_table())
    yq = fused.fused_moe_glu(x, wg, wu, sizes, row_tile=TILE,
                             table=_table(dtype=tdtype))
    # |gate error| * |up| — up values are O(1) here, so the raw bound holds
    err = float(jnp.max(jnp.abs(yq - y32)))
    assert err < BOUNDS[tdtype] * 4, f"{tdtype}: {err}"


def test_fused_moe_glu_single_pass_jaxpr():
    table = _table()
    x, wg, wu, sizes, _ = _grouped(0, 2, 32, 32, 32)
    jaxpr = str(jax.make_jaxpr(
        lambda *a: fused.fused_moe_glu(*a, sizes, row_tile=TILE, table=table)
    )(x, wg, wu))
    assert jaxpr.count("pallas_call") == 1, jaxpr
    assert "gather" not in jaxpr, "unfused PWL dispatch leaked"


def test_fused_moe_glu_grads_match_unfused():
    table = _table()
    x, wg, wu, sizes, eid = _grouped(0, 2, 9, 33, 21, scale=1.5)

    def fused_loss(x, wg, wu):
        return jnp.sum(fused.fused_moe_glu(x, wg, wu, sizes, row_tile=TILE,
                                           table=table) ** 2)

    def ref_loss(x, wg, wu):
        g = _per_row(x, wg, eid)
        u = _per_row(x, wu, eid)
        return jnp.sum((pwl.eval_coeff(g, table) * u) ** 2)

    g_f = jax.grad(fused_loss, argnums=(0, 1, 2))(x, wg, wu)
    g_r = jax.grad(ref_loss, argnums=(0, 1, 2))(x, wg, wu)
    for a, b in zip(g_f, g_r):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# fused_pwl_softmax kernel


def _softmax_ref(x, mask, table):
    """Unfused formulation (models/layers.py decode path) as oracle."""
    xf = x.astype(jnp.float32)
    mb = jnp.broadcast_to(mask, x.shape) if mask is not None else jnp.ones_like(xf, bool)
    s = jnp.where(mb, xf, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.maximum(pwl.eval_coeff(s - m, table), 0.0)
    p = jnp.where(mb, p, 0.0)
    return p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)


@pytest.mark.parametrize("shape", [(8, 64), (5, 7, 100), (3, 257), (2, 2, 9, 33)])
def test_fused_softmax_matches_ref(shape):
    table = _table("exp")
    x = _rand(0, shape, scale=3.0)
    y = fused.fused_pwl_softmax(x, table=table)
    np.testing.assert_allclose(y, _softmax_ref(x, None, table), atol=1e-6, rtol=1e-5)
    np.testing.assert_allclose(jnp.sum(y, -1), jnp.ones(shape[:-1]), atol=1e-5)


def test_fused_softmax_masked_and_fully_masked_rows():
    table = _table("exp")
    x = _rand(0, (6, 40), scale=3.0)
    mask = jax.random.bernoulli(jax.random.PRNGKey(1), 0.7, (6, 40))
    mask = mask.at[2].set(False)  # fully-masked row
    y = fused.fused_pwl_softmax(x, table=table, mask=mask)
    assert bool(jnp.all(jnp.isfinite(y)))
    assert bool(jnp.all(y[2] == 0.0))
    np.testing.assert_allclose(y, _softmax_ref(x, mask, table), atol=1e-6, rtol=1e-5)
    assert bool(jnp.all(jnp.where(mask, True, y == 0.0)))


def test_fused_softmax_causal_mask_matches_exact_shape():
    table = _table("exp")
    S = 48
    x = _rand(0, (2, 4, S, S), scale=2.0)
    mask = jnp.tril(jnp.ones((S, S), bool))[None, None]
    y = fused.fused_pwl_softmax(x, table=table, mask=mask)
    np.testing.assert_allclose(y, _softmax_ref(x, mask, table), atol=1e-6, rtol=1e-5)
    # close to the exact softmax too (32-bp exp table)
    exact = jax.nn.softmax(jnp.where(mask, x.astype(jnp.float32), -1e30), axis=-1)
    exact = jnp.where(mask, exact, 0.0)
    assert float(jnp.max(jnp.abs(y - exact))) < 5e-3


@pytest.mark.parametrize("tdtype", ["bf16", "f16"])
def test_fused_softmax_table_dtype_bound(tdtype):
    x = _rand(0, (8, 64), scale=3.0)
    y32 = fused.fused_pwl_softmax(x, table=_table("exp"))
    yq = fused.fused_pwl_softmax(x, table=_table("exp", dtype=tdtype))
    assert float(jnp.max(jnp.abs(yq - y32))) < BOUNDS[tdtype]


def test_fused_softmax_nonbinary_mask_selects_not_weights():
    """Contract: "nonzero = keep" — a float mask must select entries, never
    weight the renormalized probabilities."""
    table = _table("exp")
    x = _rand(0, (4, 32), scale=2.0)
    weighted = jnp.ones((4, 32)).at[:, 0].set(2.0).at[:, 5:].set(0.0)
    binary = weighted != 0
    np.testing.assert_array_equal(
        np.asarray(fused.fused_pwl_softmax(x, table=table, mask=weighted)),
        np.asarray(fused.fused_pwl_softmax(x, table=table, mask=binary)),
    )


def test_fused_softmax_bf16_scores_round_trip():
    """2-byte score inputs are upcast to f32 operands (fixed sublane floor)
    and the output comes back in the input dtype."""
    table = _table("exp")
    x = _rand(0, (8, 64), jnp.bfloat16, scale=2.0)
    y = fused.fused_pwl_softmax(x, table=table)
    assert y.dtype == jnp.bfloat16
    ref = _softmax_ref(x.astype(jnp.float32), None, table)
    np.testing.assert_allclose(y.astype(jnp.float32), ref, atol=1e-2, rtol=1e-2)


def test_fused_softmax_exact_epilogue_is_plain_softmax():
    x = _rand(0, (8, 64), scale=3.0)
    y = fused.fused_pwl_softmax(x)  # no table -> exact exp inside the kernel
    np.testing.assert_allclose(y, jax.nn.softmax(x, axis=-1), atol=1e-6, rtol=1e-5)


@pytest.mark.parametrize("causal,window", [(True, None), (True, 7), (False, 5)])
def test_fused_softmax_static_mask_matches_explicit(causal, window):
    """In-kernel iota causal/window masking == the explicit mask operand
    (and differentiates through the same recompute)."""
    table = _table("exp")
    S, T = 24, 24
    x = _rand(0, (2, 3, S, T), scale=2.0)
    qpos, kpos = jnp.arange(S)[:, None], jnp.arange(T)[None, :]
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= (qpos - kpos) < window
    y_static = fused.fused_pwl_softmax(x, table=table, causal=causal,
                                       window=window)
    y_mask = fused.fused_pwl_softmax(x, table=table, mask=mask[None, None])
    np.testing.assert_array_equal(np.asarray(y_static), np.asarray(y_mask))
    g1 = jax.grad(lambda x: jnp.sum(fused.fused_pwl_softmax(
        x, table=table, causal=causal, window=window) ** 2))(x)
    g2 = jax.grad(lambda x: jnp.sum(fused.fused_pwl_softmax(
        x, table=table, mask=mask[None, None]) ** 2))(x)
    np.testing.assert_allclose(g1, g2, atol=1e-6, rtol=1e-5)


def test_fused_softmax_maskless_grads_and_no_mask_operand():
    """The maskless variant (in-kernel iota padding mask, no materialized
    ones operand) must differentiate and match the masked result."""
    table = _table("exp")
    x = _rand(0, (4, 100), scale=2.0)  # non-128 N: iota masks the padding
    y_none = fused.fused_pwl_softmax(x, table=table)
    y_ones = fused.fused_pwl_softmax(x, table=table, mask=jnp.ones_like(x, bool))
    np.testing.assert_array_equal(np.asarray(y_none), np.asarray(y_ones))
    g = jax.grad(lambda x: jnp.sum(fused.fused_pwl_softmax(x, table=table) ** 2))(x)
    assert bool(jnp.all(jnp.isfinite(g)))


def test_fused_softmax_grads_match_recompute():
    table = _table("exp")
    x = _rand(0, (4, 33), scale=2.0)
    mask = jnp.ones((4, 33), bool).at[:, 20:].set(False)
    plan, tabs = fused.plan_and_operands(table, None)
    mf = mask.astype(jnp.float32)

    g_f = jax.grad(
        lambda x: jnp.sum(fused.fused_pwl_softmax(x, table=table, mask=mask) ** 2)
    )(x)
    g_r = jax.grad(
        lambda x: jnp.sum(fused.pwl_softmax_reference(x, mf, tabs, plan) ** 2)
    )(x)
    np.testing.assert_allclose(g_f, g_r, atol=1e-6, rtol=1e-5)


# ---------------------------------------------------------------------------
# plan-driven model paths


def _moe_cfg(**over):
    return get_reduced_config(
        "olmoe-1b-7b", dtype=jnp.float32, **over
    )


def _moe_params(cfg, key=0):
    from repro.models import transformer as T
    from repro.models.common import init_params

    return init_params(T.moe_defs(cfg), jax.random.PRNGKey(key))


def test_moe_layer_fused_matches_unfused():
    x = _rand(3, (2, 16, 64), scale=1.0)
    outs = {}
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        for impl in ("jnp", "fused"):
            cfg = _moe_cfg(act_impl=impl)
            params = _moe_params(cfg)
            y, aux, _ = moe_mod.moe_layer(cfg, params, x)
            outs[impl] = y
    assert not [w for w in rec if "falling back" in str(w.message)]
    np.testing.assert_allclose(outs["fused"], outs["jnp"], atol=1e-5, rtol=1e-4)


@pytest.mark.parametrize("tdtype", ["f32", "bf16", "f16"])
def test_moe_layer_fused_vs_unfused_all_table_dtypes(tdtype):
    """MoE fused-vs-unfused parity within table-dtype tolerance.  For f32
    tables the paths are arithmetically identical (1e-5).  For bf16/f16 the
    unfused jnp evaluation *computes* in the narrow dtype while the fused
    kernel quantizes the table then upcasts to f32 operands
    (quantize-then-upcast, docs/plans.md) — the results differ by narrow-
    format arithmetic rounding, bounded by the format's table error."""
    x = _rand(3, (2, 8, 64), scale=1.0)
    outs = {}
    for impl in ("jnp", "fused"):
        cfg = _moe_cfg(act_impl=impl, act_table_dtype=tdtype)
        params = _moe_params(cfg)
        outs[impl], _, _ = moe_mod.moe_layer(cfg, params, x)
    np.testing.assert_allclose(
        outs["fused"], outs["jnp"], atol=BOUNDS[tdtype], rtol=0.05
    )


def _attn_cfg(**over):
    return get_reduced_config("olmo-1b", dtype=jnp.float32, **over)


def _attn_params(cfg, key=0):
    from repro.models import transformer as T
    from repro.models.common import init_params

    return init_params(T.attn_defs(cfg), jax.random.PRNGKey(key))


@pytest.mark.parametrize("tdtype", ["f32", "bf16", "f16"])
def test_attention_fused_softmax_vs_unfused_all_table_dtypes(tdtype):
    """Prefill/train attention: at S <= one flash chunk the online softmax
    degenerates to the dense formulation, so fused-vs-unfused parity is
    tight (both read the same table)."""
    x = _rand(3, (2, 16, 64), scale=0.5)
    outs = {}
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        for impl in ("jnp", "fused"):
            cfg = _attn_cfg(act_impl=impl, pwl_softmax=True,
                            act_table_dtype=tdtype)
            params = _attn_params(cfg)
            y, _ = layers.attention_layer(cfg, params, x)
            outs[impl] = y
    assert not [w for w in rec if "falling back" in str(w.message)]
    np.testing.assert_allclose(
        outs["fused"], outs["jnp"], atol=BOUNDS[tdtype], rtol=0.05
    )


def test_decode_attention_fused_softmax_matches_unfused():
    cfg = _attn_cfg(act_impl="fused", pwl_softmax=True)
    cfg_ref = _attn_cfg(act_impl="jnp", pwl_softmax=True)
    B, T = 2, 12
    Hkv, dh = cfg.n_kv_heads, cfg.resolved_head_dim
    q = _rand(0, (B, 1, cfg.n_heads, dh), scale=0.5)
    kc = _rand(1, (B, T, Hkv, dh), scale=0.5)
    vc = _rand(2, (B, T, Hkv, dh), scale=0.5)
    valid = jnp.arange(T)[None, :] < jnp.array([[5], [T]])[:, 0, None]
    plan = sfu.plan_for(cfg)
    table = plan.fused_table(sfu.site_key(sfu.SITE_SOFTMAX, "exp"))
    assert table is not None
    y_fused = layers.decode_attention(q, kc, vc, valid, softmax_table=table)
    y_ref = layers.decode_attention(
        q, kc, vc, valid, exp_fn=layers.resolve_exp(cfg_ref)
    )
    np.testing.assert_allclose(y_fused, y_ref, atol=1e-5, rtol=1e-4)


def test_moe_model_end_to_end_fused_no_fallback():
    """Acceptance: an MoE config with fused moe.expert + attn.softmax runs
    end-to-end on a single device with no unfused fallback, matching the
    unfused PWL path within table tolerance."""
    from repro.models import Model

    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 512),
        "targets": jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, 512),
    }
    logits = {}
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        for impl in ("jnp", "fused"):
            cfg = _moe_cfg(act_impl=impl, pwl_softmax=True)
            if impl == "fused":
                plan = sfu.compile_plan(cfg)
                assert plan.spec("moe.expert:silu").impl == "fused"
                assert plan.spec("attn.softmax:exp").impl == "fused"
            m = Model(cfg)
            params = m.init(jax.random.PRNGKey(0))
            logits[impl], _ = m.forward(params, batch)
    assert not [w for w in rec if "falling back" in str(w.message)]
    np.testing.assert_allclose(
        logits["fused"], logits["jnp"], atol=1e-4, rtol=1e-4
    )


def test_moe_model_fused_grads_finite():
    from repro.models import Model

    cfg = _moe_cfg(act_impl="fused", pwl_softmax=True)
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 512),
        "targets": jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0, 512),
    }
    grads = jax.grad(lambda p: m.loss(p, batch)[0])(params)
    leaves = jax.tree_util.tree_leaves(grads)
    assert leaves and all(bool(jnp.all(jnp.isfinite(g))) for g in leaves)


# ---------------------------------------------------------------------------
# fallback edges: warn once, not per call


def test_fused_on_site_without_kernel_warns_once():
    """impl="fused" on a site with no fused producer (ssm) must warn on the
    first elementwise resolution and stay silent afterwards."""
    plan = sfu.ActivationPlan(sites=(
        ("ssm:silu", sfu.ApproxSpec(fn="silu", impl="fused")),
    ))
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        act = plan.act("ssm:silu")
        plan.act("ssm:silu")
        plan.act("ssm:silu")
    msgs = [w for w in rec if "falling back" in str(w.message)]
    assert len(msgs) == 1
    assert "ssm:silu" in str(msgs[0].message)
    # and the fallback is the unfused PWL evaluation
    x = jnp.linspace(-4, 4, 64)
    table = sfu.get_store().get(fn="silu", n_breakpoints=32)
    np.testing.assert_array_equal(np.asarray(act(x)),
                                  np.asarray(pwl.eval_coeff(x, table)))


def test_dense_softmax_cap_routes_to_fused_flash(monkeypatch):
    """Past the dense score cap, fused-planned attention must stay FUSED —
    the flash-attention kernel with the PWL-exp online softmax takes over
    (ISSUE 5); there is no fallback warning anymore."""
    monkeypatch.setattr(layers, "DENSE_FUSED_SOFTMAX_MAX_SCORES", 4)
    cfg = _attn_cfg(act_impl="fused", pwl_softmax=True)
    cfg_ref = _attn_cfg(act_impl="jnp", pwl_softmax=True)
    params = _attn_params(cfg)
    x = _rand(3, (2, 16, 64), scale=0.5)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        y, _ = layers.attention_layer(cfg, params, x)
    assert not [w for w in rec if "falling back" in str(w.message)]
    # the fused flash kernel reproduces the unfused PWL flash formulation
    y_ref, _ = layers.attention_layer(cfg_ref, params, x)
    np.testing.assert_allclose(y, y_ref, atol=1e-5, rtol=1e-5)


def test_narrow_sliding_window_routes_to_fused_flash():
    """A local-attention layer whose window covers under half the KV must
    run the fused flash kernel's banded KV loop (skipped out-of-window
    blocks), not dense fused scores — and not fall back (ISSUE 5)."""
    cfg = _attn_cfg(act_impl="fused", pwl_softmax=True, sliding_window=4)
    cfg_ref = _attn_cfg(act_impl="jnp", pwl_softmax=True, sliding_window=4)
    params = _attn_params(cfg)
    x = _rand(3, (2, 16, 64), scale=0.5)  # S=16 > 2*window
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        y, _ = layers.attention_layer(cfg, params, x, kind="attn_local")
    assert not [w for w in rec if "falling back" in str(w.message)]
    y_ref, _ = layers.attention_layer(cfg_ref, params, x, kind="attn_local")
    np.testing.assert_allclose(y, y_ref, atol=1e-5, rtol=1e-5)


def test_wide_sliding_window_stays_fused():
    """A window covering most of the KV keeps the fused dense path (the
    in-kernel window iota mask matches the banded flash result)."""
    cfg = _attn_cfg(act_impl="fused", pwl_softmax=True, sliding_window=12)
    cfg_ref = _attn_cfg(act_impl="jnp", pwl_softmax=True, sliding_window=12)
    params = _attn_params(cfg)
    x = _rand(3, (2, 16, 64), scale=0.5)  # S=16 <= 2*window
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        y, _ = layers.attention_layer(cfg, params, x, kind="attn_local")
    assert not [w for w in rec if "falling back" in str(w.message)]
    y_ref, _ = layers.attention_layer(cfg_ref, params, x, kind="attn_local")
    np.testing.assert_allclose(y, y_ref, atol=2e-5, rtol=1e-4)


def test_wide_decode_cache_routes_to_fused_flash(monkeypatch):
    """Cache rows wider than the dense kernel's VMEM-resident cap must run
    the fused flash kernel's blocked KV loop (ragged kv_valid_len masking)
    — still fused, no fallback warning (ISSUE 5)."""
    monkeypatch.setattr(layers, "DENSE_FUSED_SOFTMAX_MAX_WIDTH", 8)
    cfg = _attn_cfg(act_impl="fused", pwl_softmax=True)
    cfg_ref = _attn_cfg(act_impl="jnp", pwl_softmax=True)
    B, T = 2, 12  # T > patched width cap
    Hkv, dh = cfg.n_kv_heads, cfg.resolved_head_dim
    params = _attn_params(cfg)
    x = _rand(3, (B, 1, 64), scale=0.5)
    cache = {
        "k": _rand(1, (B, T, Hkv, dh), scale=0.5),
        "v": _rand(2, (B, T, Hkv, dh), scale=0.5),
    }
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        y, _ = layers.attention_layer(cfg, params, x, cache=cache, cache_pos=5)
    assert not [w for w in rec if "falling back" in str(w.message)]
    y_ref, _ = layers.attention_layer(cfg_ref, params, x, cache=cache, cache_pos=5)
    np.testing.assert_allclose(y, y_ref, atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# act_site_specs config migration


def test_act_site_specs_pin_exempts_single_site():
    """An act_site_specs exact pin exempts exactly its site — the plan-native
    replacement for the deleted pwl_exempt string knob."""
    pinned = ModelConfig(
        name="t", family="ssm", n_layers=2, d_model=16, n_heads=2,
        n_kv_heads=2, d_ff=32, vocab_size=64, act_impl="jnp",
        act_breakpoints=32, ssm_state=8,
        act_site_specs=(
            ("ssm:silu", sfu.ApproxSpec(fn="silu", impl="exact")),
        ),
    )
    plan = sfu.compile_plan(pinned)
    assert plan.spec("ssm:silu").impl == "exact"
    assert plan.spec("mlp:silu").impl == "jnp"
    assert plan.spec("ssm:softplus").impl == "jnp"


def test_act_site_specs_can_pin_segments_and_dtype():
    cfg = ModelConfig(
        name="t", family="dense", n_layers=2, d_model=16, n_heads=2,
        n_kv_heads=2, d_ff=32, vocab_size=64, act_impl="jnp",
        activation="gelu",
        act_site_specs=(
            ("mlp:gelu", sfu.ApproxSpec(fn="gelu", n_segments=9,
                                        dtype="bf16", impl="kernel")),
        ),
    )
    spec = sfu.compile_plan(cfg).spec("mlp:gelu")
    assert (spec.n_segments, spec.dtype, spec.impl) == (9, "bf16", "kernel")


def test_act_site_specs_unmatched_pin_raises():
    """A pin that matches no instantiated site must fail fast — silently
    dropping it would undo the accuracy exemption it exists to enforce."""
    cfg = ModelConfig(
        name="t", family="dense", n_layers=2, d_model=16, n_heads=2,
        n_kv_heads=2, d_ff=32, vocab_size=64, act_impl="jnp",
        act_site_specs=(
            ("ssm.silu", sfu.ApproxSpec(fn="silu", impl="exact")),  # typo'd
        ),
    )
    with pytest.raises(ValueError, match="ssm.silu"):
        sfu.compile_plan(cfg)


def test_shipped_ssm_configs_pin_ssm_silu_exact():
    for arch in ("mamba2-2.7b", "jamba-v0.1-52b"):
        for mode in ("jnp", "kernel", "fused"):
            plan = sfu.compile_plan(get_config(arch, act_impl=mode))
            assert plan.spec("ssm:silu").impl == "exact", (arch, mode)
