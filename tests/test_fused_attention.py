"""Fused flash attention with the PWL-exp online softmax (ISSUE 5).

Covers the acceptance criteria: the kernel matches the pure-JAX flash
formulation it replaces (same online-softmax math, PWL exp on shifted
scores AND correction factors) across table dtypes, causal/window/ragged-KV
edges, and GQA shapes; its custom VJP matches autodiff of the dense jnp
recompute; native narrow-dtype table operands decode bit-identically to the
legacy quantize-then-upcast packing; and fused-planned ``attn.softmax:``
sites execute with ZERO fallback warnings at S=16k causal prefill and
window=256 local attention — on a single device and under a 1-device mesh
(multi-device meshes run the kernel per-shard; see tests/test_shard_fused.py).
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro  # noqa: F401
from repro import sfu
from repro.kernels import fused
from repro.launch.mesh import make_mesh
from repro.models import layers

BOUNDS = {"f32": 1e-5, "bf16": 0.08, "f16": 0.02}


def _rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(jax.random.PRNGKey(key), shape) * scale).astype(dtype)


def _table(dtype="f32", n_bp=32):
    return sfu.get_store().get(fn="exp", n_breakpoints=n_bp, dtype=dtype)


def _pwl_exp(table):
    """The elementwise PWL exp of the jnp flash path (the production
    closure — layers.pwl_exp_fn is what resolve_exp builds)."""
    return layers.pwl_exp_fn(table)


def _qkv(key, B=2, S=64, T=None, H=4, Hkv=2, dh=16):
    T = T or S
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(key), 3)
    return (
        jax.random.normal(k1, (B, S, H, dh)),
        jax.random.normal(k2, (B, T, Hkv, dh)),
        jax.random.normal(k3, (B, T, Hkv, dh)),
    )


@pytest.fixture(autouse=True)
def _fresh_fallback_state():
    sfu.reset_fused_fallback_warnings()
    yield
    sfu.reset_fused_fallback_warnings()


# ---------------------------------------------------------------------------
# kernel parity vs the jnp flash formulation it replaces


@pytest.mark.parametrize("S,bq,bkv", [(64, 16, 128), (63, 16, 128),
                                      (512, 128, 128)])
def test_causal_matches_jnp_flash(S, bq, bkv):
    """With matching block sizes the kernel's online-softmax chaining is the
    same sequence of PWL-exp updates as the jnp flash scan — near-bitwise."""
    table = _table()
    q, k, v = _qkv(0, S=S)
    y = fused.fused_flash_attention(q, k, v, table=table, causal=True,
                                    block_q=bq, block_kv=bkv)
    ref = layers.flash_attention(q, k, v, causal=True, exp_fn=_pwl_exp(table),
                                 q_chunk=bq, kv_chunk=bkv,
                                 allow_causal_unroll=False)
    np.testing.assert_allclose(y, ref, atol=1e-6, rtol=1e-5)


def test_block_size_invariance():
    """Different KV blockings chain different PWL correction factors; the
    result must stay within table-approximation jitter of one another."""
    table = _table()
    q, k, v = _qkv(1, S=512)
    y1 = fused.fused_flash_attention(q, k, v, table=table, causal=True,
                                     block_q=128, block_kv=128)
    y2 = fused.fused_flash_attention(q, k, v, table=table, causal=True,
                                     block_q=256, block_kv=512)
    np.testing.assert_allclose(y1, y2, atol=5e-3, rtol=5e-3)


def test_windowed_matches_jnp_flash():
    table = _table()
    q, k, v = _qkv(2, S=96)
    y = fused.fused_flash_attention(q, k, v, table=table, causal=True,
                                    window=12, block_q=32, block_kv=128)
    ref = layers.flash_attention(q, k, v, causal=True, window=12,
                                 exp_fn=_pwl_exp(table), q_chunk=32,
                                 kv_chunk=128, allow_causal_unroll=False)
    np.testing.assert_allclose(y, ref, atol=1e-6, rtol=1e-5)


@pytest.mark.parametrize("T,vl", [(64, (17, 64)), (512, (10, 400))])
def test_ragged_kv_valid_len_matches_jnp_flash(T, vl):
    """Ragged caches match the jnp flash path — including multi-KV-block
    grids where blocks past the valid prefix are skipped per batch row
    (batch 0 runs 1 of 4 blocks at vl=10, batch 1 runs 4)."""
    table = _table()
    q, k, v = _qkv(3, S=32, T=T)
    vl = jnp.array(vl)
    y = fused.fused_flash_attention(q, k, v, table=table, causal=False,
                                    kv_valid_len=vl, block_q=16, block_kv=128)
    ref = layers.flash_attention(q, k, v, causal=False, exp_fn=_pwl_exp(table),
                                 q_chunk=16, kv_chunk=128, kv_valid_len=vl)
    np.testing.assert_allclose(y, ref, atol=1e-6, rtol=1e-5)


def test_cross_attention_no_mask():
    table = _table()
    q, k, v = _qkv(4, S=32, T=80)
    y = fused.fused_flash_attention(q, k, v, table=table, causal=False,
                                    block_q=16, block_kv=128)
    ref = layers.flash_attention(q, k, v, causal=False, exp_fn=_pwl_exp(table),
                                 q_chunk=16, kv_chunk=128)
    np.testing.assert_allclose(y, ref, atol=1e-6, rtol=1e-5)


@pytest.mark.parametrize("H,Hkv", [(4, 4), (4, 2), (8, 2), (3, 1)])
def test_gqa_head_shapes(H, Hkv):
    """(Hkv major, G minor) head split must match flash_attention exactly,
    including H == Hkv (MHA) and Hkv == 1 (MQA)."""
    table = _table()
    q, k, v = _qkv(5, S=48, H=H, Hkv=Hkv)
    y = fused.fused_flash_attention(q, k, v, table=table, causal=True,
                                    block_q=16, block_kv=128)
    ref = layers.flash_attention(q, k, v, causal=True, exp_fn=_pwl_exp(table),
                                 q_chunk=16, kv_chunk=128,
                                 allow_causal_unroll=False)
    np.testing.assert_allclose(y, ref, atol=1e-6, rtol=1e-5)


def test_exact_exp_epilogue_matches_softmax_attention():
    """act="exp" (no table) runs the exact exponential in the same online
    formulation — equal to plain softmax attention."""
    import math

    q, k, v = _qkv(6, S=40, H=2, Hkv=2)
    y = fused.fused_flash_attention(q, k, v, act="exp", causal=False,
                                    block_q=8, block_kv=128)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    np.testing.assert_allclose(y, ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("tdtype", ["bf16", "f16"])
def test_table_dtype_bound(tdtype):
    q, k, v = _qkv(7, S=64)
    y32 = fused.fused_flash_attention(q, k, v, table=_table(), causal=True,
                                      block_q=16, block_kv=128)
    yq = fused.fused_flash_attention(q, k, v, table=_table(tdtype),
                                     causal=True, block_q=16, block_kv=128)
    # attention outputs are convex combinations of V rows (|V| ~ N(0,1)),
    # so probability-level table error can amplify by the value magnitudes
    assert float(jnp.max(jnp.abs(yq - y32))) < BOUNDS[tdtype] * 4


def test_bf16_inputs_round_trip():
    table = _table()
    q, k, v = _qkv(8, S=32)
    qb, kb, vb = (a.astype(jnp.bfloat16) for a in (q, k, v))
    y = fused.fused_flash_attention(qb, kb, vb, table=table, causal=True)
    assert y.dtype == jnp.bfloat16
    ref = fused.fused_flash_attention(
        qb.astype(jnp.float32), kb.astype(jnp.float32),
        vb.astype(jnp.float32), table=table, causal=True)
    np.testing.assert_allclose(y.astype(jnp.float32), ref, atol=2e-2,
                               rtol=2e-2)


def test_single_kernel_dispatch_jaxpr():
    table = _table()
    q, k, v = _qkv(9, S=32)
    jaxpr = str(jax.make_jaxpr(
        lambda *a: fused.fused_flash_attention(*a, table=table, causal=True)
    )(q, k, v))
    assert jaxpr.count("pallas_call") == 1, jaxpr
    assert "gather" not in jaxpr, "unfused PWL dispatch leaked"


# ---------------------------------------------------------------------------
# custom VJP: fused forward, dense jnp recompute backward


def test_grads_match_dense_recompute():
    """The backward pass IS autodiff of the dense pwl reference — assert the
    custom VJP plumbs it through exactly (q, k, and v cotangents)."""
    from repro.kernels.fused import attention as A

    table = _table()
    q, k, v = _qkv(10, S=24, H=2, Hkv=1)
    plan, tables = fused.plan_and_operands(table, None)

    def fused_loss(q, k, v):
        return jnp.sum(fused.fused_flash_attention(
            q, k, v, table=table, causal=True, window=7,
            block_q=8, block_kv=128) ** 2)

    # the loss gradient flows through d(out)/d(inputs) of the recompute, at
    # the KERNEL's forward value: grad = vjp_ref(2 * y_kernel)
    y = fused.fused_flash_attention(q, k, v, table=table, causal=True,
                                    window=7, block_q=8, block_kv=128)
    _, ref_vjp = jax.vjp(
        lambda qq, kk, vv: A._reference_attention(
            qq, kk, vv, None, tables, plan, True, 7, 0),
        q, k, v,
    )
    want = ref_vjp(2.0 * y.astype(jnp.float32))
    got = jax.grad(fused_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


def test_grads_close_to_jnp_flash_grads():
    table = _table()
    q, k, v = _qkv(11, S=48)

    def f_loss(q, k, v):
        return jnp.sum(fused.fused_flash_attention(
            q, k, v, table=table, causal=True, block_q=16, block_kv=128) ** 2)

    def r_loss(q, k, v):
        return jnp.sum(layers.flash_attention(
            q, k, v, causal=True, exp_fn=_pwl_exp(table), q_chunk=16,
            kv_chunk=128, allow_causal_unroll=False) ** 2)

    g_f = jax.grad(f_loss, argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(r_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_f, g_r):
        assert bool(jnp.all(jnp.isfinite(a)))
        # forward formulations agree to ~1e-6; backwards differ only by the
        # dense-vs-online recompute of the same PWL softmax
        np.testing.assert_allclose(a, b, atol=5e-3, rtol=5e-3)


def test_ragged_grads_finite_and_masked():
    table = _table()
    q, k, v = _qkv(12, S=16, T=32)
    vl = jnp.array([9, 32])

    g = jax.grad(lambda kk: jnp.sum(fused.fused_flash_attention(
        q, kk, v, table=table, causal=False, kv_valid_len=vl,
        block_q=8, block_kv=128) ** 2))(k)
    assert bool(jnp.all(jnp.isfinite(g)))
    # keys past the valid prefix of batch row 0 must get zero gradient
    np.testing.assert_array_equal(np.asarray(g[0, 9:]),
                                  np.zeros_like(np.asarray(g[0, 9:])))


# ---------------------------------------------------------------------------
# native narrow-dtype table operands (ISSUE 5 satellite)


@pytest.mark.parametrize("tdtype", ["bf16", "f16"])
def test_native_operands_bit_identical_to_upcast_pack(tdtype):
    """pack_table ships narrow tables natively (raw rows in the storage
    format, upcast in-register); the decode must be BIT-IDENTICAL to the
    legacy quantize-then-upcast f32 delta packing of the same table."""
    t = sfu.get_store().get(fn="gelu", n_breakpoints=32, dtype=tdtype)
    bp_n, mq_n = fused.pack_table(t)                 # native (default)
    bp_u, dmq_u = fused.pack_table(t, native=False)  # legacy upcast deltas
    assert str(mq_n.dtype) in ("bfloat16", "float16")
    assert dmq_u.dtype == jnp.float32
    x = jnp.linspace(-9.0, 9.0, 4096).reshape(32, 128)
    y_native = fused.pwl_eval_tile(x, bp_n, mq_n, 32)
    y_upcast = fused.pwl_eval_tile(x, bp_u, dmq_u, 32)
    np.testing.assert_array_equal(np.asarray(y_native), np.asarray(y_upcast))


@pytest.mark.parametrize("tdtype", ["bf16", "f16"])
def test_native_operands_through_fused_kernels(tdtype):
    """The Pallas kernels consume native narrow operands end-to-end and
    reproduce the upcast-pack results exactly (standalone + flash)."""
    from repro.kernels import ops
    from repro.kernels.fused.epilogue import EpiloguePlan

    t = sfu.get_store().get(fn="exp", n_breakpoints=32, dtype=tdtype)
    x = _rand(0, (16, 256), scale=3.0) - 2.0
    y_native = ops.pwl_activation(x, t)
    # force the legacy packing through the same kernel body, compiled as the
    # kernel is: op by op, XLA contracts the value's FMA chain differently
    # and the two differ by an ulp
    bp_u, dmq_u = fused.pack_table(t, native=False)
    y_upcast, _ = jax.jit(
        lambda x: fused.pwl_value_and_slope_tile(x, bp_u, dmq_u, 32))(x)
    np.testing.assert_allclose(np.asarray(y_native), np.asarray(y_upcast),
                               atol=1e-7, rtol=1e-7)
    # flash attention with a native table runs one pallas_call and stays
    # within the format bound of the f32-table result
    q, k, v = _qkv(13, S=32)
    y_q = fused.fused_flash_attention(q, k, v, table=t, causal=True)
    y_32 = fused.fused_flash_attention(q, k, v, table=_table(), causal=True)
    assert float(jnp.max(jnp.abs(y_q - y_32))) < BOUNDS[tdtype] * 4
    # the epilogue plan records the storage format
    plan, _ = fused.plan_and_operands(t, None)
    assert plan == EpiloguePlan("pwl", 32, tdtype)


# ---------------------------------------------------------------------------
# plan-driven dispatch: fused everywhere, zero fallback warnings


def _attn_cfg(**over):
    from repro.configs import get_reduced_config

    return get_reduced_config("olmo-1b", dtype=jnp.float32, **over)


def _attn_params(cfg, key=0):
    from repro.models import transformer as T
    from repro.models.common import init_params

    return init_params(T.attn_defs(cfg), jax.random.PRNGKey(key))


def test_prefill_past_score_cap_runs_flash_kernel(monkeypatch):
    """Past the dense cap the layer path must emit the fused flash kernel
    (exactly one pallas_call for attention) and warn nothing."""
    monkeypatch.setattr(layers, "DENSE_FUSED_SOFTMAX_MAX_SCORES", 4)
    cfg = _attn_cfg(act_impl="fused", pwl_softmax=True)
    params = _attn_params(cfg)
    x = _rand(3, (2, 16, 64), scale=0.5)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        jaxpr = str(jax.make_jaxpr(
            lambda x: layers.attention_layer(cfg, params, x)[0]
        )(x))
    assert not [w for w in rec if "falling back" in str(w.message)]
    assert jaxpr.count("pallas_call") == 1, "fused flash kernel not emitted"
    assert "while" not in jaxpr and "scan" not in jaxpr, (
        "jnp flash scan leaked into a fused-planned site"
    )


def test_acceptance_16k_prefill_and_window256_no_fallback():
    """ISSUE 5 acceptance: fused-planned attn.softmax sites execute with
    zero fallback warnings at S=16k causal prefill and window=256 local
    attention on a single device (trace-level — warnings fire at trace)."""
    cfg = _attn_cfg(act_impl="fused", pwl_softmax=True,
                    sliding_window=256)
    plan = sfu.plan_for(cfg)
    exp_fn = layers.resolve_exp(cfg, plan)
    S = 16384
    dh = cfg.resolved_head_dim
    q = jax.ShapeDtypeStruct((1, S, cfg.n_heads, dh), jnp.float32)
    kv = jax.ShapeDtypeStruct((1, S, cfg.n_kv_heads, dh), jnp.float32)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        # 16k causal prefill (global layer)
        out = jax.eval_shape(
            lambda q, k, v: layers._attn_softmax_dispatch(
                cfg, q, k, v, causal=True, window=None, exp_fn=exp_fn,
                plan=plan),
            q, kv, kv,
        )
        # window=256 local attention at 16k (covers < half the KV)
        out_w = jax.eval_shape(
            lambda q, k, v: layers._attn_softmax_dispatch(
                cfg, q, k, v, causal=True, window=256, exp_fn=exp_fn,
                plan=plan),
            q, kv, kv,
        )
    assert not [w for w in rec if "falling back" in str(w.message)], [
        str(w.message) for w in rec
    ]
    assert out.shape == (1, S, cfg.n_heads, dh)
    assert out_w.shape == (1, S, cfg.n_heads, dh)


def test_small_problem_keeps_dense_fast_path():
    """Under every threshold the dense PWL-exp softmax kernel remains the
    executor (it is the fast path, not a fallback)."""
    assert layers._dense_softmax_preferred(1024, 64, None, 64)
    assert not layers._dense_softmax_preferred(
        layers.DENSE_FUSED_SOFTMAX_MAX_SCORES + 1, 64, None, 64)
    assert not layers._dense_softmax_preferred(
        1024, layers.DENSE_FUSED_SOFTMAX_MAX_WIDTH + 1,
        None, layers.DENSE_FUSED_SOFTMAX_MAX_WIDTH + 1)
    assert not layers._dense_softmax_preferred(1024, 1024, 256, 1024)
    assert layers._dense_softmax_preferred(1024, 1024, 600, 1024)


def test_one_device_mesh_keeps_fused_and_never_warns():
    """An active mesh no longer forces the unfused fallback.  On a 1-device
    mesh the shard-aware predicate (active_mesh_rules) is None, the fused
    kernel dispatches directly, and NOTHING warns — the old blanket
    ``mesh.size > 1`` gate is gone."""
    from repro.distributed.sharding import (
        active_mesh_rules, make_rules, use_rules,
    )

    cfg = _attn_cfg(act_impl="fused", pwl_softmax=True)
    plan = sfu.plan_for(cfg)
    exp_fn = layers.resolve_exp(cfg, plan)
    q, k, v = _qkv(14, S=16, H=cfg.n_heads, Hkv=cfg.n_kv_heads,
                   dh=cfg.resolved_head_dim)
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = make_rules(cfg, mesh)
    sfu.reset_fused_fallback_warnings()
    with use_rules(rules):
        assert active_mesh_rules() is None  # 1-device mesh: run direct
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            jaxpr = str(jax.make_jaxpr(
                lambda q, k, v: layers._attn_softmax_dispatch(
                    cfg, q, k, v, causal=True, window=None, exp_fn=exp_fn,
                    plan=plan)
            )(q, k, v))
    assert not [w for w in rec if "falling back" in str(w.message)], [
        str(w.message) for w in rec
    ]
    assert "pallas_call" in jaxpr, "fused kernel lost under a 1-device mesh"
