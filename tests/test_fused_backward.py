"""Grad parity: fused Pallas backward kernels vs the jnp-recompute oracle.

Every fused op (linear / glu / moe / rmsnorm / softmax / attention) carries a
custom VJP with two interchangeable backward implementations:

  impl_bwd="fused"      Pallas kernels that decode the per-segment PWL
                        *slope* in-kernel — the slope IS the activation
                        derivative (paper Sec. II: the approximation is
                        piecewise-linear, so its derivative is exactly the
                        segment coefficient m_i)
  impl_bwd="recompute"  pure-jnp rematerialization through
                        ``plan_value_and_slope`` — the oracle

This suite pins fused == recompute across table dtypes (f32/bf16/f16/int8),
segment counts (8..64), op variants (bias/no-bias, GLU, MoE, causal /
sliding-window / ragged / GQA attention), and odd shapes that exercise
block-edge masking.

Inputs are drawn on an **integer grid** (random integers scaled by 2^-3,
attention head dim 64 so softmax scale = 1/8 is exact): every blocked f32
partial sum the kernels form is then exactly representable, so the fused
and jnp pre-activations agree bitwise and the strict tolerances below can
never flake on a knife-edge segment or argmax-tie flip.  The decode itself
is shared (``EpiloguePlan.apply_value_and_slope`` runs in the kernels and
in the oracle), which is what makes the exact-breakpoint test *bitwise*:
the strict ``x > bp_i`` compare gives the LEFT segment ownership of inputs
landing exactly on a breakpoint — value and slope — in both paths.

The memory test pins the tentpole's headline property: the attention
backward's compiled temp footprint no longer scales with S*T (no dense
score tensor is ever materialized), while the recompute oracle's does.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro  # noqa: F401
import mem_utils
from repro import sfu
from repro.kernels import fused
from repro.kernels.fused.epilogue import plan_value_and_slope

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

# small blocks so every grid axis takes multiple steps (edge masking live)
BLK = (16, 32, 16)
TABLE_DTYPES = ["f32", "bf16", "f16", "int8"]
SEGMENTS = [8, 16, 32, 64]


def _table(fn="gelu", n_bp=32, dtype="f32"):
    return sfu.get_store().get(fn=fn, n_breakpoints=n_bp, dtype=dtype)


def _igrid(key, shape, span=16, step=0.125):
    """Integer-grid reals: exact under blocked f32 accumulation."""
    ints = jax.random.randint(jax.random.PRNGKey(key), shape, -span, span + 1)
    return ints.astype(jnp.float32) * step


def _grads(f, *args):
    loss = lambda *a: jnp.sum(jnp.cos(f(*a).astype(jnp.float32)))
    return jax.grad(loss, argnums=tuple(range(len(args))))(*args)


def _parity(f, *args, rel=1e-5, bitwise=False):
    """Grads of ``f(*args, impl_bwd=...)``: fused vs recompute."""
    gf = _grads(lambda *a: f(*a, impl_bwd="fused"), *args)
    gr = _grads(lambda *a: f(*a, impl_bwd="recompute"), *args)
    for i, (a, b) in enumerate(zip(gf, gr)):
        a, b = np.asarray(a), np.asarray(b)
        if bitwise:
            np.testing.assert_array_equal(a, b, err_msg=f"arg {i}")
        else:
            scale = max(float(np.max(np.abs(b))), 1e-12)
            np.testing.assert_allclose(
                a, b, atol=rel * scale, rtol=rel, err_msg=f"arg {i}"
            )
    return gf


# ---------------------------------------------------------------------------
# matmul-family epilogues: linear / glu / moe / rmsnorm


@pytest.mark.parametrize("table_dtype", TABLE_DTYPES)
@pytest.mark.parametrize("n_bp", SEGMENTS)
def test_linear_grad_parity(table_dtype, n_bp):
    table = _table("gelu", n_bp, table_dtype)
    x = _igrid(0, (19, 33))
    w = _igrid(1, (33, 21), span=4)
    b = _igrid(2, (21,), span=4)
    _parity(
        lambda x, w, b, **kw: fused.fused_linear(
            x, w, b, table=table, block=BLK, **kw
        ),
        x, w, b, rel=1e-6,
    )


def test_linear_no_bias_grad_parity():
    table = _table("silu")
    x = _igrid(0, (2, 5, 33))  # leading batch dims
    w = _igrid(1, (33, 40), span=4)
    _parity(
        lambda x, w, **kw: fused.fused_linear(x, w, table=table, block=BLK, **kw),
        x, w, rel=1e-6,
    )


@pytest.mark.parametrize("table_dtype", TABLE_DTYPES)
def test_glu_grad_parity(table_dtype):
    table = _table("silu", 32, table_dtype)
    x = _igrid(0, (37, 33))
    wg = _igrid(1, (33, 24), span=4)
    wu = _igrid(2, (33, 24), span=4)
    _parity(
        lambda x, wg, wu, **kw: fused.fused_glu(
            x, wg, wu, table=table, block=BLK, **kw
        ),
        x, wg, wu, rel=1e-6,
    )


def test_moe_grad_parity():
    table = _table("silu")
    # three experts' groups of 32, 16 and 0 rows (row tile 16), then 16
    # rows past the last group
    x = _igrid(0, (64, 33))
    wg = _igrid(1, (3, 33, 24), span=4)
    wu = _igrid(2, (3, 33, 24), span=4)
    sizes = jnp.asarray([32, 16, 0], jnp.int32)
    _parity(
        lambda x, wg, wu, **kw: fused.fused_moe_glu(
            x, wg, wu, sizes, row_tile=16, table=table, **kw
        ),
        x, wg, wu, rel=1e-6,
    )


@pytest.mark.parametrize("table_dtype", ["f32", "bf16", "int8"])
def test_rmsnorm_grad_parity(table_dtype):
    table = _table("gelu", 32, table_dtype)
    x = _igrid(0, (21, 48))
    s = _igrid(1, (48,), span=4)
    _parity(
        lambda x, s, **kw: fused.fused_rmsnorm(
            x, s, table=table, block_rows=16, **kw
        ),
        x, s, rel=1e-5,
    )


def test_identity_epilogue_grad_parity():
    # no table: the backward shortcut dz = g must match plain autodiff
    x = _igrid(0, (17, 34))
    w = _igrid(1, (34, 21), span=4)
    gf = _parity(
        lambda x, w, **kw: fused.fused_linear(x, w, block=BLK, **kw),
        x, w, bitwise=True,
    )
    ref = jax.grad(lambda x, w: jnp.sum(jnp.cos(x @ w)), argnums=(0, 1))(x, w)
    for a, b in zip(gf, ref):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-6)


# ---------------------------------------------------------------------------
# softmax: the row max IS differentiated (see kernels/fused/softmax.py —
# for PWL exp the max-shift gradient does NOT cancel like it does for true
# exp, so the backward carries the tie-split dm term)


@pytest.mark.parametrize("n_bp", SEGMENTS)
def test_softmax_grad_parity(n_bp):
    table = _table("exp", n_bp)
    x = _igrid(0, (12, 24), span=12)
    _parity(
        lambda x, **kw: fused.fused_pwl_softmax(
            x, table=table, block_rows=8, **kw
        ),
        x, rel=1e-5,
    )


def test_softmax_causal_and_mask_grad_parity():
    table = _table("exp")
    x = _igrid(0, (2, 6, 11), span=12)
    _parity(
        lambda x, **kw: fused.fused_pwl_softmax(
            x, table=table, causal=True, block_rows=8, **kw
        ),
        x, rel=1e-5,
    )
    xm = _igrid(1, (12, 24), span=12)
    mask = (_igrid(2, (12, 24)) > 0).astype(jnp.float32)
    _parity(
        lambda x, **kw: fused.fused_pwl_softmax(
            x, table=table, mask=mask, block_rows=8, **kw
        ),
        xm, rel=1e-5,
    )


def test_softmax_argmax_tie_grad_parity():
    # duplicated maxima: the dm term must split across ties identically
    table = _table("exp")
    x = _igrid(0, (8, 16), span=4)
    x = x.at[:, :3].set(jnp.max(x, axis=-1, keepdims=True) + 1.0)
    _parity(
        lambda x, **kw: fused.fused_pwl_softmax(
            x, table=table, block_rows=8, **kw
        ),
        x, rel=1e-5,
    )


# ---------------------------------------------------------------------------
# attention: blocked flash backward (4 Pallas passes, O(S) stats, no dense
# (S, T) score tensor) vs dense-reference autodiff


def _attn_qkv(B=2, S=20, H=2, Hkv=2, dh=64, span=8):
    q = _igrid(10, (B, S, H, dh), span=span)
    k = _igrid(11, (B, S, Hkv, dh), span=span)
    v = _igrid(12, (B, S, Hkv, dh), span=span)
    return q, k, v


def _attn_parity(q, k, v, rel=1e-5, **attn_kw):
    _parity(
        lambda q, k, v, **kw: fused.fused_flash_attention(
            q, k, v, block_q=8, block_kv=128, **attn_kw, **kw
        ),
        q, k, v, rel=rel,
    )


def test_attention_causal_grad_parity():
    q, k, v = _attn_qkv()
    _attn_parity(q, k, v, table=_table("exp"), causal=True)


def test_attention_window_grad_parity():
    q, k, v = _attn_qkv()
    _attn_parity(q, k, v, table=_table("exp"), causal=True, window=7)


def test_attention_ragged_grad_parity():
    q, k, v = _attn_qkv()
    vl = jnp.array([9.0, 17.0])
    _attn_parity(q, k, v, table=_table("exp"), causal=False, kv_valid_len=vl)


def test_attention_gqa_grad_parity():
    q, k, v = _attn_qkv(H=4, Hkv=2)
    _attn_parity(q, k, v, table=_table("exp"), causal=True)


def test_attention_odd_shape_block_edges():
    # S=19 with block_q=8: the last q block is ragged; T=13 pads inside
    # the single kv block — both edges must mask identically in fwd+bwd
    q = _igrid(10, (1, 19, 2, 64), span=8)
    k = _igrid(11, (1, 13, 2, 64), span=8)
    v = _igrid(12, (1, 13, 2, 64), span=8)
    _attn_parity(q, k, v, table=_table("exp"), causal=False)


@pytest.mark.parametrize("table_dtype", ["bf16", "int8"])
def test_attention_table_dtype_grad_parity(table_dtype):
    q, k, v = _attn_qkv(B=1)
    _attn_parity(q, k, v, table=_table("exp", 32, table_dtype), causal=True)


def test_attention_small_table_grad_parity():
    q, k, v = _attn_qkv(B=1)
    _attn_parity(q, k, v, table=_table("exp", 8), causal=True)


def test_attention_exact_exp_grad_parity():
    # act="exp" epilogue: slope comes from jax.vjp inside the kernel
    q, k, v = _attn_qkv(B=1)
    _attn_parity(q, k, v, act="exp", causal=True)


# ---------------------------------------------------------------------------
# breakpoint-boundary convention: exactly ON a breakpoint the LEFT segment
# owns value AND slope (strict x > bp compare), bitwise across paths


@pytest.mark.parametrize("table_dtype", TABLE_DTYPES)
def test_breakpoint_boundary_bitwise(table_dtype):
    table = _table("gelu", 32, table_dtype)
    plan, operands = fused.plan_and_operands(table)
    bp = np.asarray(jnp.asarray(operands[0], jnp.float32)).reshape(-1)
    # exact breakpoints, plus off-boundary controls straddling each one
    z = jnp.asarray(
        np.concatenate([bp, np.nextafter(bp, np.inf), np.nextafter(bp, -np.inf)]),
        jnp.float32,
    ).reshape(-1, 1)
    w = jnp.ones((1, 1), jnp.float32)  # K=1 identity: pre-activation == z

    val_ref, slope_ref = plan_value_and_slope(plan, operands, z)

    for mode in fused.IMPL_BWD_MODES:
        f = lambda x: fused.fused_linear(x, w, table=table, block=BLK, impl_bwd=mode)
        # the VALUE decode's dm*x + prev chain is subject to XLA FMA
        # contraction, which rounds differently across compilation
        # contexts — pin it to ~1 ulp, not bitwise
        np.testing.assert_allclose(
            np.asarray(f(z)), np.asarray(val_ref),
            rtol=1e-6, atol=1e-6, err_msg=f"value ({mode})",
        )
        # the SLOPE decode is contraction-immune (gate * dm is exact for
        # gate in {0, 1}), so segment ownership at the boundary — and the
        # backward's d/dz = act'(z) — is bitwise in both impl_bwd modes
        dz = jax.grad(lambda x: jnp.sum(f(x)))(z)
        np.testing.assert_array_equal(
            np.asarray(dz), np.asarray(slope_ref), err_msg=f"slope ({mode})"
        )


# ---------------------------------------------------------------------------
# impl_bwd selection machinery


def test_use_impl_bwd_contextmanager():
    table = _table("gelu")
    x, w = _igrid(0, (17, 33)), _igrid(1, (33, 21), span=4)
    f = lambda x: jnp.sum(jnp.cos(fused.fused_linear(x, w, table=table, block=BLK)))
    assert fused.current_impl_bwd() == "fused"
    g_default = jax.grad(f)(x)
    with fused.use_impl_bwd("recompute"):
        assert fused.current_impl_bwd() == "recompute"
        g_ctx = jax.grad(f)(x)
    assert fused.current_impl_bwd() == "fused"
    g_explicit = jax.grad(
        lambda x: jnp.sum(jnp.cos(fused.fused_linear(
            x, w, table=table, block=BLK, impl_bwd="recompute")))
    )(x)
    np.testing.assert_array_equal(np.asarray(g_ctx), np.asarray(g_explicit))
    np.testing.assert_allclose(g_default, g_ctx, atol=1e-6, rtol=1e-6)


def test_impl_bwd_rejects_unknown_mode():
    with pytest.raises(ValueError, match="impl_bwd"):
        fused.resolve_impl_bwd("jnp")
    with pytest.raises(ValueError, match="impl_bwd"):
        with fused.use_impl_bwd("dense"):
            pass


# ---------------------------------------------------------------------------
# peak-memory regression: the fused attention backward's temp footprint must
# not scale with S*T (the recompute oracle's does — dense score autodiff)


def _attn_grad_fn(S, mode, table):
    def loss(q, k, v):
        out = fused.fused_flash_attention(
            q, k, v, table=table, causal=True,
            block_q=64, block_kv=128, impl_bwd=mode,
        )
        return jnp.sum(out)

    return jax.grad(loss, argnums=(0, 1, 2))


def _attn_args(S):
    shape_q = (1, S, 2, 64)
    return (jnp.ones(shape_q), jnp.ones(shape_q), jnp.ones(shape_q))


def test_attention_backward_temp_memory_subquadratic():
    table = _table("exp")
    sizes = (256, 512)
    fused_bytes = [
        mem_utils.temp_bytes(_attn_grad_fn(S, "fused", table), *_attn_args(S))
        for S in sizes
    ]
    if any(b is None for b in fused_bytes):
        pytest.skip("backend does not implement compiled memory analysis")
    # doubling S must not ~quadruple temp memory: the blocked backward keeps
    # only O(S) stats + block scratch live (measured: exactly 2.0x per
    # doubling on the CPU backend).  2.5x + slack leaves padding headroom
    # while still failing hard if a dense (S, T) tensor sneaks back in.
    assert fused_bytes[1] <= 2.5 * fused_bytes[0] + (1 << 20), fused_bytes
    # the recompute oracle IS quadratic (dense-score autodiff) — pinning
    # its ~4x ratio proves the instrument can see the difference
    rec_bytes = [
        mem_utils.temp_bytes(_attn_grad_fn(S, "recompute", table), *_attn_args(S))
        for S in sizes
    ]
    if all(b is not None for b in rec_bytes):
        dense_score_bytes = 2 * sizes[1] * sizes[1] * 4  # B*H * S*T * f32
        assert rec_bytes[1] >= dense_score_bytes, (rec_bytes, dense_score_bytes)
        assert rec_bytes[1] >= 3.5 * rec_bytes[0], rec_bytes


# ---------------------------------------------------------------------------
# property-based sweep (hypothesis optional, mirroring test_pwl_core.py)


if HAVE_HYPOTHESIS:

    @settings(max_examples=8, deadline=None)
    @given(
        seed=st.integers(0, 2**31 - 1),
        m=st.integers(3, 40),
        k=st.integers(3, 40),
        n=st.integers(3, 40),
        bias=st.booleans(),
        table_dtype=st.sampled_from(TABLE_DTYPES),
        n_bp=st.sampled_from(SEGMENTS),
    )
    def test_linear_grad_parity_property(seed, m, k, n, bias, table_dtype, n_bp):
        table = _table("gelu", n_bp, table_dtype)
        x = _igrid(seed, (m, k))
        w = _igrid(seed + 1, (k, n), span=4)
        args = (x, w) + ((_igrid(seed + 2, (n,), span=4),) if bias else ())
        _parity(
            lambda *a, **kw: fused.fused_linear(*a, table=table, block=BLK, **kw),
            *args, rel=1e-6,
        )

    @settings(max_examples=6, deadline=None)
    @given(
        seed=st.integers(0, 2**31 - 1),
        m=st.integers(3, 33),
        k=st.integers(3, 33),
        n=st.integers(3, 33),
    )
    def test_glu_grad_parity_property(seed, m, k, n):
        table = _table("silu")
        x = _igrid(seed, (m, k))
        wg = _igrid(seed + 1, (k, n), span=4)
        wu = _igrid(seed + 2, (k, n), span=4)
        _parity(
            lambda x, wg, wu, **kw: fused.fused_glu(
                x, wg, wu, table=table, block=BLK, **kw
            ),
            x, wg, wu, rel=1e-6,
        )

else:

    @pytest.mark.skip(reason="hypothesis not installed (pip install hypothesis)")
    def test_linear_grad_parity_property():
        pass

    @pytest.mark.skip(reason="hypothesis not installed (pip install hypothesis)")
    def test_glu_grad_parity_property():
        pass
