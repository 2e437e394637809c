"""Pallas kernel vs pure-jnp oracle: shape/dtype sweeps + property tests.

All kernels run in interpret mode on CPU (the kernel body is executed in
Python), validating the exact code that compiles via Mosaic on TPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # optional dep: only the property-based tests need it
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

import repro  # noqa: F401
from repro import sfu
from repro.core import functions as F, pwl
from repro.kernels import ops, ref

TABLE = sfu.get_store().get(fn="gelu", n_breakpoints=32)
TABLE16 = sfu.get_store().get(fn="silu", n_breakpoints=16)


SHAPES = [
    (16,),
    (128,),
    (1000,),           # non-aligned
    (8, 128),
    (3, 257),          # ragged 2-D
    (4, 4, 96),
    (2, 5, 7, 33),     # ragged 4-D
    (1, 131072),       # large, multi-tile
]


@pytest.mark.parametrize("shape", SHAPES)
def test_nonuniform_kernel_matches_ref_shapes(shape):
    x = jax.random.normal(jax.random.PRNGKey(42), shape) * 5.0
    y_k = ops.pwl_activation(x, TABLE)
    y_r = ref.pwl_activation_ref(x, TABLE)
    np.testing.assert_allclose(y_k, y_r, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
def test_nonuniform_kernel_dtypes(dtype):
    x = (jax.random.normal(jax.random.PRNGKey(0), (64, 256)) * 5.0).astype(dtype)
    y_k = ops.pwl_activation(x, TABLE)
    y_r = ref.pwl_activation_ref(x, TABLE)
    assert y_k.dtype == dtype
    np.testing.assert_allclose(
        y_k.astype(jnp.float32), y_r.astype(jnp.float32), rtol=1e-2, atol=1e-2
    )


@pytest.mark.parametrize("n_bp", [4, 8, 16, 32, 64])
def test_nonuniform_kernel_breakpoint_counts(n_bp):
    """Sweep LTC depths (paper Table I: 4..64 segments)."""
    table = pwl.make_uniform_table(F.get("tanh"), n_bp)
    x = jnp.linspace(-10, 10, 2048).reshape(8, 256)
    np.testing.assert_allclose(
        ops.pwl_activation(x, table),
        ref.pwl_activation_ref(x, table),
        rtol=1e-5,
        atol=1e-6,
    )


def test_uniform_kernel_matches_ref():
    spec = F.get("sigmoid")
    table = pwl.make_uniform_table(spec, 32)
    lo, hi = spec.default_range
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 384)) * 6
    y_k = ops.pwl_activation_uniform(x, table.m, table.q, lo, hi)
    y_r = ref.pwl_activation_uniform_ref(x, lo, hi, table.m, table.q)
    np.testing.assert_allclose(y_k, y_r, rtol=1e-5, atol=1e-6)


def test_kernel_approximates_exact_gelu():
    """End goal: kernel output ~= exact GELU within the table's MAE."""
    x = jnp.linspace(-8, 8, 8192)
    y_k = ops.pwl_activation(x, TABLE)
    err = float(jnp.max(jnp.abs(y_k - F.get("gelu").fn(x))))
    assert err < 5e-3, err


if HAVE_HYPOTHESIS:

    @given(
        st.integers(1, 4),
        st.integers(1, 300),
        st.sampled_from([jnp.float32, jnp.bfloat16]),
        st.floats(0.1, 20.0),
    )
    @settings(max_examples=20, deadline=None)
    def test_kernel_property_random_shapes(ndim_tail, last, dtype, scale):
        """Property: kernel == oracle for arbitrary shapes/scales/dtypes."""
        shape = (2,) * (ndim_tail - 1) + (last,)
        x = (jax.random.normal(jax.random.PRNGKey(7), shape) * scale).astype(dtype)
        y_k = ops.pwl_activation(x, TABLE16)
        y_r = ref.pwl_activation_ref(x, TABLE16)
        np.testing.assert_allclose(
            y_k.astype(jnp.float32), y_r.astype(jnp.float32), rtol=2e-2, atol=2e-2
        )

else:

    @pytest.mark.skip(reason="hypothesis not installed (pip install hypothesis)")
    def test_kernel_property_random_shapes():
        pass


def test_pwl_softmax_ref_close_to_exact():
    table = sfu.get_store().get(fn="exp", n_breakpoints=32)
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 128)) * 3
    n = x.shape[-1]
    approx = ref.pwl_softmax_ref(x, table)
    exact = jax.nn.softmax(x, axis=-1)
    # delta: the table's own max |PWL(exp) - exp| over the shifted inputs'
    # domain [min(x - max), 0], on a dense grid
    grid = jnp.linspace(float(jnp.min(x - jnp.max(x, -1, keepdims=True))),
                        0.0, 100_001)
    e_grid = jnp.maximum(ref.pwl_activation_ref(grid, table), 0.0)
    delta = float(jnp.max(jnp.abs(e_grid - jnp.exp(grid))))
    assert delta < 1e-3, delta
    # With e'_j = e_j + d_j, |d_j| <= delta and the row max giving
    # sum(e') >= 1 - delta: |p'_i - p_i| <= (|d_i| + p_i |sum d|) / sum(e')
    #                                     <= delta (1 + n p_i) / (1 - delta)
    bound = delta * (1.0 + n * exact) / (1.0 - delta)
    assert bool(jnp.all(jnp.abs(approx - exact) <= bound)), float(
        jnp.max(jnp.abs(approx - exact) - bound))
    np.testing.assert_allclose(jnp.sum(approx, -1), 1.0, rtol=1e-5)


def test_kernel_under_jit_and_grad_composition():
    """Kernel output feeding a jitted loss must not break tracing."""

    @jax.jit
    def loss(x):
        return jnp.sum(ops.pwl_activation(x, TABLE) ** 2)

    x = jax.random.normal(jax.random.PRNGKey(5), (8, 128))
    assert jnp.isfinite(loss(x))
