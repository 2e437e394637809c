"""Fixed-point table simulation (paper: 8/16/32-bit fixed-point support).

The hardware stores breakpoints and (m, q) coefficients in b-bit integer
memories with power-of-two scale factors and evaluates y = m·x + q in a wide
accumulator.  We simulate exactly that arithmetic so the numerical behaviour
of the fixed-point configurations is testable on CPU:

  x_q  = round(x / s_x)           (b-bit, saturating)
  bp_q = round(bp / s_x)          (compare in the *input* scale: exact decode)
  m_q  = round(m / s_m),  q_q = round(q / (s_m * s_x))
  y    = (m_q * x_q + q_q) * (s_m * s_x)

Decode compares x_q with bp_q — integer compares, same result as comparing
de-quantized values, matching the paper's SIMD integer comparator.

Accumulator width: the paper's MADD accumulates at 2b bits.  For b=8/16 the
int32 JAX path is exact; for b=32 we run the accumulation under
``jax.enable_x64`` (int64), mirroring the 64-bit accumulator.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from .pwl import PWLTable

_INT_INFO = {8: (-128, 127), 16: (-32768, 32767), 32: (-(2**31), 2**31 - 1)}


def _pow2_scale(max_abs: float, bits: int) -> float:
    """Smallest power-of-two scale s such that max_abs/s fits in `bits`."""
    _, hi = _INT_INFO[bits]
    if max_abs == 0:
        return 1.0
    return float(2.0 ** np.ceil(np.log2(max_abs / hi)))


@dataclasses.dataclass
class QuantizedPWLTable:
    """Integer PWL table: the deployable fixed-point artifact.

    Tables are host (numpy) arrays — they are tiny and the 32-bit mode needs
    int64 storage that jnp would silently downcast with x64 disabled."""

    bp_q: np.ndarray   # (n,)   int
    m_q: np.ndarray    # (n+1,) int
    q_q: np.ndarray    # (n+1,) int64 (accumulator scale)
    s_x: float
    s_m: float
    bits: int
    name: str = "?"

    def __call__(self, x):
        return eval_fixed_point(x, self)


def quantize_table(table: PWLTable, bits: int, x_range: tuple[float, float]) -> QuantizedPWLTable:
    if bits not in _INT_INFO:
        raise ValueError(f"bits must be one of {sorted(_INT_INFO)}")
    lo, hi = _INT_INFO[bits]
    bp = np.asarray(table.bp, np.float64)
    m = np.asarray(table.m, np.float64)
    q = np.asarray(table.q, np.float64)
    s_x = _pow2_scale(max(abs(x_range[0]), abs(x_range[1]), np.abs(bp).max()), bits)
    s_m = _pow2_scale(np.abs(m).max(), bits)
    bp_q = np.clip(np.round(bp / s_x), lo, hi).astype(np.int64)
    m_q = np.clip(np.round(m / s_m), lo, hi).astype(np.int64)
    # q lives at the accumulator scale s_m*s_x with 2b-bit headroom
    acc_lo, acc_hi = -(2 ** (2 * bits - 1)), 2 ** (2 * bits - 1) - 1
    q_q = np.clip(np.round(q / (s_m * s_x)), acc_lo, acc_hi).astype(np.int64)
    return QuantizedPWLTable(
        bp_q=bp_q, m_q=m_q, q_q=q_q, s_x=s_x, s_m=s_m, bits=bits, name=table.name
    )


def full_space_int8(table: PWLTable) -> PWLTable:
    """FQA-style full-space int8 quantization of a PWL table (table *storage*
    format ``"int8"``, the quantization-axis counterpart of bf16/f16).

    Each coefficient array (bp, m, q) is quantized to int8 independently with
    its own power-of-two scale spanning the array's full value range — "full
    space": the scale covers max|v| with no outlier clipping, so every
    breakpoint and coefficient lands on the int8 grid of its array.  The
    arrays are then de-quantized back to f32: ``v_q * s`` is exactly
    representable (|v_q| <= 127 needs 7 mantissa bits; a power-of-two scale
    only shifts the exponent), so the returned table carries exactly the
    int8 format error while every downstream evaluation path — jnp
    ``eval_coeff``, the standalone kernel, the fused epilogues — keeps its
    full-rate f32 decode arithmetic.  Same narrow-memories / wide-MADD
    contract as the hardware's multi-format SRAMs, applied to an 8-bit
    integer grid instead of a narrow float.

    Unlike :func:`quantize_table` (which simulates the *integer datapath*:
    quantized inputs, integer compares, 2b-bit accumulator), this is a table
    *storage* format: inputs and arithmetic stay f32.  The returned table is
    tagged ``storage="int8"`` so pack/plan layers record the format.
    """
    bits = 8
    lo, hi = _INT_INFO[bits]

    def q8(v):
        v = np.asarray(v, np.float64)
        s = _pow2_scale(float(np.abs(v).max()), bits)
        vq = np.clip(np.round(v / s), lo, hi)
        return (vq * s).astype(np.float32)

    return PWLTable(
        bp=q8(table.bp), m=q8(table.m), q=q8(table.q),
        name=table.name, storage="int8",
    )


def eval_fixed_point(x, qt: QuantizedPWLTable):
    """Simulate the integer datapath: quantize input, int compare-count decode,
    2b-bit MADD accumulate, de-quantize output."""
    lo, hi = _INT_INFO[qt.bits]
    if qt.bits == 32:
        with jax.enable_x64(True):
            xq = jnp.clip(jnp.round(jnp.asarray(np.asarray(x, np.float64)) / qt.s_x), lo, hi).astype(jnp.int64)
            idx = jnp.sum(xq[..., None] > jnp.asarray(qt.bp_q), axis=-1)
            m = jnp.take(jnp.asarray(qt.m_q), idx)
            q = jnp.take(jnp.asarray(qt.q_q), idx)
            acc = m * xq + q  # int64 accumulate
            y = np.asarray(acc, np.float64) * (qt.s_m * qt.s_x)
        return jnp.asarray(y, jnp.float32).astype(x.dtype)
    xq = jnp.clip(jnp.round(x / qt.s_x), lo, hi).astype(jnp.int32)
    idx = jnp.sum(xq[..., None] > jnp.asarray(qt.bp_q, jnp.int32), axis=-1)
    m = jnp.take(jnp.asarray(qt.m_q, jnp.int32), idx)
    q = jnp.take(jnp.asarray(qt.q_q, jnp.int32), idx)
    acc = m * xq + q  # int32 accumulate (exact for b<=16)
    return (acc.astype(jnp.float32) * (qt.s_m * qt.s_x)).astype(x.dtype)
