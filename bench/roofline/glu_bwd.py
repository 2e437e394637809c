"""Fused GLU backward (``_glu_bwd_kernel``): from x, w_gate, w_up and the
gradient of h it returns the gradients of both pre-activations.

Work: the kernel is given x and the weights, not the pre-activations, so
forming them is its work: two matmuls, 4 operations per row, input and
hidden width.  Bytes: x, both weights and dh read, dz_gate and dz_up
written, at the compute dtype (bfloat16).  The weight and input gradients
are XLA's matmuls, outside the kernel, and not counted here.
"""
from __future__ import annotations

from .common import BYTES


def count(rows: int, *, d_model: int, d_ff: int,
          dtype: str = "bfloat16") -> tuple[float, float]:
    b = BYTES[dtype]
    flops = 4.0 * rows * d_model * d_ff
    nbytes = (rows * d_model + 2.0 * d_model * d_ff + 3.0 * rows * d_ff) * b
    return flops, nbytes
