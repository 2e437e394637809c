"""Causal self-attention backward, whichever kernels implement it.

Work the algorithm needs from q, k, v, the output, its gradient and the
softmax statistics: dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q, four
matmuls over the causal pairs (S(S+1)/2 per head), 2 operations per
multiply-add.  Forming P again (or reading it) is not counted: either is a
choice of the implementation.  Bytes: q, k, v, o, dO read and dq, dk, dv
written at the compute dtype, the f32 row statistics read.
"""
from __future__ import annotations

from .common import BYTES


def count(batch: int, seq: int, *, heads: int, kv_heads: int, head_dim: int,
          dtype: str = "bfloat16") -> tuple[float, float]:
    b = BYTES[dtype]
    pairs = seq * (seq + 1) / 2.0
    flops = 4 * 2.0 * head_dim * pairs * heads * batch
    q_like = batch * seq * heads * head_dim          # q, o, dO, dq
    kv_like = batch * seq * kv_heads * head_dim      # k, v, dk, dv
    nbytes = (4 * q_like + 4 * kv_like) * b + batch * heads * seq * 4
    return flops, nbytes
