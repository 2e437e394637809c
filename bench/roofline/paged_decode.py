"""Split-KV paged decode attention (``_decode_kernel``): one new query token
per active request against its ``kv_len`` cached positions.

Work the algorithm needs: QK^T and PV over the live positions, 2 operations
per multiply-add, and one read of the live keys and values at the pool's
dtype.  Empty slots, whole pages past the live tokens and a wider dtype
than the pool's are not counted, so a kernel that skips them cannot read
over 100%.
"""
from __future__ import annotations

from .common import BYTES


def count(kv_lens, *, heads: int, kv_heads: int, head_dim: int,
          dtype: str = "bfloat16") -> tuple[float, float]:
    """(operations, bytes) of one call over requests with these depths."""
    live = float(sum(kv_lens))
    b = BYTES[dtype]
    flops = 4.0 * heads * head_dim * live
    kv_bytes = 2.0 * kv_heads * head_dim * live * b
    n = len(kv_lens)
    q_out_bytes = 2.0 * n * heads * head_dim * b
    return flops, kv_bytes + q_out_bytes
