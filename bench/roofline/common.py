"""What every roofline shares: the dtype sizes and the least time."""
from __future__ import annotations

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the operations at
    peak bf16 rate and the bytes at peak HBM bandwidth."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])

