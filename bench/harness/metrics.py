"""Helpers the per-layer readers share."""
from __future__ import annotations

from roofline.common import least_seconds


def idle_share(r):
    """Share of the traced window, in %, in which no op ran on the chip
    (averaged over the chips the cell uses)."""
    if r.trace.window_s <= 0 or r.trace.busy_s <= 0:
        return None
    return 100.0 * (1.0 - r.trace.busy_s / r.trace.window_s)


def kernel_roofline(r, op: str, span: str, calls, per_call: int = 1,
                    chip: int = 0):
    """Roofline share, in %, of the kernel ``op`` inside host spans
    ``span`` on ``chip``: the least time of ``calls`` (operation and byte
    counts, each made ``per_call`` times, e.g. once per layer) over the
    kernel's device time.  None when the kernel did not run there."""
    secs = r.trace.op_seconds(op, span, chip)
    if not calls or secs <= 0:
        return None
    least = per_call * sum(least_seconds(f, b, r.peaks) for f, b in calls)
    return 100.0 * least / secs
