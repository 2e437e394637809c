"""The window's decode steps against their roofline: the least time for
each step's operations, weights and live K/V at the configuration's dtype
(``roofline/decode_step.py``), summed, over the device time under the
decode spans."""
from roofline import decode_step
from roofline.common import least_seconds


def read(r):
    steps = r.counts.get("decode")
    dev = r.trace.span_busy_s("decode")
    if not steps or dev <= 0:
        return None
    least = sum(least_seconds(*decode_step.count(r.config, d), r.peaks)
                for d in steps)
    return 100.0 * least / dev
