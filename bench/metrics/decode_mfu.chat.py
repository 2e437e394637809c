"""Model FLOPs of the active slots' tokens in the window's decode steps
over the bf16 peak times the device time under the decode spans."""
from roofline import model_flops


def read(r):
    steps = r.counts.get("decode")
    dev = r.trace.span_busy_s("decode")
    if not steps or dev <= 0:
        return None
    flops = sum(model_flops.decode(r.config, d) for d in steps)
    return 100.0 * flops / (r.peaks["bf16_flops_per_s"] * dev)
