"""Model FLOPs of the prompts prefilled in the window (real tokens, pads
left out, the head at the last position) over the bf16 peak times the
device time under the prefill spans."""
from roofline import model_flops


def read(r):
    pre = r.counts.get("prefill")
    dev = r.trace.span_busy_s("prefill")
    if not pre or dev <= 0:
        return None
    flops = sum(model_flops.prefill(r.config, n) for n, _ in pre)
    return 100.0 * flops / (r.peaks["bf16_flops_per_s"] * dev)
