"""Model FLOPs of the train steps completed in the window (6 per weight and
token, plus three times the forward's causal attention; recomputation not
counted) over the bf16 peak times the window."""
from roofline import model_flops


def read(r):
    n = r.counts.get("steps")
    if not n:
        return None
    flops = n * model_flops.train(r.config, r.counts["batch"], r.counts["seq"])
    secs = r.window[1] - r.window[0]
    return 100.0 * flops / (r.chips * r.peaks["bf16_flops_per_s"] * secs)
