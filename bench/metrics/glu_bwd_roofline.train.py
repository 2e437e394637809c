"""Fused GLU backward (``_glu_bwd_kernel``, wrapped as ``_glu_dz_2d``) in
the window's train steps: the least time for the work it is given (one call
per layer, rows = batch x sequence), over its device time."""
from harness.metrics import kernel_roofline
from roofline import glu_bwd


def read(r):
    c = r.config
    n = r.counts.get("steps", 0)
    rows = r.counts.get("batch", 0) * r.counts.get("seq", 0)
    calls = [glu_bwd.count(rows, d_model=c["hidden_size"],
                           d_ff=c["intermediate_size"], dtype=c["dtype"])] * n
    return kernel_roofline(r, "_glu_dz_2d", "train_step", calls,
                           c["num_hidden_layers"])
