"""Causal attention backward in the window's train steps, over the device
time of the kernels that implement it (the fused flash backward,
``_flash_bwd_4d``, which the step compiles at this batch and sequence)."""
from harness.metrics import kernel_roofline
from roofline import attn_bwd


def read(r):
    c = r.config
    n = r.counts.get("steps", 0)
    h = c["num_attention_heads"]
    calls = [attn_bwd.count(r.counts.get("batch", 0), r.counts.get("seq", 0),
                            heads=h, kv_heads=c["num_key_value_heads"],
                            head_dim=c["hidden_size"] // h,
                            dtype=c["dtype"])] * n
    return kernel_roofline(r, "_flash_bwd_4d", "train_step", calls,
                           c["num_hidden_layers"])
