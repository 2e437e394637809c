"""Split-KV paged decode (``_decode_kernel``, wrapped as ``_paged_decode``)
inside the decode spans: the least time for its operations and live K/V
bytes at the pool's dtype, summed over every layer's call, over its device
time."""
from harness.metrics import kernel_roofline
from roofline import paged_decode


def read(r):
    c = r.config
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    calls = [paged_decode.count(d, heads=h, kv_heads=hkv,
                                head_dim=c["hidden_size"] // h,
                                dtype=c["dtype"])
             for d in r.counts.get("decode", [])]
    return kernel_roofline(r, "_paged_decode", "decode", calls,
                           c["num_hidden_layers"])
