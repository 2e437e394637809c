"""Split-KV paged decode (``_paged_decode``) on chip 0 inside the decode
spans, against its roofline, as the chat cell reads it, for the heads one
chip holds: the KV pool and the queries shard by head over the mesh's
``model`` axis, so chip 0 computes ``heads / model`` query heads against
``kv_heads / model`` of each live position.  The window's decode steps are
the driver's ``decode`` counts (the depth of each active slot)."""
from harness.metrics import kernel_roofline
from roofline import paged_decode


def read(r):
    c = r.config
    tp = r.traffic["mesh"]["model"]
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    calls = [paged_decode.count(d, heads=h // tp, kv_heads=hkv // tp,
                                head_dim=c["hidden_size"] // h,
                                dtype=c["dtype"])
             for d in r.counts.get("decode", [])]
    return kernel_roofline(r, "_paged_decode", "decode", calls,
                           c["num_hidden_layers"])
