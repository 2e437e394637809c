"""One paged decode step of the whole model (``Model.decode_step_paged``):
every active request's new token through all layers and the head.

Work the algorithm needs: the model's operations (``model_flops.decode``),
one read of every weight at the configuration's dtype, and one read of the
live keys and values at that dtype.  The float32 master weights, their
cast, the pool's cast and copies, empty slots and pages past the live
tokens are not counted, so a step that drops them cannot read over 100%.
"""
from __future__ import annotations

from . import model_flops
from .common import BYTES


def count(cfg: dict, kv_lens) -> tuple[float, float]:
    """(operations, bytes) of one step of requests attending ``kv_lens``
    positions (their new token included)."""
    b = BYTES[cfg["dtype"]]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    per_position = (2.0 * cfg["num_hidden_layers"] * hkv
                    * (cfg["hidden_size"] // h) * b)
    weights = (model_flops.body_params(cfg) + model_flops.head_params(cfg)) * b
    return (model_flops.decode(cfg, kv_lens),
            weights + per_position * float(sum(kv_lens)))
