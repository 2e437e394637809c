"""Model FLOPs of an OLMo-family decoder, from the configuration's sizes:
2 operations per multiply-add of every weight a token meets, plus attention's QK^T and PV over the positions it attends.
Recomputation is never counted.
"""
from __future__ import annotations


def _sizes(cfg: dict):
    d = cfg["hidden_size"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // h
    attn = d * (h + 2 * hkv) * dh + h * dh * d
    return d, h, dh, attn + 3 * d * cfg["intermediate_size"]


def body_params(cfg: dict) -> float:
    """Weights a token meets in the layers."""
    return float(cfg["num_hidden_layers"] * _sizes(cfg)[3])


def head_params(cfg: dict) -> float:
    return float(cfg["hidden_size"] * cfg["vocab_size"])


def attention_flops(cfg: dict, positions: float) -> float:
    """QK^T and PV of one token that attends ``positions`` positions, over
    all layers."""
    d, h, dh, _ = _sizes(cfg)
    return 4.0 * h * dh * positions * cfg["num_hidden_layers"]


def prefill(cfg: dict, n: int) -> float:
    """A prompt of ``n`` tokens: every token through the layers, causal
    attention, the head at the last position only (the one whose logits
    are used)."""
    return (2.0 * body_params(cfg) * n + 2.0 * head_params(cfg)
            + attention_flops(cfg, n * (n + 1) / 2.0))


def decode(cfg: dict, kv_lens) -> float:
    """One decode step of requests that attend ``kv_lens`` positions."""
    per_tok = 2.0 * (body_params(cfg) + head_params(cfg))
    return per_tok * len(kv_lens) + attention_flops(cfg, float(sum(kv_lens)))


def train(cfg: dict, batch: int, seq: int) -> float:
    """Forward and backward of ``batch`` sequences of ``seq`` tokens, with
    the head at every position: 6 operations per weight and token, and
    three times the forward's causal attention."""
    tokens = batch * seq
    return (6.0 * (body_params(cfg) + head_params(cfg)) * tokens
            + 3.0 * batch * attention_flops(cfg, seq * (seq + 1) / 2.0))
