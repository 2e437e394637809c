"""Plain reference of OLMoE (allenai/OLMoE-1B-7B-0924, arXiv:2409.02060) as
the configuration file states it: pre-norm decoder with RMSNorm, attention
whose whole q and whole k projections are RMS-normalised (learned scales,
``q_norm`` and ``k_norm``) before rotary positions, and a mixture of SwiGLU
experts in place of the MLP: a softmax router over every expert, each token
weighted by its top ``num_experts_per_tok`` probabilities as they are
(renormalised only where ``norm_topk_prob`` says so), and an untied head.

Straightforward ``jax.numpy`` in float32 with every matmul at
``Precision.HIGHEST``: no kernels, no cache, no batching of requests, no
PWL tables (SiLU and exp are exact), and no routing layout: every expert is
computed for every token and weighted by the token's gates, which are zero
outside its top k.  ``precision="fp8"`` rounds every matmul operand, the
router's too, to float8 e4m3 first: the control, the next precision below
the configuration's bfloat16.  The rotary, norm and head helpers are
``olmo_decoder.py``'s, loaded by path.

On the chip the reference runs under ``jit`` over the benchmark's f32
parameters as they are sharded for the program (experts and heads over the
model axis), so the compiler partitions it; nothing is gathered to one chip.

Departures from the published model: the weights are drawn from the seed
(``harness/weights.py``; the router by the serving driver's rule), norm
scales are stored as offsets from 1, as the program stores them, and the
vocabulary is the embedding's 50304 rows (the tokenizer's 50280 ids
padded).  The training-time load-balancing and router z-losses are not part
of a forward pass.
"""
from __future__ import annotations

import functools
import importlib.util
import math
import pathlib

import jax
import jax.numpy as jnp


def _olmo():
    path = pathlib.Path(__file__).with_name("olmo_decoder.py")
    spec = importlib.util.spec_from_file_location("bench_olmo_decoder", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_O = _olmo()
_mm, _norm, _rope, _head, _f32 = _O._mm, _O._norm, _O._rope, _O._head, _O._f32


def _qk_norm(cfg: dict, p: dict, x):
    """RMSNorm of each position's whole projection (N, H, dh)."""
    n, h, dh = x.shape
    return _norm(cfg, p, x.reshape(n, h * dh)).reshape(n, h, dh)


def _attention(cfg: dict, p: dict, x, precision: str):
    n = x.shape[0]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg["hidden_size"] // h
    q = _qk_norm(cfg, p["q_norm"], _mm("nd,dhk->nhk", x, p["wq"], precision))
    k = _qk_norm(cfg, p["k_norm"], _mm("nd,dhk->nhk", x, p["wk"], precision))
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    v = _mm("nd,dhk->nhk", x, p["wv"], precision)
    k = jnp.repeat(k, h // hkv, axis=1)
    v = jnp.repeat(v, h // hkv, axis=1)
    s = _mm("qhk,thk->hqt", q, k, precision) / math.sqrt(dh)
    causal = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = _mm("hqt,thk->qhk", w, v, precision)
    return _mm("qhk,hkd->qd", o, p["wo"], precision)


def gates(cfg: dict, router, x, precision: str = "f32"):
    """(N, E): each token's weight for each expert, zero outside its top
    ``num_experts_per_tok``."""
    probs = jax.nn.softmax(_mm("nd,de->ne", x, router, precision), axis=-1)
    top_w, top_e = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, top_e].set(top_w)


def experts(cfg: dict, f: dict, x, precision: str = "f32"):
    """The expert layer: every expert's SwiGLU for every token, summed with
    the token's gates."""
    g = _mm("nd,edf->nef", x, f["w_gate"], precision)
    u = _mm("nd,edf->nef", x, f["w_up"], precision)
    y = _mm("nef,efd->ned", jax.nn.silu(g) * u, f["w_down"], precision)
    return jnp.einsum("ne,ned->nd", gates(cfg, f["router"], x, precision), y,
                      precision=_O.HI)


def _block(cfg: dict, h, lp: dict, precision: str):
    h = h + _attention(cfg, lp["mixer"], _norm(cfg, lp["ln1"], h), precision)
    return h + experts(cfg, lp["ffn"], _norm(cfg, lp["ln2"], h), precision)


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _logits(params, tokens, cfg_items, precision):
    cfg = dict(cfg_items)
    params = _f32(params)
    h = params["embed"][tokens]

    def body(h, lp):
        return _block(cfg, h, lp, precision), None

    h, _ = jax.lax.scan(body, h, params["layers"][0])
    return _head(cfg, params, h, precision)


def _items(cfg: dict) -> tuple:
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "rope_theta", "norm", "norm_eps", "tie_word_embeddings",
            "vocab_size", "num_experts_per_tok", "norm_topk_prob")
    return tuple((k, cfg.get(k)) for k in keys)


def logits(cfg: dict, params, tokens, precision: str = "f32"):
    """(N, vocab) float32 logits at every position of one sequence."""
    return _logits(params, jnp.asarray(tokens, jnp.int32), _items(cfg),
                   precision)
