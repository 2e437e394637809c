"""Plain reference of the OLMo family as the configuration files state it:
pre-norm decoder, rotary positions (halves rotated), causal softmax
attention, SwiGLU MLP, tied or separate output head.

Straightforward ``jax.numpy`` in float32 with every matmul at
``Precision.HIGHEST``: no kernels, no cache, no batching of requests, no
PWL tables (SiLU and exp are exact).  It imports nothing of the program.
``precision="fp8"`` rounds every matmul operand to float8 e4m3 first: the
control, the next precision below the configuration's bfloat16.

The parameter tree is the one the benchmark makes from the seed
(``harness/weights.py``) in the program's layout: ``embed`` (V, D),
``final_norm``, ``layers`` (a one-entry list of dicts whose leaves carry a
leading layer axis), and ``unembed`` when the head is not tied.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _q(x, precision: str):
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _mm(spec: str, a, b, precision: str):
    return jnp.einsum(spec, _q(a, precision), _q(b, precision),
                      precision=HI, preferred_element_type=jnp.float32)


def _norm(cfg: dict, p: dict, x):
    if cfg["norm"] == "nonparametric_layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + cfg["norm_eps"])
    if cfg["norm"] == "rmsnorm":
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        # the scale is stored as an offset from 1
        return x * jax.lax.rsqrt(var + cfg["norm_eps"]) * (1.0 + p["scale"])
    raise ValueError(cfg["norm"])


def _rope(x, theta: float):
    """x: (N, H, dh); rotates the two halves of each head."""
    n, _, dh = x.shape
    half = dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * freqs[None]
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(cfg: dict, p: dict, x, precision: str):
    n = x.shape[0]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg["hidden_size"] // h
    q = _rope(_mm("nd,dhk->nhk", x, p["wq"], precision), cfg["rope_theta"])
    k = _rope(_mm("nd,dhk->nhk", x, p["wk"], precision), cfg["rope_theta"])
    v = _mm("nd,dhk->nhk", x, p["wv"], precision)
    k = jnp.repeat(k, h // hkv, axis=1)
    v = jnp.repeat(v, h // hkv, axis=1)
    s = _mm("qhk,thk->hqt", q, k, precision) / math.sqrt(dh)
    causal = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = _mm("hqt,thk->qhk", w, v, precision)
    return _mm("qhk,hkd->qd", o, p["wo"], precision)


def _swiglu(x, wg, wu, wd, precision: str):
    g = _mm("nd,df->nf", x, wg, precision)
    u = _mm("nd,df->nf", x, wu, precision)
    return _mm("nf,fd->nd", jax.nn.silu(g) * u, wd, precision)


def _block(cfg: dict, h, lp: dict, precision: str):
    h = h + _attention(cfg, lp["mixer"], _norm(cfg, lp["ln1"], h), precision)
    x = _norm(cfg, lp["ln2"], h)
    f = lp["ffn"]
    return h + _swiglu(x, f["w_gate"], f["w_up"], f["w_down"], precision)


def _head(cfg: dict, params: dict, h, precision: str):
    x = _norm(cfg, params["final_norm"], h)
    if cfg["tie_word_embeddings"]:
        logits = _mm("nd,vd->nv", x, params["embed"], precision)
    else:
        logits = _mm("nd,dv->nv", x, params["unembed"], precision)
    return logits[:, : cfg["vocab_size"]]


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


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
            "vocab_size")
    return tuple((k, cfg.get(k)) for k in keys)


def logits(cfg: dict, params, tokens, precision: str = "f32"):
    """(N, vocab) float32 logits at every position of one sequence."""
    return _logits(params, jnp.asarray(tokens, jnp.int32), _items(cfg),
                   precision)


# ---------------------------------------------------------------------------
# training: the loss of a batch, its gradient, and AdamW


def _row_loss(params, tok, tgt, cfg: dict, precision: str):
    h = params["embed"][tok]

    @jax.checkpoint
    def body(h, lp):
        return _block(cfg, h, lp, precision), None

    h, _ = jax.lax.scan(body, h, params["layers"][0])
    lg = _head(cfg, params, h, precision)
    lse = jax.nn.logsumexp(lg, axis=-1)
    pick = jnp.take_along_axis(lg, tgt[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - pick)


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _batch_grad(params, tokens, targets, cfg_items, precision):
    cfg = dict(cfg_items)
    params = _f32(params)
    grad_fn = jax.value_and_grad(_row_loss)

    def row(carry, xs):
        total, acc = carry
        lv, g = grad_fn(params, xs[0], xs[1], cfg, precision)
        return (total + lv, jax.tree_util.tree_map(jnp.add, acc, g)), None

    zero = jax.tree_util.tree_map(jnp.zeros_like, params)
    (total, grad), _ = jax.lax.scan(row, (jnp.float32(0.0), zero),
                                    (tokens, targets))
    n = tokens.shape[0] * tokens.shape[1]
    return total / n, jax.tree_util.tree_map(lambda g: g / n, grad)


def loss_and_grad(cfg: dict, params, tokens, targets, precision="f32"):
    """Mean next-token cross entropy over a batch and its gradient; the
    rows go one at a time, so the activations of one row are live at
    once."""
    loss, grad = _batch_grad(params, jnp.asarray(tokens, jnp.int32),
                             jnp.asarray(targets, jnp.int32), _items(cfg),
                             precision)
    return float(loss), grad


def adamw_lr(opt: dict, step: int) -> float:
    """Linear warm-up to ``lr`` over ``warmup_steps``, then cosine decay to
    0 at ``total_steps``."""
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    t = (step - opt["warmup_steps"]) / max(
        opt["total_steps"] - opt["warmup_steps"], 1)
    t = min(max(t, 0.0), 1.0)
    return opt["lr"] * warm * 0.5 * (1 + math.cos(math.pi * t))


@functools.partial(jax.jit, static_argnames=("decay",))
def _adamw_leaf(p, g, m, n, lr, scale, bc1, bc2, b1, b2, eps, wd, decay):
    g = g * scale
    m = b1 * m + (1 - b1) * g
    n = b2 * n + (1 - b2) * jnp.square(g)
    u = (m / bc1) / (jnp.sqrt(n / bc2) + eps)
    if decay:
        u = u + wd * p
    return p - lr * u, m, n


def host_clip_scale(opt: dict, grads) -> float:
    """The factor that clips the gradient to ``grad_clip`` in global norm."""
    sq = sum(float(np.sum(np.square(g, dtype=np.float64)))
             for g in jax.tree_util.tree_leaves(grads))
    return min(1.0, opt["grad_clip"] / max(math.sqrt(sq), 1e-12))


def host_adamw(opt: dict, params, grads, mu, nu, step: int, scale: float):
    """One AdamW step (``step`` counts from 1) over host arrays, a leaf at a
    time on the device: decoupled weight decay on every leaf but the norm
    scales.  Returns (params, mu, nu) as host arrays."""
    b1, b2 = opt["b1"], opt["b2"]
    lr = adamw_lr(opt, step)
    flat, tdef = jax.tree_util.tree_flatten_with_path(params)
    gs, ms, ns = (jax.tree_util.tree_leaves(t) for t in (grads, mu, nu))
    out = []
    for (path, p), g, m, n in zip(flat, gs, ms, ns):
        r = _adamw_leaf(p, g, m, n, lr, scale, 1 - b1 ** step,
                        1 - b2 ** step, b1, b2, opt["eps"],
                        opt["weight_decay"], decay=_leaf(path) != "scale")
        out.append(tuple(np.asarray(x) for x in r))
    pick = lambda i: jax.tree_util.tree_unflatten(  # noqa: E731
        tdef, [o[i] for o in out])
    return pick(0), pick(1), pick(2)


def _leaf(path) -> str:
    last = path[-1]
    return getattr(last, "key", str(last))
