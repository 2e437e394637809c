"""Generic decoder LM over a periodic layer pattern.

One implementation covers dense (qwen/stablelm/olmo/gemma/internvl2 backbone),
MoE (olmoe/phi3.5), SSM (mamba2), and hybrid (jamba): the config's
``layer_kinds`` gives each layer a (mixer, ffn) kind; layers are scanned in
*periods* (the smallest repeating kind pattern) so heterogeneous interleaves
(jamba's 1-attn:7-mamba, gemma3's 5-local:1-global) still compile as one
compact scanned HLO with stacked weights.

The compiled activation plan (``sfu.plan_for(cfg)``, one per trace) is
threaded through every block: sites planned ``impl="fused"`` run their PWL
tables as Pallas producer-kernel epilogues — dense MLPs (``layers.mlp``),
MoE expert FFNs (``moe.moe_layer``), and the attention softmax
(``layers._attn_softmax_dispatch`` / ``decode_attention``, paper Sec. V-B)
— with warn-once unfused fallbacks where fused execution is impossible.

API (all pure functions over a params pytree):
  model_defs(cfg)                          -> ParamDef tree
  forward(cfg, params, tokens, ...)        -> logits           (teacher forcing)
  loss_fn(cfg, params, batch)              -> scalar
  make_cache(cfg, batch, max_len)          -> cache pytree
  prefill(cfg, params, tokens, cache)      -> (logits_last, cache)
  decode_step(cfg, params, tokens, cache, pos) -> (logits, cache)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro import sfu
from repro.distributed.sharding import constrain

from . import layers as L
from . import moe as MOE
from . import ssm as SSM
from .common import ModelConfig, ParamDef

# ---------------------------------------------------------------------------
# parameter definitions


def norm_defs(cfg: ModelConfig, d=None):
    d = d or cfg.d_model
    if cfg.norm_type == "rmsnorm":
        return {"scale": ParamDef((d,), (None,), init="zeros", read_f32=True)}
    if cfg.norm_type == "layernorm":
        return {
            "scale": ParamDef((d,), (None,), init="ones", read_f32=True),
            "bias": ParamDef((d,), (None,), init="zeros", read_f32=True),
        }
    return {}  # nonparam_ln


def attn_defs(cfg: ModelConfig):
    D, H, Hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    defs = {
        "wq": ParamDef((D, H, dh), ("embed", "heads", None), fan_in=D),
        "wk": ParamDef((D, Hkv, dh), ("embed", "kv", None), fan_in=D),
        "wv": ParamDef((D, Hkv, dh), ("embed", "kv", None), fan_in=D),
        "wo": ParamDef((H, dh, D), ("heads", None, "embed"), fan_in=H * dh),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef((H, dh), ("heads", None), init="zeros")
        defs["bk"] = ParamDef((Hkv, dh), ("kv", None), init="zeros")
        defs["bv"] = ParamDef((Hkv, dh), ("kv", None), init="zeros")
    if cfg.qk_norm:
        defs["q_norm"] = {"scale": ParamDef((H * dh,), (None,), init="zeros",
                                            read_f32=True)}
        defs["k_norm"] = {"scale": ParamDef((Hkv * dh,), (None,), init="zeros",
                                            read_f32=True)}
    return defs


def mlp_defs(cfg: ModelConfig):
    D, F = cfg.d_model, cfg.d_ff
    if cfg.mlp_type in ("swiglu", "geglu"):
        return {
            "w_gate": ParamDef((D, F), ("embed", "mlp")),
            "w_up": ParamDef((D, F), ("embed", "mlp")),
            "w_down": ParamDef((F, D), ("mlp", "embed")),
        }
    return {
        "w_in": ParamDef((D, F), ("embed", "mlp")),
        "b_in": ParamDef((F,), ("mlp",), init="zeros"),
        "w_down": ParamDef((F, D), ("mlp", "embed")),
        "b_down": ParamDef((D,), (None,), init="zeros"),
    }


def moe_defs(cfg: ModelConfig):
    D, E, Fe = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    return {
        "router": ParamDef((D, E), ("embed", None), init="small_normal",
                           read_f32=True),
        "w_gate": ParamDef((E, D, Fe), ("experts", "embed", "mlp")),
        "w_up": ParamDef((E, D, Fe), ("experts", "embed", "mlp")),
        "w_down": ParamDef((E, Fe, D), ("experts", "mlp", "embed")),
    }


def ssm_defs(cfg: ModelConfig):
    D = cfg.d_model
    d_inner, n_heads, d_state, conv_ch, d_in_proj = SSM.ssm_dims(cfg)
    # in_proj split into z/x/BC/dt sub-projections: the packed (D, d_in_proj)
    # matrix has a TP-hostile output dim (2*d_inner + 2*N + H is rarely
    # divisible); split, each sub-output shards (or replicates) cleanly.
    return {
        "in_z": ParamDef((D, d_inner), ("embed", "ssm_inner")),
        "in_x": ParamDef((D, d_inner), ("embed", "ssm_inner")),
        "in_bc": ParamDef((D, 2 * d_state), ("embed", None)),
        "in_dt": ParamDef((D, n_heads), ("embed", "ssm_heads")),
        "conv_w": ParamDef((cfg.ssm_conv_dim, conv_ch), (None, None), init="small_normal"),
        "conv_b": ParamDef((conv_ch,), (None,), init="zeros"),
        "A_log": ParamDef((n_heads,), ("ssm_heads",), init="zeros", read_f32=True),
        "D": ParamDef((n_heads,), ("ssm_heads",), init="ones", read_f32=True),
        "dt_bias": ParamDef((n_heads,), ("ssm_heads",), init="zeros", read_f32=True),
        "norm_scale": ParamDef((d_inner,), ("ssm_inner",), init="zeros",
                               read_f32=True),
        "out_proj": ParamDef((d_inner, D), ("ssm_inner", "embed")),
    }


def block_defs(cfg: ModelConfig, mixer: str, ffn: str):
    d = {"ln1": norm_defs(cfg), "ln2": norm_defs(cfg)}
    d["mixer"] = ssm_defs(cfg) if mixer == "ssm" else attn_defs(cfg)
    d["ffn"] = moe_defs(cfg) if ffn == "moe" else mlp_defs(cfg)
    return d


def _stack_defs(defs, n: int):
    """Prepend a (n,) scan axis ("layers") to every leaf ParamDef."""
    return jax.tree_util.tree_map(
        lambda p: dataclasses.replace(p, shape=(n,) + p.shape,
                                      logical_axes=("layers",) + p.logical_axes),
        defs,
        is_leaf=lambda x: isinstance(x, ParamDef),
    )


def model_defs(cfg: ModelConfig):
    kinds = cfg.layer_kinds
    period = cfg.period
    n_periods = cfg.n_layers // period
    layer_stacks = [
        _stack_defs(block_defs(cfg, *kinds[j]), n_periods) for j in range(period)
    ]
    defs = {
        "embed": ParamDef((cfg.padded_vocab, cfg.d_model), ("vocab", "embed"), init="small_normal"),
        "final_norm": norm_defs(cfg),
        "layers": layer_stacks,
    }
    if not cfg.tie_embeddings:
        defs["unembed"] = ParamDef((cfg.d_model, cfg.padded_vocab), ("embed", "vocab"))
    return defs


# ---------------------------------------------------------------------------
# blocks


def block_apply(cfg: ModelConfig, p, h, mixer: str, ffn: str, cache=None,
                pos=None, plan=None, paged=None):
    """Pre-norm residual block.  Returns (h, new_cache, aux_loss,
    expert_rows): the rows each expert computed, (ranks, E / ranks) int32
    as ``moe_layer`` gives them, for an MoE block, None for a dense one.

    ``plan`` is the compiled activation plan threaded down from the forward
    entry points (one ``sfu.plan_for`` per trace, not per layer);
    ``paged`` is the serving path's shared {page_table, kv_len} plus this
    layer's index into the stacked page pools, which ride in ``cache``."""
    plan = plan if plan is not None else sfu.plan_for(cfg)
    hn = L.apply_norm(cfg, p["ln1"], h)
    if mixer == "ssm":
        y, new_cache = SSM.mamba2_layer(cfg, p["mixer"], hn, cache, plan=plan)
    else:
        y, new_cache = L.attention_layer(
            cfg, p["mixer"], hn, kind=mixer, cache=cache, cache_pos=pos,
            plan=plan, paged=paged,
        )
    h = h + y
    hn2 = L.apply_norm(cfg, p["ln2"], h)
    if ffn == "moe":
        y2, aux, rows = MOE.moe_layer(cfg, p["ffn"], hn2, plan=plan)
    else:
        y2, aux, rows = L.mlp(cfg, p["ffn"], hn2, plan=plan), 0.0, None
    return h + y2, new_cache, aux, rows


# ---------------------------------------------------------------------------
# forward / loss (training)


def embed_tokens(cfg: ModelConfig, params, tokens, vision_embeds=None):
    if tokens.shape[-1] <= 16:
        # decode path: one-hot CONTRACTION over the (vocab-sharded) table —
        # a gather here makes GSPMD all-gather the whole embedding table
        # per step ("involuntary full rematerialization", ~1.5 GB/step on
        # qwen2.5-32b).  The one-hot matmul reduces over the sharded vocab
        # dim instead (one tiny psum).  See EXPERIMENTS.md Sec. Perf.
        oh = jax.nn.one_hot(tokens, cfg.padded_vocab, dtype=cfg.dtype)
        h = oh @ params["embed"].astype(cfg.dtype)
    else:
        h = params["embed"].astype(cfg.dtype)[tokens]  # (B, S, D) gather
    if vision_embeds is not None:
        h = jnp.concatenate([vision_embeds.astype(cfg.dtype), h], axis=1)
    return constrain(h, "batch", "act_seq", "act_embed")


def unembed(cfg: ModelConfig, params, h):
    if cfg.tie_embeddings:
        w = params["embed"].astype(cfg.dtype)
        logits = jnp.einsum("bsd,vd->bsv", h, w)
    else:
        logits = h @ params["unembed"].astype(cfg.dtype)
    logits = logits.astype(jnp.float32)
    if cfg.padded_vocab != cfg.vocab_size:  # mask pad ids out of the softmax
        pad_mask = (jnp.arange(cfg.padded_vocab) >= cfg.vocab_size) * jnp.float32(1e9)
        logits = logits - pad_mask
    return constrain(logits, "batch", "act_seq", "vocab")


def forward(cfg: ModelConfig, params, tokens, vision_embeds=None):
    """Teacher-forcing forward -> (logits, aux_loss)."""
    kinds = cfg.layer_kinds
    period = cfg.period
    plan = sfu.plan_for(cfg)
    h = embed_tokens(cfg, params, tokens, vision_embeds)

    def period_fn(carry, stacked):
        h, aux = carry
        for j in range(period):
            h, _, a, _ = block_apply(cfg, stacked[j], h, *kinds[j], plan=plan)
            aux = aux + a
        return (h, aux), None

    fn = period_fn
    if cfg.remat:
        fn = jax.checkpoint(
            period_fn, policy=jax.checkpoint_policies.nothing_saveable
        )
    if cfg.scan_layers:
        (h, aux), _ = jax.lax.scan(fn, (h, jnp.float32(0.0)), params["layers"])
    else:  # unrolled: exact per-layer cost visible to cost_analysis (dry-run probes)
        carry = (h, jnp.float32(0.0))
        n_periods = cfg.n_layers // period
        for i in range(n_periods):
            sub = jax.tree_util.tree_map(lambda x: x[i], params["layers"])
            carry, _ = fn(carry, sub)
        h, aux = carry
    h = L.apply_norm(cfg, params["final_norm"], h)
    return unembed(cfg, params, h), aux


def sharded_cross_entropy(logits, targets, mask=None):
    """Cross entropy that keeps the vocab dim sharded end-to-end: logsumexp
    and the target-logit pick are both *reductions* over vocab (psum-able),
    never a gather (which would all-gather (B,S,V) logits over the TP axis)."""
    lf = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(lf, axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(lf - m), axis=-1)) + m[..., 0]
    vocab_iota = jnp.arange(lf.shape[-1], dtype=targets.dtype)
    tgt = jnp.sum(
        jnp.where(vocab_iota == targets[..., None], lf, 0.0), axis=-1
    )
    ll = tgt - lse
    if mask is None:
        mask = jnp.ones_like(ll)
    return -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1)


def loss_fn(cfg: ModelConfig, params, batch):
    """Next-token cross entropy (+ MoE aux).  batch: tokens, targets, [mask]."""
    logits, aux = forward(
        cfg, params, batch["tokens"], batch.get("vision_embeds")
    )
    if "vision_embeds" in batch and batch["vision_embeds"] is not None:
        nv = batch["vision_embeds"].shape[1]
        logits = logits[:, nv:]
    nll = sharded_cross_entropy(logits, batch["targets"], batch.get("mask"))
    return nll + 0.01 * aux, {"nll": nll, "aux": aux}


# ---------------------------------------------------------------------------
# caches / prefill / decode


def _block_cache_spec(cfg: ModelConfig, mixer: str, batch: int, max_len: int):
    Hkv, dh = cfg.n_kv_heads, cfg.resolved_head_dim
    if mixer == "ssm":
        d_inner, n_heads, d_state, conv_ch, _ = SSM.ssm_dims(cfg)
        return {
            "conv": ((batch, cfg.ssm_conv_dim - 1, conv_ch), ("batch", None, None)),
            "ssm": ((batch, n_heads, cfg.ssm_head_dim, d_state), ("batch", "ssm_heads", None, None)),
        }
    T = max_len
    if mixer == "attn_local" and cfg.sliding_window:
        T = min(cfg.sliding_window, max_len)
    return {
        "k": ((batch, T, Hkv, dh), ("batch", "cache_seq", "cache_kv", None)),
        "v": ((batch, T, Hkv, dh), ("batch", "cache_seq", "cache_kv", None)),
    }


def cache_defs(cfg: ModelConfig, batch: int, max_len: int):
    """ParamDef-style tree for the KV/SSM cache (zeros init, bf16)."""
    kinds = cfg.layer_kinds
    period = cfg.period
    n_periods = cfg.n_layers // period
    out = []
    for j in range(period):
        spec = _block_cache_spec(cfg, kinds[j][0], batch, max_len)
        out.append(
            {
                k: ParamDef((n_periods,) + shape, ("layers",) + axes, init="zeros", dtype=cfg.dtype)
                for k, (shape, axes) in spec.items()
            }
        )
    return out


def make_cache(cfg: ModelConfig, batch: int, max_len: int):
    from .common import init_params

    return init_params(cache_defs(cfg, batch, max_len), jax.random.PRNGKey(0))


def _scan_with_cache(cfg: ModelConfig, params, h, cache, pos):
    kinds = cfg.layer_kinds
    period = cfg.period
    plan = sfu.plan_for(cfg)

    # sfu.guard counters emitted inside the scan body would leak inner-trace
    # tracers into the engine's collector, so the body reroutes them through
    # guard.capture() and threads them out as scan ys; guard.emit sums the
    # stacked (n_periods, 2) leaves back into the ambient collector.
    def period_fn(h, xs):
        stacked, cache_p = xs
        new_caches = []
        with sfu.guard.capture() as cap:
            for j in range(period):
                h, nc, _, _ = block_apply(
                    cfg, stacked[j], h, *kinds[j], cache=cache_p[j], pos=pos,
                    plan=plan,
                )
                new_caches.append(nc)
        return h, (new_caches, cap.result())

    if cfg.scan_layers:
        h, (new_cache, gcounts) = jax.lax.scan(
            period_fn, h, (params["layers"], cache)
        )
        sfu.guard.emit(gcounts)
        return h, new_cache
    n_periods = cfg.n_layers // period
    outs = []
    for i in range(n_periods):
        xs = jax.tree_util.tree_map(lambda x: x[i], (params["layers"], cache))
        h, (nc, gcounts) = period_fn(h, xs)
        sfu.guard.emit(gcounts)
        outs.append(nc)
    new_cache = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *outs)
    return h, new_cache


def _scan_with_pools(cfg: ModelConfig, params, h, pools, pos, paged):
    """The layer scan over the paged cache: the stacked pools ride the carry
    and each layer writes and reads its own slot of them in place, through
    the period index.  A dense per-layer cache is small and rewritten, so it
    rides xs/ys (:func:`_scan_with_cache`); a shared pool is large and gains
    one token a step, and slicing it out and stacking it back would copy
    every layer's pool twice per step.  Returns (h, pools, expert_rows), the
    last as :func:`_layer_rows` gives it."""
    kinds = cfg.layer_kinds
    period = cfg.period
    plan = sfu.plan_for(cfg)

    # `paged` (page_table + kv_len) is shared by every layer, so it enters
    # the scan body as a closure constant; guard counters leave as ys, as in
    # _scan_with_cache, and so do the MoE blocks' rows per expert.
    def period_fn(carry, stacked, i):
        h, pools = carry
        pools = list(pools)
        rows = []
        with sfu.guard.capture() as cap:
            for j in range(period):
                h, pools[j], _, r = block_apply(
                    cfg, stacked[j], h, *kinds[j], cache=pools[j], pos=pos,
                    plan=plan, paged={**paged, "layer": i},
                )
                rows.append(r)
        return (h, pools), (cap.result(), rows)

    n_periods = cfg.n_layers // period
    if cfg.scan_layers:
        (h, pools), (gcounts, rows) = jax.lax.scan(
            lambda carry, xs: period_fn(carry, *xs), (h, pools),
            (params["layers"], jnp.arange(n_periods, dtype=jnp.int32)),
        )
        sfu.guard.emit(gcounts)
    else:
        steps = []
        for i in range(n_periods):
            stacked = jax.tree_util.tree_map(lambda x: x[i], params["layers"])
            (h, pools), (gcounts, r) = period_fn((h, pools), stacked, i)
            sfu.guard.emit(gcounts)
            steps.append(r)
        rows = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *steps)
    return h, pools, _layer_rows(rows)


def _layer_rows(rows):
    """The MoE blocks' rows per expert, (n_moe_layers, ranks, E / ranks) in
    layer order, from the scan's per-period-slot (n_periods, ranks,
    E / ranks) stacks (None for a dense slot); None when no block is MoE."""
    if all(r is None for r in rows):
        return None
    # (n_periods, period, ranks, E_loc) -> layers in order, dense slots
    # left out
    stacked = jnp.stack([r for r in rows if r is not None], axis=1)
    return stacked.reshape(-1, *stacked.shape[2:])


def prefill(cfg: ModelConfig, params, tokens, cache, vision_embeds=None):
    """Run the prompt through the model, filling `cache`.  Returns
    (last-position logits, filled cache)."""
    h = embed_tokens(cfg, params, tokens, vision_embeds)
    h, new_cache = _scan_with_cache(cfg, params, h, cache, pos=0)
    h = L.apply_norm(cfg, params["final_norm"], h[:, -1:])
    return unembed(cfg, params, h), new_cache


def decode_step(cfg: ModelConfig, params, tokens, cache, pos):
    """One-token decode.  tokens: (B, 1); pos: scalar absolute position."""
    h = embed_tokens(cfg, params, tokens)
    h, new_cache = _scan_with_cache(cfg, params, h, cache, pos=pos)
    h = L.apply_norm(cfg, params["final_norm"], h)
    return unembed(cfg, params, h), new_cache


# ---------------------------------------------------------------------------
# paged serving entry points (repro.serving)


def paged_cache_defs(cfg: ModelConfig, num_pages: int, page_size: int):
    """ParamDef-style tree for the per-layer paged KV pools (serving; zeros
    init).  Same pytree structure the scan
    expects — one {k_pages, v_pages} dict per period slot, each leaf stacked
    (n_periods, Hkv, num_pages, page_size, dh) and sharded over KV heads
    ("cache_kv") — but the pools are SHARED
    across requests through a page table rather than sliced per batch row.
    Paged serving covers global-attention stacks only (ring-buffer local
    layers and SSM states have no paged layout); mixed stacks raise the
    typed :class:`~repro.serving.resilience.UnsupportedCacheError` (a
    ``ValueError`` subclass) so front-ends can fall back to the dense cache
    path per-config instead of dying.
    """
    from repro.serving.resilience import UnsupportedCacheError

    for mixer, _ in cfg.layer_kinds:
        if mixer != "attn":
            raise UnsupportedCacheError(
                f"paged serving supports global-attention mixers only, got "
                f"{mixer!r} in layer_kinds"
            )
    if num_pages < 2:
        raise ValueError("num_pages must be >= 2 (page 0 is the sentinel)")
    n_periods = cfg.n_layers // cfg.period
    Hkv, dh = cfg.n_kv_heads, cfg.resolved_head_dim
    pool = ParamDef((n_periods, Hkv, num_pages, page_size, dh),
                    ("layers", "cache_kv", None, None, None), init="zeros",
                    dtype=cfg.dtype)
    return [{"k_pages": pool, "v_pages": pool} for _ in range(cfg.period)]


def prefill_paged(cfg: ModelConfig, params, tokens, cache, page_table,
                  lengths):
    """Prompt prefill into a paged cache.  tokens: (B, S) with S a multiple
    of the page size (engine-bucketed; rows padded past ``lengths`` are
    causal-masked by position).  Returns (logits at position lengths-1,
    cache, expert_rows) — the logits of each request's true last prompt
    token, and the rows each expert computed in each MoE layer,
    (n_moe_layers, ranks, E / ranks) int32 (None for a dense model).
    """
    h = embed_tokens(cfg, params, tokens)
    h, new_cache, rows = _scan_with_pools(
        cfg, params, h, cache, pos=0, paged={"page_table": page_table}
    )
    h = L.apply_norm(cfg, params["final_norm"], h)
    logits = unembed(cfg, params, h)  # (B, S, V)
    idx = jnp.clip(lengths - 1, 0, logits.shape[1] - 1)[:, None, None]
    last = jnp.take_along_axis(
        logits, jnp.broadcast_to(idx, (logits.shape[0], 1, logits.shape[2])),
        axis=1,
    )
    return last, new_cache, rows


def decode_step_paged(cfg: ModelConfig, params, tokens, cache, page_table,
                      kv_len):
    """One-token decode over the paged cache.  tokens: (B, 1);
    kv_len: (B,) per-request depths (the new token's position — continuous
    batching runs every slot at its own depth).  Appends in place, attends
    through the page table.  Returns (logits, cache, expert_rows), the last
    as :func:`prefill_paged` gives it."""
    h = embed_tokens(cfg, params, tokens)
    h, new_cache, rows = _scan_with_pools(
        cfg, params, h, cache, pos=kv_len,
        paged={"page_table": page_table, "kv_len": kv_len},
    )
    h = L.apply_norm(cfg, params["final_norm"], h)
    return unembed(cfg, params, h), new_cache, rows
