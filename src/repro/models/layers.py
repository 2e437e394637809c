"""Shared model layers: norms, RoPE, flash-style attention, GLU MLPs.

Every nonlinearity resolves through a compiled ``repro.sfu.ActivationPlan``
(threaded in by the model composition; ``sfu.plan_for(cfg)`` when absent) so
one plan swaps exact <-> PWL (Flex-SFU) implementations, table depth, and
table dtype across the whole zoo.

Attention is a pure-JAX flash formulation (two-level lax.scan with online
softmax in f32): peak memory is O(q_chunk * kv_chunk) per head instead of
O(S^2), which is what makes the 32k-prefill and 500k-decode dry-run cells fit.
Sliding-window layers dynamic-slice the KV to [q_start-window, q_end), making
local attention O(S * window) compute instead of O(S^2).

When the plan compiles ``attn.softmax:exp`` with ``impl="fused"`` (paper
Sec. V-B), attention executes fused for EVERY shape: small problems take
the dense PWL-exp softmax kernel (``kernels/fused/softmax.py``, gated by
``DENSE_FUSED_SOFTMAX_MAX_SCORES`` / ``_MAX_WIDTH`` / the window-coverage
crossover as a fast path), and everything past those thresholds —
long-context prefill/train, narrow sliding windows, wide decode caches —
runs the fused flash-attention kernel with the PWL-exp online softmax
(``kernels/fused/attention.py``).  Under a multi-device mesh the same
executors run **per shard** inside ``shard_map`` (GSPMD cannot partition a
``pallas_call``): heads shard over the rules' model axis, batch over the
data axes, PWL tables replicate as closed-over constants, and the executor
choice is made on per-shard shapes (see ``repro.distributed.shard_fused``
and docs/distributed.md).  The one genuinely unsupported layout — a decode
KV cache sharded over the *sequence* axis (``cache_seq``, the
seq-parallel-attention rules) — falls back to the unfused path, whose
psum-partitioned contraction actually honors that sharding, and says so
once via ``sfu.warn_fused_fallback``.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro import sfu
from repro.distributed import shard_fused as shf
from repro.distributed.sharding import active_mesh_rules, constrain, logical_extent

from .common import ModelConfig

# ---------------------------------------------------------------------------
# norms


def rms_norm(x, scale, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layer_norm(x, scale, bias, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def nonparam_ln(x, eps=1e-5):
    """OLMo's non-parametric LayerNorm (no scale/bias)."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype)


def apply_norm(cfg: ModelConfig, params, x):
    if cfg.norm_type == "rmsnorm":
        return rms_norm(x, params["scale"])
    if cfg.norm_type == "layernorm":
        return layer_norm(x, params["scale"], params["bias"])
    if cfg.norm_type == "nonparam_ln":
        return nonparam_ln(x)
    raise ValueError(cfg.norm_type)


# ---------------------------------------------------------------------------
# rotary embeddings


def rope(x, positions, theta: float):
    """x: (..., S, H, dh), positions: broadcastable to (..., S)."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = jnp.exp(
        -math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half
    )
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    sin = jnp.sin(angles)[..., None, :]  # (..., S, 1, half)
    cos = jnp.cos(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


def sinusoidal_positions(seq_len: int, d_model: int):
    pos = jnp.arange(seq_len, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d_model, 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, dim / d_model)
    pe = jnp.zeros((seq_len, d_model))
    pe = pe.at[:, 0::2].set(jnp.sin(angle)).at[:, 1::2].set(jnp.cos(angle))
    return pe


# ---------------------------------------------------------------------------
# softmax exp resolution (paper Sec. V-B: PWL exp for softmax)


def _softmax_safe_exp(raw: Callable) -> Callable:
    """Wrap an elementwise exp approximation with the two clamps that keep
    it softmax-safe: the output clamp keeps it non-negative so the
    normalizer stays positive, and the input clamp (exp's fit range is
    [-10, 0.1]; exp(-30) is already ~1e-13) keeps the -1e30 mask fills of
    the attention paths from overflowing the table's linear left tail —
    narrow-dtype (f16) tables evaluate in f16, where -1e30 becomes -inf
    and a flushed-to-zero slope turns it into NaN."""
    def pwl_exp(x):
        return jnp.maximum(raw(jnp.maximum(x, -30.0)), 0.0)

    return pwl_exp


def pwl_exp_fn(table) -> Callable:
    """Softmax-safe elementwise PWL exp over a fitted table — the exact
    closure :func:`resolve_exp` builds for non-exact planned specs.  Public
    so benchmarks/tests exercise the real flash-path exp, not a copy that
    can drift from the clamps above."""
    from repro.core import pwl

    return _softmax_safe_exp(lambda x: pwl.eval_coeff(x, table))


def resolve_exp(cfg: ModelConfig, plan=None) -> Callable:
    plan = plan if plan is not None else sfu.plan_for(cfg)
    spec = plan.get(sfu.site_key(sfu.SITE_SOFTMAX, "exp"))
    if spec is not None and not spec.is_exact:
        # resolve_spec honors the spec's impl (jnp / kernel / fused-fallback)
        return _softmax_safe_exp(sfu.resolve_spec(spec))
    return jnp.exp


# dense-vs-flash crossover for the fused softmax path.  These are NOT
# fallback gates anymore — past them the fused FLASH-attention kernel
# (kernels/fused/attention.py) runs instead of the dense kernel, still
# fused.  MAX_SCORES bounds the TOTAL score-tensor elements (B*H*S*T) the
# dense path materializes in f32 (~0.5 GiB at the default); the flash
# kernel never allocates that tensor.  MAX_WIDTH bounds the dense kernel's
# softmax reduction axis: it keeps the whole (128-padded) row in VMEM and
# its row block bottoms out at 8 sublanes, where the 8 MiB budget admits
# ~52k masked / ~64k maskless columns — the 32k cap leaves margin; wider
# rows (e.g. 500k-token decode caches) cannot lower on TPU and take the
# flash kernel's blocked KV loop instead.
DENSE_FUSED_SOFTMAX_MAX_SCORES = 1 << 27
DENSE_FUSED_SOFTMAX_MAX_WIDTH = 32768


def _softmax_fused_table(plan):
    """Table for the fused PWL-exp softmax kernels (dense or flash), or None
    when attention must use the pure-JAX flash/online path (site absent or
    not planned fused).  The single fused-softmax decision point, mirroring
    ``plan.fused_table`` for producer epilogues; which fused kernel runs —
    and, under a mesh, which per-shard specs it runs with — is a shape
    question decided by the caller (``_attn_softmax_dispatch`` /
    ``decode_attention`` / ``paged_decode_attention``)."""
    if plan is None:
        return None
    key = sfu.site_key(sfu.SITE_SOFTMAX, "exp")
    spec = plan.get(key)
    if spec is None or spec.impl != "fused":
        return None
    return plan.fused_table(key)


def dense_pwl_attention(q, k, v, *, table, causal=True, window=None):
    """Dense attention with the fused PWL-exp softmax kernel (Sec. V-B).

    q: (B, S, H, dh);  k/v: (B, T, Hkv, dh).  The softmax — row-max
    subtract, non-uniform PWL exp, clamp, renormalize — runs as ONE Pallas
    kernel over the score rows (``kernels/fused/softmax.py``) instead of
    three elementwise passes.  Causal/window masking goes in through the
    kernel's mask operand, exactly matching the unfused formulation
    (masked scores filled with -1e30 pre-max, probabilities zeroed).
    """
    from repro.kernels import fused

    B, S, H, dh = q.shape
    T = k.shape[1]
    Hkv = k.shape[2]
    G = H // Hkv
    scale = 1.0 / math.sqrt(dh)
    # (B, G, Hkv, S, dh) — same (Hkv major, G minor) head split as flash
    qf = q.astype(jnp.float32).reshape(B, S, Hkv, G, dh).transpose(0, 3, 2, 1, 4)
    kf = k.astype(jnp.float32).transpose(0, 2, 1, 3)  # (B, Hkv, T, dh)
    vf = v.astype(jnp.float32).transpose(0, 2, 1, 3)
    s = jnp.einsum("bghqd,bhkd->bghqk", qf, kf,
                   preferred_element_type=jnp.float32) * scale
    # causal/window structure is position-static: the kernel synthesizes it
    # from iotas in-register, so no score-sized mask array is materialized
    p = fused.fused_pwl_softmax(s, table=table, causal=causal, window=window)
    out = jnp.einsum("bghqk,bhkd->bghqd", p, vf,
                     preferred_element_type=jnp.float32)
    return out.transpose(0, 3, 2, 1, 4).reshape(B, S, H, dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# flash attention (pure JAX, chunked, online softmax)


def _chunk_attn_block(q, k, v, mask, exp_fn, m_prev, l_prev, acc_prev, scale):
    """One (q_chunk x kv_chunk) online-softmax update. All f32.

    q: (B, G, Hkv, Sq, dh)   k/v: (B, Hkv, Skv, dh)   mask: (B, 1, 1, Sq, Skv)
    """
    s = jnp.einsum("bghqd,bhkd->bghqk", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    s = jnp.where(mask, s, -1e30)
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = exp_fn(s - m_new[..., None])
    p = jnp.where(mask, p, 0.0)
    corr = exp_fn(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1)
    acc_new = acc_prev * corr[..., None] + jnp.einsum(
        "bghqk,bhkd->bghqd", p, v.astype(jnp.float32), preferred_element_type=jnp.float32
    )
    return m_new, l_new, acc_new


def flash_attention(
    q,  # (B, S, H, dh)
    k,  # (B, T, Hkv, dh)
    v,  # (B, T, Hkv, dh)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    exp_fn: Callable = jnp.exp,
    q_chunk: int = 256,
    kv_chunk: int = 2048,
    kv_valid_len=None,  # None or (B,) — for ragged caches
    unroll: bool = False,  # python-loop instead of lax.scan: exact FLOP
    #                        accounting for the dry-run probes (cost_analysis
    #                        counts scan bodies once) — see dryrun.probe_metrics
    allow_causal_unroll: bool = True,  # Perf H2 kill-switch (baseline runs)
):
    """Chunked online-softmax attention.  Returns (B, S, H, dh).

    window: sliding-window size; for windowed layers KV is dynamic-sliced to
    the reachable band per q-chunk (O(S*window) instead of O(S^2)).
    """
    B, S, H, dh = q.shape
    T = k.shape[1]
    Hkv = k.shape[2]
    G = H // Hkv
    scale = 1.0 / math.sqrt(dh)
    static_zero_off = (
        allow_causal_unroll and isinstance(q_offset, int) and q_offset == 0
    )
    if causal and static_zero_off and S == T and kv_valid_len is None:
        # size q chunks so the causal static unroll below stays <= 16 blocks
        q_chunk = max(q_chunk, -(-S // 16))
    q_chunk = min(q_chunk, S)
    kv_chunk = min(kv_chunk, T)
    n_q = -(-S // q_chunk)
    pad_q = n_q * q_chunk - S
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    qf = q.astype(jnp.float32).reshape(B, n_q, q_chunk, Hkv, G, dh)
    qf = qf.transpose(1, 0, 4, 3, 2, 5)  # (n_q, B, G, Hkv, q_chunk, dh)
    kf = k.astype(jnp.float32).transpose(0, 2, 1, 3)  # (B, Hkv, T, dh)
    vf = v.astype(jnp.float32).transpose(0, 2, 1, 3)

    if window is not None and window < T:
        # windowed: slice the reachable KV band per q chunk (static size)
        band = window + q_chunk
        band = min(band, T)

        def q_step(_, qc_i):
            qc, i = qc_i
            q_start = i * q_chunk + q_offset
            band_start = jnp.clip(q_start - window + 1, 0, T - band)
            kb = jax.lax.dynamic_slice_in_dim(kf, band_start, band, axis=2)
            vb = jax.lax.dynamic_slice_in_dim(vf, band_start, band, axis=2)
            qpos = q_start + jnp.arange(q_chunk)
            kpos = band_start + jnp.arange(band)
            mask = kpos[None, :] <= qpos[:, None] if causal else jnp.ones(
                (q_chunk, band), bool
            )
            mask &= (qpos[:, None] - kpos[None, :]) < window
            if kv_valid_len is not None:
                mask = mask[None] & (kpos[None, None, :] < kv_valid_len[:, None, None])
                mask = mask[:, None, None]
            else:
                mask = mask[None, None, None]
            m0 = jnp.full((B, G, Hkv, q_chunk), -1e30)
            l0 = jnp.zeros((B, G, Hkv, q_chunk))
            a0 = jnp.zeros((B, G, Hkv, q_chunk, dh))
            m, l, acc = _chunk_attn_block(qc, kb, vb, mask, exp_fn, m0, l0, a0, scale)
            return None, acc / jnp.maximum(l[..., None], 1e-30)

        if unroll:
            out = jnp.stack([q_step(None, (qf[i], i))[1] for i in range(n_q)])
        else:
            _, out = jax.lax.scan(q_step, None, (qf, jnp.arange(n_q)))
    elif (
        causal
        and static_zero_off
        and S == T
        and kv_valid_len is None
        and n_q <= 16
        and S % q_chunk == 0
    ):
        # -- causal static unroll (Perf-H2, EXPERIMENTS.md Sec. Perf) --------
        # the scan formulation computes scores for every (q, kv) block pair,
        # including fully-masked future blocks: ~2x wasted attention FLOPs.
        # Unrolling q chunks with a *static* kv prefix slice [0 : (i+1)*qc]
        # halves the compute; the diagonal block keeps its triangular mask.
        outs = []
        for i in range(n_q):
            qc = qf[i]  # (B, G, Hkv, q_chunk, dh)
            L_i = (i + 1) * q_chunk
            kb = kf[:, :, :L_i]
            vb = vf[:, :, :L_i]
            qpos = i * q_chunk + jnp.arange(q_chunk)
            kpos = jnp.arange(L_i)
            mask = (kpos[None, :] <= qpos[:, None])[None, None, None]
            m0 = jnp.full((B, G, Hkv, q_chunk), -1e30)
            l0 = jnp.zeros((B, G, Hkv, q_chunk))
            a0 = jnp.zeros((B, G, Hkv, q_chunk, dh))
            m, l, acc = _chunk_attn_block(qc, kb, vb, mask, exp_fn, m0, l0, a0, scale)
            outs.append(acc / jnp.maximum(l[..., None], 1e-30))
        out = jnp.stack(outs)
    else:
        n_kv = -(-T // kv_chunk)
        pad_kv = n_kv * kv_chunk - T
        if pad_kv:
            kf = jnp.pad(kf, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
            vf = jnp.pad(vf, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
        kf = kf.reshape(B, Hkv, n_kv, kv_chunk, dh).transpose(2, 0, 1, 3, 4)
        vf = vf.reshape(B, Hkv, n_kv, kv_chunk, dh).transpose(2, 0, 1, 3, 4)

        def q_step(_, qc_i):
            qc, i = qc_i
            q_start = i * q_chunk + q_offset
            qpos = q_start + jnp.arange(q_chunk)

            def kv_step(carry, kc_j):
                kb, vb, j = kc_j
                m_p, l_p, a_p = carry
                kpos = j * kv_chunk + jnp.arange(kv_chunk)
                mask = (
                    kpos[None, :] <= qpos[:, None]
                    if causal
                    else jnp.ones((q_chunk, kv_chunk), bool)
                )
                mask &= (kpos < T)[None, :]
                if kv_valid_len is not None:
                    mask = mask[None] & (
                        kpos[None, None, :] < kv_valid_len[:, None, None]
                    )
                    mask = mask[:, None, None]
                else:
                    mask = mask[None, None, None]
                m, l, acc = _chunk_attn_block(
                    qc, kb, vb, mask, exp_fn, m_p, l_p, a_p, scale
                )
                return (m, l, acc), None

            m0 = jnp.full((B, G, Hkv, q_chunk), -1e30)
            l0 = jnp.zeros((B, G, Hkv, q_chunk))
            a0 = jnp.zeros((B, G, Hkv, q_chunk, dh))
            if unroll:
                carry = (m0, l0, a0)
                for j in range(n_kv):
                    carry, _ = kv_step(carry, (kf[j], vf[j], j))
                m, l, acc = carry
            else:
                (m, l, acc), _ = jax.lax.scan(
                    kv_step, (m0, l0, a0), (kf, vf, jnp.arange(n_kv))
                )
            return None, acc / jnp.maximum(l[..., None], 1e-30)

        if unroll:
            out = jnp.stack([q_step(None, (qf[i], i))[1] for i in range(n_q)])
        else:
            _, out = jax.lax.scan(q_step, None, (qf, jnp.arange(n_q)))

    # out: (n_q, B, G, Hkv, q_chunk, dh) -> (B, S, H, dh)
    out = out.transpose(1, 0, 4, 3, 2, 5).reshape(B, n_q * q_chunk, H, dh)
    return out[:, :S].astype(q.dtype)


def _decode_attention_fused(q, k_cache, v_cache, valid, table):
    """Fused decode executor over one (local) cache block: the dense PWL-exp
    softmax kernel while a cache row fits its VMEM-resident width, the fused
    flash-attention kernel (blocked KV loop, ragged ``kv_valid_len``
    masking) for wider caches — e.g. 500k-token decode.  Shapes here are
    PER-SHARD under a mesh (called inside shard_map by
    :func:`decode_attention`)."""
    from repro.kernels import fused

    B, _, H, dh = q.shape
    T = k_cache.shape[1]
    Hkv = k_cache.shape[2]
    G = H // Hkv
    if T > DENSE_FUSED_SOFTMAX_MAX_WIDTH:
        return fused.fused_flash_attention(
            q, k_cache, v_cache, table=table, causal=False,
            kv_valid_len=jnp.sum(valid, axis=-1),
        )
    scale = 1.0 / math.sqrt(dh)
    qf = q.astype(jnp.float32).reshape(B, Hkv, G, dh)
    s = jnp.einsum(
        "bhgd,bthd->bhgt", qf, k_cache.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ) * scale
    p = fused.fused_pwl_softmax(s, table=table, mask=valid[:, None, None, :])
    out = jnp.einsum(
        "bhgt,bthd->bhgd", p, v_cache.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, 1, H, dh).astype(q.dtype)


def decode_attention(
    q,        # (B, 1, H, dh)
    k_cache,  # (B, T, Hkv, dh)
    v_cache,  # (B, T, Hkv, dh)
    valid,    # (B, T) bool
    exp_fn: Callable = jnp.exp,
    softmax_table=None,  # PWL exp table -> fused softmax kernel
):
    """Single-position attention over a cache.

    With ``softmax_table`` set (site ``attn.softmax:exp`` planned
    ``impl="fused"``), the row-max/PWL-exp/renormalize reduction runs as one
    fused Pallas kernel (:func:`_decode_attention_fused` picks dense vs
    flash by cache width).  Under a multi-device mesh the fused executor
    runs per-shard inside shard_map — heads over the model axis, batch over
    the data axes.  The one layout it cannot shard is a cache sharded over
    the SEQUENCE axis (``cache_seq``, seq-parallel-attention rules): there
    the unfused contraction below is genuinely better (GSPMD partitions it
    over the cache length with a psum, while the fused kernel would force
    full-cache replication), so it warns once and falls back.  Otherwise the
    elementwise ``exp_fn`` formulation below (identical math — see
    kernels/fused/softmax.py).

    ``valid`` must be a prefix-or-full mask per batch row, which the ring
    and linear cache layouts in :func:`attention_layer` guarantee.
    """
    B, _, H, dh = q.shape
    T = k_cache.shape[1]
    Hkv = k_cache.shape[2]
    G = H // Hkv
    if softmax_table is not None:
        rules = active_mesh_rules()
        if rules is None:
            return _decode_attention_fused(q, k_cache, v_cache, valid,
                                           softmax_table)
        if logical_extent(rules, "cache_seq") > 1:
            sfu.warn_fused_fallback(
                sfu.site_key(sfu.SITE_SOFTMAX, "exp"),
                "decode KV cache is sharded over the sequence axis "
                "(cache_seq, seq-parallel attention rules); the unfused "
                "psum-partitioned contraction honors that sharding, the "
                "per-shard fused kernel would replicate the cache",
            )
            softmax_table = None
        else:
            b = shf.batch_entry(rules, B)
            h, hk = _gqa_shard_entries(rules, "act_heads", H, "cache_kv", Hkv)
            table = softmax_table

            def body(q_l, k_l, v_l, valid_l):
                return _decode_attention_fused(q_l, k_l, v_l, valid_l, table)

            return shf.run_sharded(
                rules, body, (q, k_cache, v_cache, valid),
                (shf.P(b, None, h, None), shf.P(b, None, hk, None),
                 shf.P(b, None, hk, None), shf.P(b, None)),
                shf.P(b, None, h, None),
            )
    scale = 1.0 / math.sqrt(dh)
    qf = q.astype(jnp.float32).reshape(B, Hkv, G, dh)
    s = jnp.einsum(
        "bhgd,bthd->bhgt", qf, k_cache.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ) * scale
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = exp_fn(s - m)
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.maximum(l, 1e-30)
    out = jnp.einsum(
        "bhgt,bthd->bhgd", p, v_cache.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, 1, H, dh).astype(q.dtype)


def paged_decode_attention(
    q,           # (B, 1, H, dh)
    k_pages,     # (Hkv, P, page_size, dh)
    v_pages,     # (Hkv, P, page_size, dh)
    page_table,  # (B, n_pages) int32
    kv_len,      # (B,) int32 — tokens to attend (incl. the one just written)
    exp_fn: Callable = jnp.exp,
    softmax_table=None,
):
    """Single-position attention straight over a paged KV cache.

    With ``softmax_table`` set (site ``attn.softmax:exp`` planned
    ``impl="fused"``), the split-KV flash-decoding kernel gathers K/V
    through the page table inside the kernel — no dense cache is ever
    materialized, and work scales with the table's column count, not the
    pool capacity.  Otherwise (exact/jnp/kernel plans) the pages are
    gathered into logical order once and :func:`decode_attention` runs its
    elementwise formulation — the unfused fallback docs/distributed.md
    documents.

    Under a multi-device mesh the split-KV kernel runs per-shard: the page
    pools shard over KV heads (each rank owns whole pools for its head
    slice), q over the matching head groups, page table and lengths shard
    with the batch.  A pool sharded over ``cache_seq`` (seq-parallel rules)
    is the one unsupported layout — the gather fallback's contraction
    shards over the cache length, so it warns once and takes that path.
    """
    if softmax_table is not None:
        from repro.kernels import fused

        softmax_key = sfu.site_key(sfu.SITE_SOFTMAX, "exp")
        rules = active_mesh_rules()
        if rules is None:
            return sfu.guard.check_fused(softmax_key, fused.paged_flash_decode(
                q, k_pages, v_pages, page_table, kv_len, table=softmax_table
            ))
        if logical_extent(rules, "cache_seq") > 1:
            sfu.warn_fused_fallback(
                sfu.site_key(sfu.SITE_SOFTMAX, "exp"),
                "paged KV pool is sharded over the sequence axis (cache_seq, "
                "seq-parallel attention rules); the gather fallback's "
                "contraction honors that sharding, the per-shard split-KV "
                "kernel would replicate the pool",
            )
        else:
            B, _, H, _ = q.shape
            Hkv = k_pages.shape[0]
            b = shf.batch_entry(rules, B)
            h, hk = _gqa_shard_entries(rules, "act_heads", H, "cache_kv", Hkv)
            table = softmax_table

            def body(q_l, kp_l, vp_l, pt_l, len_l):
                return fused.paged_flash_decode(
                    q_l, kp_l, vp_l, pt_l, len_l, table=table
                )

            return sfu.guard.check_fused(softmax_key, shf.run_sharded(
                rules, body, (q, k_pages, v_pages, page_table, kv_len),
                (shf.P(b, None, h, None), shf.P(hk, None, None, None),
                 shf.P(hk, None, None, None), shf.P(b, None), shf.P(b)),
                shf.P(b, None, h, None),
            ))
    from repro.serving.kv_cache import gather_pages

    k_dense = gather_pages(k_pages, page_table)
    v_dense = gather_pages(v_pages, page_table)
    T = k_dense.shape[1]
    valid = jnp.arange(T)[None, :] < kv_len[:, None]
    return decode_attention(q, k_dense, v_dense, valid, exp_fn)


# ---------------------------------------------------------------------------
# sliced-q sharded attention (Perf H1, EXPERIMENTS.md Sec. Perf)


def _sliced_q_attention(cfg, q, k, v, *, causal, window, exp_fn, rules):
    """Shard attention COMPUTE over the model axis when head counts don't
    divide it: K/V stay replicated (they already are under our rules), each
    model rank runs flash attention for its contiguous q stripe, and one
    all-gather reassembles the sequence.  Per-rank attention FLOPs drop from
    the full S x T (GSPMD's replicated fallback) to (S/tp) x T.

    (A true ring/zigzag would also shard KV residency; at 4k-32k sequence the
    replicated-KV variant is strictly cheaper in link traffic — one output
    all-gather vs tp K/V rotations.)"""
    import functools as _ft

    from jax.sharding import PartitionSpec as P

    from repro.distributed.shard_fused import shard_map

    mesh = rules.mesh
    tp = dict(mesh.shape).get("model", 1)
    B, S, H, dh = q.shape
    S_loc = S // tp
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp = 1
    for a in batch_axes:
        dp *= mesh.shape[a]
    bspec = batch_axes if (batch_axes and B % dp == 0) else None

    @_ft.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(bspec, None, None, None),) * 3,
        out_specs=P(bspec, None, None, None),
    )
    def run(q_r, k_r, v_r):
        r = jax.lax.axis_index("model")
        q_loc = jax.lax.dynamic_slice_in_dim(q_r, r * S_loc, S_loc, axis=1)
        out_loc = flash_attention(
            q_loc, k_r, v_r, causal=causal, window=window,
            q_offset=r * S_loc, exp_fn=exp_fn, unroll=cfg.unroll_scans,
        )
        return jax.lax.all_gather(out_loc, "model", axis=1, tiled=True)

    return run(q, k, v)


def _flash_or_sliced(cfg, q, k, v, *, causal, window, exp_fn):
    """Attention dispatch.  Perf iterations H1 (sliced-q shard_map) and H1c
    (attention-segment batch resharding) were both MEASURED AND REFUTED on
    qwen2.5-32b train_4k — the gradient psums / GSPMD resharding they induce
    cost more than the replicated attention compute they save (Sec. Perf).
    The shipped configuration: plain flash with the H2 causal unroll; GSPMD
    replicates attention across the model axis for non-divisible head counts.
    """
    return flash_attention(
        q, k, v, causal=causal, window=window, exp_fn=exp_fn,
        unroll=cfg.unroll_scans,
        allow_causal_unroll=cfg.causal_unroll,
    )


def _dense_softmax_preferred(n_scores: int, width: int,
                             window: Optional[int], kv_len: int) -> bool:
    """True when the dense fused-softmax kernel is the better fused executor
    for these shapes: the score tensor fits the dense cap, a row fits the
    kernel's VMEM-resident width, and any sliding window covers at least
    half the KV (narrower windows make the flash kernel's banded KV loop —
    O(S*window) scores — strictly cheaper than dense O(S*T))."""
    if window is not None and kv_len > 2 * window:
        return False
    return (n_scores <= DENSE_FUSED_SOFTMAX_MAX_SCORES
            and width <= DENSE_FUSED_SOFTMAX_MAX_WIDTH)


def _gqa_shard_entries(rules, q_axis: str, H: int, kv_axis: str, Hkv: int):
    """Spec entries for sharding (q heads, kv heads) together.

    GQA folds G query heads onto each KV head, so a head split must keep
    whole groups per shard: q and kv heads shard over the SAME mesh axes or
    not at all.  Either dim not dividing its extent (or the two logical axes
    mapping to different physical axes — custom rules) drops BOTH to
    replicated, which is exactly what ``sanitize_spec`` does to the unfused
    path's constraints for the same shapes."""
    h = shf.dim_entry(rules, q_axis, H)
    hk = shf.dim_entry(rules, kv_axis, Hkv)
    if h != hk:
        return None, None
    return h, hk


def _shard_fused_attention(cfg, q, k, v, *, causal, window, table, rules):
    """Run the fused attention executors per-shard on the rules' mesh.

    Heads shard over the model axis (whole GQA groups per rank), batch over
    the data axes, K/V stay head-sharded alongside q — attention is
    head-local so there is no psum.  The PWL table is closed over (packed
    host-side at trace time; replicated to every rank as a constant).  The
    dense-vs-flash executor choice is made on PER-SHARD shapes: what a rank
    actually materializes is what the dense cap must bound."""
    from repro.kernels import fused

    B, _, H, _ = q.shape
    Hkv = k.shape[2]
    b = shf.batch_entry(rules, B)
    h, hk = _gqa_shard_entries(rules, "act_heads", H, "act_kv", Hkv)

    def body(q_l, k_l, v_l):
        Bl, Sl, Hl = q_l.shape[0], q_l.shape[1], q_l.shape[2]
        Tl = k_l.shape[1]
        if _dense_softmax_preferred(Bl * Hl * Sl * Tl, Tl, window, Tl):
            return dense_pwl_attention(q_l, k_l, v_l, table=table,
                                       causal=causal, window=window)
        return fused.fused_flash_attention(
            q_l, k_l, v_l, table=table, causal=causal, window=window
        )

    return shf.run_sharded(
        rules, body, (q, k, v),
        (shf.P(b, None, h, None), shf.P(b, None, hk, None),
         shf.P(b, None, hk, None)),
        shf.P(b, None, h, None),
    )


def _attn_softmax_dispatch(cfg, q, k, v, *, causal, window, exp_fn, plan):
    """Attention entry for train/prefill/cross.  When the plan compiles the
    ``attn.softmax:exp`` site ``impl="fused"``, attention ALWAYS executes
    fused: the dense PWL-exp softmax kernel for small problems, the fused
    flash-attention kernel (PWL-exp online softmax) for everything else —
    long-context prefill, narrow sliding windows, cross attention.  Under a
    multi-device mesh the same executors run per-shard inside shard_map
    (:func:`_shard_fused_attention`).  Otherwise the pure-JAX flash path
    with the (possibly PWL) elementwise ``exp_fn``."""
    B, S, H = q.shape[0], q.shape[1], q.shape[2]
    T = k.shape[1]
    table = _softmax_fused_table(plan)
    if table is not None:
        # sfu.guard checkpoint sits on the full (unsharded) output — inside
        # a shard_map body the collector would capture per-shard tracers
        softmax_key = sfu.site_key(sfu.SITE_SOFTMAX, "exp")
        rules = active_mesh_rules()
        if rules is not None:
            y = _shard_fused_attention(
                cfg, q, k, v, causal=causal, window=window, table=table,
                rules=rules,
            )
        elif _dense_softmax_preferred(B * H * S * T, T, window, T):
            y = dense_pwl_attention(q, k, v, table=table, causal=causal,
                                    window=window)
        else:
            from repro.kernels import fused

            y = fused.fused_flash_attention(
                q, k, v, table=table, causal=causal, window=window
            )
        return sfu.guard.check_fused(softmax_key, y)
    if not causal and window is None:  # cross-attention (encdec)
        return flash_attention(q, k, v, causal=False, exp_fn=exp_fn,
                               unroll=cfg.unroll_scans)
    return _flash_or_sliced(cfg, q, k, v, causal=causal, window=window,
                            exp_fn=exp_fn)


# ---------------------------------------------------------------------------
# MLPs


def _fused_mlp_hidden(cfg: ModelConfig, params, x, plan):
    """Fused-kernel hidden state for plan sites with ``impl="fused"``: the
    PWL activation runs as an epilogue inside the gemm that produced it
    (kernels/fused/), so the (tokens, d_ff) pre-activation never round-trips
    HBM.  Returns None when this site is not planned fused (exempt / other
    impl).

    Under a multi-device mesh the kernel runs per-shard inside shard_map:
    d_ff columns shard over the rules' "mlp" axis (matching the unfused
    path's ``constrain(h, "batch", None, "mlp")``), batch over the data
    axes, and the weights' d_model rows replicate on entry — the same
    per-use all-gather GSPMD performs for the FSDP-sharded unfused gemms.
    The hidden is d_ff-local, so there is no psum.  A d_ff that doesn't
    divide the mlp extent replicates the column dim instead (exactly what
    ``sanitize_spec`` does to the unfused constraint for the same shape).

    Differentiable: the fused ops carry custom VJPs whose default backward
    is a fused Pallas kernel decoding the per-segment PWL slope (the exact
    local derivative) on the rematerialized accumulator tile — including
    per-shard inside the shard_map bodies below.  ``cfg.act_impl_bwd`` /
    ``fused.use_impl_bwd`` select the jnp recompute oracle instead."""
    key = sfu.site_key(sfu.SITE_MLP, cfg.activation)
    spec = plan.get(key)
    if spec is None or spec.impl != "fused":
        return None
    from repro.kernels import fused

    table = plan.fused_table(key)
    if table is None:
        return None
    dtype = x.dtype
    rules = active_mesh_rules()
    if cfg.mlp_type in ("swiglu", "geglu"):
        wg = params["w_gate"].astype(dtype)
        wu = params["w_up"].astype(dtype)
        if rules is None:
            return fused.fused_glu(x, wg, wu, table=table)
        b = shf.batch_entry(rules, x.shape[0])
        f = shf.dim_entry(rules, "mlp", wg.shape[-1])

        def glu_body(x_l, wg_l, wu_l):
            return fused.fused_glu(x_l, wg_l, wu_l, table=table)

        return shf.run_sharded(
            rules, glu_body, (x, wg, wu),
            (shf.P(b, None, None), shf.P(None, f), shf.P(None, f)),
            shf.P(b, None, f),
        )
    w_in = params["w_in"].astype(dtype)
    b_in = params["b_in"].astype(dtype) if "b_in" in params else None
    if rules is None:
        return fused.fused_linear(x, w_in, b_in, table=table)
    b = shf.batch_entry(rules, x.shape[0])
    f = shf.dim_entry(rules, "mlp", w_in.shape[-1])
    if b_in is None:
        def lin_body(x_l, w_l):
            return fused.fused_linear(x_l, w_l, None, table=table)

        return shf.run_sharded(
            rules, lin_body, (x, w_in),
            (shf.P(b, None, None), shf.P(None, f)),
            shf.P(b, None, f),
        )

    def lin_bias_body(x_l, w_l, b_l):
        return fused.fused_linear(x_l, w_l, b_l, table=table)

    return shf.run_sharded(
        rules, lin_bias_body, (x, w_in, b_in),
        (shf.P(b, None, None), shf.P(None, f), shf.P(f)),
        shf.P(b, None, f),
    )


def _guard_fused_mlp(cfg: ModelConfig, params, x, h, plan, key):
    """sfu.guard checkpoint on the fused-MLP hidden state.  The fused kernel
    consumes the pre-activation internally, so with an active collector the
    clamp counter recomputes it in jnp against the table's fitted range —
    a deliberate diagnostics-mode cost (documented in docs/plans.md); with
    no collector this is the bare NaN-injection hook (a no-op unless armed).
    Runs on the full (unsharded) hidden, outside any shard_map body."""
    clamped = None
    if sfu.guard.active():
        table = plan.fused_table(key)
        lo, hi = float(table.bp[0]), float(table.bp[-1])
        if cfg.mlp_type in ("swiglu", "geglu"):
            z = x @ params["w_gate"].astype(x.dtype)
        else:
            z = x @ params["w_in"].astype(x.dtype)
            if "b_in" in params:
                z = z + params["b_in"].astype(x.dtype)
        clamped = jnp.sum((z < lo) | (z > hi), dtype=jnp.int32)
    return sfu.guard.check_fused(key, h, clamped)


def mlp(cfg: ModelConfig, params, x, plan=None):
    """Dense FFN: swiglu / geglu / plain, activation via the activation plan
    (site ``"mlp:<activation>"``).

    For sites planned ``impl="fused"`` the hidden state comes from the fused
    Pallas kernels; the down-projection tail below is shared with the
    unfused path.
    """
    dtype = x.dtype
    plan = plan if plan is not None else sfu.plan_for(cfg)
    key = sfu.site_key(sfu.SITE_MLP, cfg.activation)
    h = _fused_mlp_hidden(cfg, params, x, plan)
    # Megatron-style sequence parallelism: inside the TP region the hidden is
    # sharded on d_ff ONLY (seq replicated) — one all-gather in, one
    # reduce-scatter out per layer.  Constraining seq@model here too would
    # force an activation all-gather per gemm (measured: 6.4 GB/layer on
    # qwen2.5-32b, see EXPERIMENTS.md Sec. Perf).
    if h is not None:
        h = _guard_fused_mlp(cfg, params, x, h, plan, key)
        h = constrain(h, "batch", None, "mlp")
    elif cfg.mlp_type in ("swiglu", "geglu"):
        act = plan.act(key)
        g = x @ params["w_gate"].astype(dtype)
        u = x @ params["w_up"].astype(dtype)
        g = constrain(g, "batch", None, "mlp")
        u = constrain(u, "batch", None, "mlp")
        h = act(g) * u
    else:
        act = plan.act(key)
        h = x @ params["w_in"].astype(dtype)
        if "b_in" in params:
            h = h + params["b_in"].astype(dtype)
        h = constrain(h, "batch", None, "mlp")
        h = act(h)
    y = h @ params["w_down"].astype(dtype)
    if "b_down" in params:
        y = y + params["b_down"].astype(dtype)
    return constrain(y, "batch", "act_seq", "act_embed")


# ---------------------------------------------------------------------------
# attention layer (projections + flash / decode)


def attention_layer(
    cfg: ModelConfig,
    params,
    x,
    *,
    kind: str = "attn",        # attn | attn_local | attn_global
    positions=None,            # (B, S) absolute positions
    cache=None,                # dict(k, v, ...) for decode, or None
    cache_pos=None,            # scalar int — or (B,) per-request positions
    #                            (continuous batching: each slot at its own
    #                            depth), write offset for decode
    cross_kv=None,             # (k, v) for cross-attention (whisper)
    use_rope: bool = True,
    plan=None,                 # repro.sfu.ActivationPlan (softmax-exp site)
    paged=None,                # dict(page_table, kv_len) — serving's paged
    #                            KV cache (cache holds k_pages/v_pages)
):
    """Returns (y, new_cache).  Train/prefill when cache is None or a fresh
    buffer being filled; decode when x has seq_len 1 and cache is given."""
    B, S, D = x.shape
    H, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    dtype = x.dtype
    plan = plan if plan is not None else sfu.plan_for(cfg)
    exp_fn = resolve_exp(cfg, plan)
    window = cfg.sliding_window if kind == "attn_local" else None

    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dtype))
    if "bq" in params:
        q = q + params["bq"].astype(dtype)
    if cross_kv is None:
        k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dtype))
        v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dtype))
        if "bk" in params:
            k = k + params["bk"].astype(dtype)
            v = v + params["bv"].astype(dtype)
    else:
        k, v = cross_kv

    if positions is None:
        off = 0 if cache_pos is None else cache_pos
        if getattr(off, "ndim", 0) == 1:  # per-request depths (serving)
            positions = off[:, None] + jnp.arange(S)[None, :]
        else:
            positions = jnp.arange(S)[None, :] + off
        positions = jnp.broadcast_to(positions, (B, S))
    theta = cfg.rope_theta
    if use_rope and cross_kv is None:
        q = rope(q, positions, theta)
        k = rope(k, positions, theta)

    q = constrain(q, "batch", "act_seq", "act_heads", None)

    if cache is not None and "k_pages" in cache:
        # paged KV cache (repro.serving): k/v live in a shared page pool,
        # the per-request page table maps logical position -> physical slot.
        from repro.serving import kv_cache as _pg

        page_table = paged["page_table"]
        if S == 1:
            # decode: in-place append at kv_len, then attend the kv_len+1
            # prefix through the page table (split-KV kernel when the
            # softmax site is planned fused, gather fallback otherwise).
            # Inactive batch slots (all-sentinel table rows, kv_len == 0)
            # append into the sentinel page and read back one garbage row —
            # finite and discarded by the scheduler.
            kv_len = paged["kv_len"]
            k_pages, v_pages = _pg.append_kv(
                cache["k_pages"], cache["v_pages"], k, v, page_table, kv_len
            )
            new_cache = {"k_pages": k_pages, "v_pages": v_pages}
            y = paged_decode_attention(
                q, k_pages, v_pages, page_table, kv_len + 1, exp_fn,
                softmax_table=_softmax_fused_table(plan),
            )
        else:
            # prefill: write the prompt's K/V into the table's pages (whole
            # pages — the engine buckets prompts to a page multiple) and
            # attend causally over the in-flight k/v, never via the pool.
            k_pages, v_pages = _pg.write_prompt_pages(
                cache["k_pages"], cache["v_pages"], k, v, page_table
            )
            new_cache = {"k_pages": k_pages, "v_pages": v_pages}
            y = _attn_softmax_dispatch(
                cfg, q, k, v, causal=True, window=window, exp_fn=exp_fn,
                plan=plan,
            )
    elif cache is not None and cross_kv is None:
        # cache layout: full-length buffer for global layers; ring buffer of
        # size `window` for local layers (slot = pos % window).
        T = cache["k"].shape[1]
        ring = window is not None and T == window
        kc, vc = k.astype(cache["k"].dtype), v.astype(cache["v"].dtype)
        pos0 = cache_pos if cache_pos is not None else 0
        if S == 1:
            slot = (pos0 % T) if ring else pos0
            k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], kc, slot, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], vc, slot, axis=1)
        elif ring and S >= T:
            # prefill overflowing a ring: keep last T tokens at their modular
            # slots (token at abs pos p lands at slot p % T  <=>  roll by S%T)
            k_cache = jnp.roll(kc[:, S - T :], S % T, axis=1)
            v_cache = jnp.roll(vc[:, S - T :], S % T, axis=1)
        else:
            k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], kc, pos0, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], vc, pos0, axis=1)
        new_cache = {"k": k_cache, "v": v_cache}
        if S == 1:
            # decode: attend over cache with validity mask
            t = jnp.arange(T)
            if ring:
                valid = (t[None, :] <= pos0) | (pos0 >= T)  # all slots once wrapped
            else:
                valid = t[None, :] <= pos0
            valid = jnp.broadcast_to(valid, (B, T))
            k_cache = constrain(k_cache, "batch", "cache_seq", "cache_kv", None)
            v_cache = constrain(v_cache, "batch", "cache_seq", "cache_kv", None)
            # fused-planned decode picks its kernel by cache width (dense
            # softmax kernel vs blocked flash) inside decode_attention
            y = decode_attention(
                q, k_cache, v_cache, valid, exp_fn,
                softmax_table=_softmax_fused_table(plan),
            )
        else:
            # prefill: full causal attention over the (fresh) prefix
            y = _attn_softmax_dispatch(
                cfg, q, k, v, causal=True, window=window, exp_fn=exp_fn,
                plan=plan,
            )
    else:
        new_cache = cache
        if cross_kv is not None:
            y = _attn_softmax_dispatch(
                cfg, q, k, v, causal=False, window=None, exp_fn=exp_fn,
                plan=plan,
            )
        else:
            y = _attn_softmax_dispatch(
                cfg, q, k, v, causal=True, window=window, exp_fn=exp_fn,
                plan=plan,
            )

    y = constrain(y, "batch", "act_seq", "act_heads", None)
    out = jnp.einsum("bshk,hkd->bsd", y, params["wo"].astype(dtype))
    return constrain(out, "batch", "act_seq", "act_embed"), new_cache
