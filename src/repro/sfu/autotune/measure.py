"""Measurement primitives for the autotuner: accuracy, latency, provenance.

This module is the single source of truth for the repo's measurement
conventions — ``benchmarks/common.py`` re-exports :func:`provenance` and
:func:`time_fn` from here so the BENCH_*.json provenance block and the
autotune report can never disagree about what "latency" means.

Three layers, matching the autotuner's two-part objective:

  * **per-function accuracy** — :func:`site_mse`: MSE of the candidate's
    quantized table against the exact function over its paper interval
    (``core.functions`` ``default_range``), i.e. the quantity the paper's
    Fig. 5 / Table 2 sweep.  Deterministic, never cached.
  * **site latency** — :func:`measure_site_latency`: median wall time of a
    representative jitted workload per plan site (GLU MLP, per-expert MoE
    GLU, flash attention, elementwise SSM gate) at the target config's
    dimensions, including the fused kernels' block-shape axis.
  * **end-to-end accuracy** — :func:`e2e_logit_check`: the Table-3-style
    gate on the target config — max |logit delta|, mean KL(exact || plan)
    and greedy top-1 agreement of the candidate plan vs the all-exact
    reference on the same parameters.

Latency caveat (same as every BENCH_*.json): on a non-TPU backend the
fused kernels run in Pallas interpret mode, so the numbers are a
functional-ordering signal only; :func:`provenance` labels this and the
driver embeds it in both the cache keys and the report.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.core import functions as F
from repro.core import pwl
from repro.sfu.plan import (
    SITE_MLP,
    SITE_MOE,
    SITE_SOFTMAX,
    resolve_spec,
)
from repro.sfu.spec import ApproxSpec
from repro.sfu.store import get_store


# ---------------------------------------------------------------------------
# provenance + timing (canonical; benchmarks/common.py delegates here)


def provenance(quick: bool = False, mesh=None) -> dict:
    """The provenance block every BENCH_*.json / autotune report embeds.

    ``backend``/``interpret_mode`` are the load-bearing fields: on any
    non-TPU backend the Pallas kernels run in interpret mode, so latency
    numbers are validation-only and must never be read as TPU latencies
    (ROADMAP flags this).  ``device``/``jax_version`` pin the machine, and
    ``quick`` marks CI-smoke shapes.  ``device_count``/``mesh`` pin the
    topology: per-shard fused dispatch means a number measured on a 2x2
    mesh is not comparable to a single-device run of the same shape.
    Pass ``mesh`` explicitly, or it is read from the active sharding rules.
    """
    backend = jax.default_backend()
    if mesh is None:
        from repro.distributed.sharding import active_rules

        rules = active_rules()
        mesh = rules.mesh if rules is not None else None
    return {
        "backend": backend,
        "interpret_mode": backend != "tpu",
        "device": jax.devices()[0].device_kind,
        "device_count": jax.device_count(),
        "mesh": dict(mesh.shape) if mesh is not None else None,
        "jax_version": jax.__version__,
        "unix_time": int(time.time()),
        "quick": bool(quick),
    }


def machine_id(prov: dict) -> dict:
    """The provenance subset that keys measurements: numbers from different
    machines/topologies must never alias in the MeasurementCache."""
    return {
        "backend": prov["backend"],
        "device": prov["device"],
        "device_count": prov["device_count"],
        "mesh": prov["mesh"],
    }


def time_fn(fn, *args, warmup: int = 2, iters: int = 10) -> float:
    """Median wall-time (us) of a jitted callable."""
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


# ---------------------------------------------------------------------------
# accuracy: per-function table MSE


def site_mse(spec: ApproxSpec) -> float:
    """MSE of the candidate's (quantized) table vs the exact function over
    its paper interval.  ``exact`` is 0 by definition.  Deterministic —
    cheap enough to recompute, so never cached."""
    if spec.impl == "exact":
        return 0.0
    fspec = F.get(spec.fn)
    lo, hi = fspec.default_range
    table = get_store().get(spec)
    return float(pwl.mse(table, fspec, lo, hi))


# ---------------------------------------------------------------------------
# latency: one representative workload per plan site


@dataclasses.dataclass(frozen=True)
class SiteWorkload:
    """The dims one site's latency is measured at.  JSON-able (cache key)."""

    site: str
    tokens: int = 1024          # flattened batch*seq rows for matmul sites
    d_model: int = 768
    d_ff: int = 3072
    n_experts: int = 0          # moe.expert only
    expert_capacity: int = 0    # moe.expert only
    seq: int = 512              # attn.softmax only
    n_heads: int = 12           # attn.softmax only
    head_dim: int = 64          # attn.softmax only

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def workload_for(cfg, site: str, *, quick: bool = False) -> SiteWorkload:
    """Derive the measurement workload from a model config's dimensions."""
    scale = 4 if quick else 1
    # ssm-family configs expose an mlp: site with d_ff=0 (the gate lives in
    # the block's own projections) — measure at the conventional 4x width
    d_ff = cfg.d_ff or 4 * cfg.d_model
    if site == SITE_MOE and getattr(cfg, "moe_d_ff", 0):
        d_ff = cfg.moe_d_ff
    n_exp = max(1, getattr(cfg, "n_experts", 0)) if site == SITE_MOE else 0
    return SiteWorkload(
        site=site,
        tokens=max(128, 1024 // scale),
        d_model=cfg.d_model,
        d_ff=d_ff,
        n_experts=n_exp,
        expert_capacity=max(32, 256 // scale) if site == SITE_MOE else 0,
        seq=max(128, 512 // scale),
        n_heads=cfg.n_heads,
        head_dim=cfg.resolved_head_dim,
    )


def _latency_thunk(spec: ApproxSpec, block, wl: SiteWorkload):
    """Build (jitted_fn, args) for one measurement point.

    fused arms call the real fused kernels (with the candidate block);
    jnp/exact arms run the same math through XLA with the elementwise
    callable from :func:`repro.sfu.plan.resolve_spec` — i.e. exactly what
    the model layers dispatch for that impl.
    """
    key = jax.random.PRNGKey(0)
    site = wl.site

    if site == SITE_SOFTMAX:
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (1, wl.seq, wl.n_heads, wl.head_dim), jnp.float32)
        k = jax.random.normal(kk, (1, wl.seq, wl.n_heads, wl.head_dim), jnp.float32)
        v = jax.random.normal(kv, (1, wl.seq, wl.n_heads, wl.head_dim), jnp.float32)
        if spec.impl == "fused":
            from repro.kernels.fused import attention as A

            bq, bkv = block if block is not None else (A.DEFAULT_BLOCK_Q,
                                                       A.DEFAULT_BLOCK_KV)
            table = get_store().get(spec)

            @jax.jit
            def run_fused(q, k, v, _t=table, _bq=bq, _bkv=bkv):
                return A.fused_flash_attention(q, k, v, table=_t, causal=True,
                                               block_q=_bq, block_kv=_bkv)

            return run_fused, (q, k, v)

        act = resolve_spec(spec) if spec.impl != "exact" else None
        scale = wl.head_dim ** -0.5
        mask = jnp.tril(jnp.ones((wl.seq, wl.seq), bool))

        @jax.jit
        def run_jnp(q, k, v):
            s = jnp.einsum("bshd,bthd->bhst", q, k) * scale
            s = jnp.where(mask, s, -jnp.inf)
            if act is None:
                p = jax.nn.softmax(s, axis=-1)
            else:
                # PWL-exp softmax: shifted scores through the approx exp
                e = act(s - jnp.max(s, axis=-1, keepdims=True))
                e = jnp.where(mask, e, 0.0)
                p = e / jnp.sum(e, axis=-1, keepdims=True)
            return jnp.einsum("bhst,bthd->bshd", p, v)

        return run_jnp, (q, k, v)

    if site == SITE_MOE:
        from repro.kernels.fused import moe as M

        kx, kg, ku = jax.random.split(key, 3)
        # every expert takes `expert_capacity` rows, grouped by expert
        bm = M.row_tile(wl.expert_capacity)
        rows = -(-wl.expert_capacity // bm) * bm
        sizes = jnp.full((wl.n_experts,), rows, jnp.int32)
        x = jax.random.normal(
            kx, (wl.n_experts * rows, wl.d_model), jnp.float32)
        wg = jax.random.normal(
            kg, (wl.n_experts, wl.d_model, wl.d_ff), jnp.float32) * 0.02
        wu = jax.random.normal(
            ku, (wl.n_experts, wl.d_model, wl.d_ff), jnp.float32) * 0.02
        if spec.impl == "fused":
            table = get_store().get(spec)

            @jax.jit
            def run_fused(x, wg, wu, _t=table):
                return M.fused_moe_glu(x, wg, wu, sizes, row_tile=bm, table=_t)

            return run_fused, (x, wg, wu)

        act = resolve_spec(spec)

        @jax.jit
        def run_jnp(x, wg, wu):
            return act(jax.lax.ragged_dot(x, wg, sizes)) * \
                jax.lax.ragged_dot(x, wu, sizes)

        return run_jnp, (x, wg, wu)

    # SITE_MLP: GLU at (tokens, d_model) x (d_model, d_ff)
    if site == SITE_MLP:
        kx, kg, ku = jax.random.split(key, 3)
        x = jax.random.normal(kx, (wl.tokens, wl.d_model), jnp.float32)
        wg = jax.random.normal(kg, (wl.d_model, wl.d_ff), jnp.float32) * 0.02
        wu = jax.random.normal(ku, (wl.d_model, wl.d_ff), jnp.float32) * 0.02
        if spec.impl == "fused":
            from repro.kernels.fused import glu as G

            blk = block if block is not None else G.DEFAULT_BLOCK
            table = get_store().get(spec)

            @jax.jit
            def run_fused(x, wg, wu, _t=table, _b=tuple(blk)):
                return G.fused_glu(x, wg, wu, table=_t, block=_b)

            return run_fused, (x, wg, wu)

        act = resolve_spec(spec)

        @jax.jit
        def run_jnp(x, wg, wu):
            return act(x @ wg) * (x @ wu)

        return run_jnp, (x, wg, wu)

    # ssm (and any future unfused site): elementwise gate over (tokens, d)
    x = jax.random.normal(key, (wl.tokens, wl.d_model), jnp.float32)
    act = resolve_spec(spec)
    run = jax.jit(act)
    return run, (x,)


def measure_site_latency(
    spec: ApproxSpec,
    block,
    wl: SiteWorkload,
    *,
    warmup: int = 2,
    iters: int = 10,
) -> float:
    """Median wall-time (us) of one (spec, block) point at ``wl`` dims."""
    fn, args = _latency_thunk(spec, block, wl)
    return time_fn(fn, *args, warmup=warmup, iters=iters)


# ---------------------------------------------------------------------------
# end-to-end accuracy gate (paper Table III analogue)


def e2e_logit_check(cfg, plan, *, batch: int = 4, seq: int = 32,
                    seed: int = 0) -> dict:
    """Run the target config exact and under ``plan`` on the SAME params
    and batch; report the Table-3-style distribution deltas.

    Returns {"max_logit_delta", "mean_kl", "top1_agree"} — the driver
    gates the emitted plan on ``top1_agree`` (greedy-decode agreement, the
    closest analogue of the paper's top-1 accuracy drop).
    """
    from repro.models import Model

    cfg_exact = dataclasses.replace(cfg, act_impl="exact", act_plan=None)
    cfg_plan = dataclasses.replace(cfg, act_plan=plan)
    model_e = Model(cfg_exact)
    params = model_e.init(jax.random.PRNGKey(seed))
    batch_d = {"tokens": jax.random.randint(
        jax.random.PRNGKey(seed + 1), (batch, seq), 0, cfg.vocab_size)}
    if getattr(cfg, "is_encoder_decoder", False):
        batch_d["frames"] = jax.random.normal(
            jax.random.PRNGKey(seed + 2),
            (batch, cfg.encoder_seq, cfg.d_model), cfg.dtype)
    if getattr(cfg, "n_vision_tokens", 0):
        batch_d["vision_embeds"] = jax.random.normal(
            jax.random.PRNGKey(seed + 2),
            (batch, cfg.n_vision_tokens, cfg.d_model), cfg.dtype)
    le, _ = model_e.forward(params, batch_d)
    lp, _ = Model(cfg_plan).forward(params, batch_d)
    pe = jax.nn.softmax(le, -1)
    logp = jax.nn.log_softmax(le, -1)
    logq = jax.nn.log_softmax(lp, -1)
    return {
        "max_logit_delta": float(jnp.max(jnp.abs(le - lp))),
        "mean_kl": float(jnp.mean(jnp.sum(pe * (logp - logq), -1))),
        "top1_agree": float(jnp.mean(
            jnp.argmax(le, -1) == jnp.argmax(lp, -1))),
    }
