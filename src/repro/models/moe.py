"""Mixture-of-Experts FFN: token-choice top-k routing with capacity buckets.

Dispatch is the cumsum-of-one-hot scheme (no sort, no double-batched
gather): every (token, choice)
pair gets a position within its expert's capacity bucket; overflow tokens are
dropped (residual passes through).

Two execution paths:

* **shard_map** (any multi-device mesh): dispatch is LOCAL per shard.  With
  E divisible by the "model" extent, experts are parallel: tokens replicate
  over the model axis, each rank builds capacity buckets for its own expert
  slice, and one psum combines partial outputs (Perf H-MoE-2).  Otherwise
  expert weights replicate over "model" and only the batch shards.  Either
  way, fused-planned sites run the per-expert GLU Pallas kernel *inside*
  the shard_map body on local expert slices.

* **single device** (tests, no mesh): plain local dispatch.

The shard_map path exists because GSPMD's scatter partitioner cannot prove
our dispatch local: it materializes each (E, C, D) buffer with a full
all-reduce — measured 25-40 GiB/layer/device on olmoe-1b-7b train_4k, 343 s
of ICI time per step (EXPERIMENTS.md Sec. Perf, hypothesis H-MoE).

Aux loss: Switch-style load-balancing loss, returned to the train step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import sfu
from repro.distributed.shard_fused import shard_map
from repro.distributed.sharding import _ACTIVE, constrain

from .common import ModelConfig


def moe_layer(cfg: ModelConfig, params, x, plan=None):
    """x: (B, S, D) -> (y: (B, S, D), aux_loss: scalar f32).

    The expert activation resolves through the activation plan (site
    ``"moe.expert:<activation>"``).  Under an active Rules mesh the layer
    always runs inside shard_map: expert-parallel (weights sharded over the
    "model" axis, replicated-token dispatch + psum combine — Perf H-MoE-2)
    when E divides the model extent, replicated-expert otherwise (batch
    still shards over the data axes).

    Sites planned ``impl="fused"`` run the expert gate/up gemms + PWL
    activation + gating as ONE Pallas kernel (``kernels/fused/moe.py``) —
    on a single device directly, and under a mesh *inside* the shard_map
    body, on each rank's local expert slice (the PWL table is closed over
    and replicated; the psum combine is the one the unfused math already
    performs)."""
    plan = plan if plan is not None else sfu.plan_for(cfg)
    key = sfu.site_key(sfu.SITE_MOE, cfg.activation)
    spec = plan.get(key)
    planned_fused = spec is not None and spec.impl == "fused"
    fused_table = plan.fused_table(key) if planned_fused else None
    # the elementwise callable is only resolved (table fetch and all) on the
    # path that actually consumes it
    act = None if fused_table is not None else plan.act(key)
    rules = _ACTIVE.get()
    if rules is not None and rules.mesh is not None and rules.mesh.size > 1:
        y, aux = _moe_layer_shardmap(cfg, params, x, rules, act,
                                     fused_table=fused_table)
    else:
        y, aux = _moe_layer_local(cfg, params, x, act, fused_table=fused_table)
    if fused_table is not None:
        # sfu.guard checkpoint on the combined expert output — placed here
        # (outside the shard_map body) so collector emissions never capture
        # per-shard tracers; a NaN in any expert propagates through the
        # weighted combine, so finite-checking the combine covers the site
        y = sfu.guard.check_fused(key, y)
    return y, aux


def _moe_layer_shardmap(cfg: ModelConfig, params, x, rules, act,
                        fused_table=None):
    """MoE under a mesh: local dispatch per shard (Perf H-MoE).

    Expert-parallel (`ep`) when E divides the "model" extent: expert weights
    shard over "model", each rank builds capacity buckets for its own expert
    slice, one psum combines partial outputs.  Otherwise expert weights
    replicate over "model" and every model rank computes identically (the
    same replication GSPMD's sanitized constraints produce) — still inside
    shard_map so a fused-planned site keeps its Pallas kernel per shard."""
    mesh = rules.mesh
    axes = mesh.axis_names
    batch_axes = tuple(a for a in ("pod", "data") if a in axes)
    B = x.shape[0]
    dp = 1
    for a in batch_axes:
        dp *= mesh.shape[a]
    x_bspec = batch_axes if (batch_axes and B % dp == 0) else None
    tp = dict(mesh.shape).get("model", 1)
    ep = tp > 1 and cfg.n_experts % tp == 0

    espec = P("model", None, None) if ep else P(None, None, None)
    pspecs = {
        "router": P(None, None),
        "w_gate": espec,
        "w_up": espec,
        "w_down": espec,
    }

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(x_bspec, None, None), pspecs),
        out_specs=(P(x_bspec, None, None), P()),
    )
    def run(x_loc, p_loc):
        y, aux = _moe_local_dispatch(
            cfg, p_loc, x_loc, act,
            ep_axis="model" if ep else None,
            ep_size=tp if ep else 1,
            fused_table=fused_table,
        )
        for a in batch_axes:
            aux = jax.lax.pmean(aux, a)
        return y, aux

    return run(x, {k: params[k] for k in pspecs})


def _moe_layer_local(cfg: ModelConfig, params, x, act, fused_table=None):
    y, aux = _moe_local_dispatch(
        cfg, params, x, act, ep_axis=None, fused_table=fused_table
    )
    return y, aux


def _moe_local_dispatch(cfg: ModelConfig, params, x, act, ep_axis,
                        ep_size: int = 1, fused_table=None):
    """Token-choice dispatch on the LOCAL token shard.  With ep_axis set, the
    expert dim is distributed over that mesh axis via all_to_all."""
    B, S, D = x.shape
    T = B * S
    E, K = cfg.n_experts, cfg.n_active_experts
    dtype = x.dtype
    xt = x.reshape(T, D)
    # --- routing (f32 for numerics) ---
    logits = (xt.astype(jnp.float32)) @ params["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
    top_w, top_e = jax.lax.top_k(probs, K)   # (T, K)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)

    # --- capacity & positions (cumsum-of-one-hot), LOCAL to this shard ---
    capacity = max(1, int(cfg.capacity_factor * T * K / E))
    flat_e = top_e.reshape(-1)                       # (T*K,)
    oh = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # (T*K, E)
    pos_all = jnp.cumsum(oh, axis=0) - 1             # position per expert
    pos = jnp.take_along_axis(pos_all, flat_e[:, None], axis=1)[:, 0]
    keep = pos < capacity
    flat_t = jnp.repeat(jnp.arange(T), K)
    flat_w = top_w.reshape(-1) * keep.astype(jnp.float32)

    # --- dispatch: scatter tokens into LOCAL (E, C, D) expert buffers ---
    safe_pos = jnp.where(keep, pos, capacity - 1)
    # Perf H-MoE-2 (EXPERIMENTS.md): tokens are REPLICATED over the model
    # (EP) axis inside shard_map, so no token movement is needed at all —
    # each rank builds capacity buckets only for ITS OWN expert slice and a
    # single psum of the (T, D) partial outputs combines across ranks.
    # Link traffic ~2 x T x D bytes/layer vs K x cf x T x D for the bucket
    # all-to-all of H-MoE-1 (measured ladder in Sec. Perf).
    if ep_axis is not None:
        E_loc = E // ep_size  # static: ep_size is the mesh "model" extent
        rank = jax.lax.axis_index(ep_axis)
        mine = (flat_e // E_loc) == rank
        local_e = jnp.where(mine, flat_e - rank * E_loc, 0)
        sel = mine & keep
    else:
        E_loc = E
        local_e = flat_e
        sel = keep
    contrib = jnp.where(sel[:, None], xt[flat_t], 0).astype(dtype)
    buf = jnp.zeros((E_loc, capacity, D), dtype)
    buf = buf.at[local_e, safe_pos].add(contrib, mode="drop")

    # --- expert FFN on local experts ---
    if fused_table is not None:
        # fused path: both gemms + PWL activation + gating in one Pallas
        # kernel — the (E, C, F) pre-activations never round-trip HBM.
        # Training goes through the kernel's custom VJP: the backward
        # rematerializes zg/zu per expert blockwise and decodes the PWL
        # slope in-kernel (impl_bwd="recompute" restores jnp autodiff math)
        from repro.kernels import fused

        h = fused.fused_moe_glu(
            buf, params["w_gate"].astype(dtype), params["w_up"].astype(dtype),
            table=fused_table,
        )
    else:
        g = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"].astype(dtype))
        u = jnp.einsum("ecd,edf->ecf", buf, params["w_up"].astype(dtype))
        h = act(g) * u
    out = jnp.einsum("ecf,efd->ecd", h, params["w_down"].astype(dtype))

    # --- combine: partial outputs for local tokens, psum across EP ranks ---
    w_sel = jnp.where(sel, flat_w, 0.0)
    picked = out[local_e, safe_pos] * w_sel[:, None].astype(dtype)  # (T*K, D)
    y = jnp.zeros((T, D), dtype).at[flat_t].add(picked)
    if ep_axis is not None:
        y = jax.lax.psum(y, ep_axis)

    # --- switch load-balancing loss (local stats; caller pmean's) ---
    frac_tokens = jnp.mean(
        jax.nn.one_hot(top_e[:, 0], E, dtype=jnp.float32), axis=0
    )
    frac_probs = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_tokens * frac_probs)

    return y.reshape(B, S, D), aux
