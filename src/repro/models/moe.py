"""Mixture-of-Experts FFN: token-choice top-k routing, dropless.

The router is a softmax over every expert in f32; each token takes its top
``n_active_experts``, weighted by their probabilities, renormalised to sum
to one only where the configuration says so (``norm_topk``).  No routed
(token, choice) row is ever dropped: the rows are laid out grouped by
expert, each group padded with zero rows to the kernels' row tile, and the
grouped expert GLU and down projection run over that layout
(``kernels/fused/moe.py``; ``jax.lax.ragged_dot`` where the expert site is
not planned fused).  A row's result is then weighted and summed back into
its token.

Two execution paths:

* **shard_map** (any multi-device mesh).  With E divisible by the "model"
  extent, experts are parallel: tokens replicate over the model axis, each
  rank lays out and computes only the rows routed to its own slice of the
  experts, and one psum of the (T, D) partial outputs combines the ranks.
  Otherwise expert weights replicate over "model" and only the batch
  shards.
* **single device**: one rank that holds every expert.

GSPMD's scatter partitioner cannot prove the layout local to a rank and
would gather it over the mesh; inside shard_map the layout is the rank's
own.

Alongside the output the layer returns the rows each expert computed,
``(ranks, E / ranks)`` int32 over the whole batch, one line per rank of
the expert-parallel share (one rank where the experts do not shard): the
rows that landed inside the layout the kernels run over, counted after the
layout is built, so a row the layout lost is missing from them.  The
serving engine's routing counters hold them to every routed row.  Aux loss:
Switch-style load balancing, returned to the train step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import sfu
from repro.distributed.shard_fused import shard_map
from repro.distributed.sharding import _ACTIVE

from .common import ModelConfig


def moe_layer(cfg: ModelConfig, params, x, plan=None):
    """x: (B, S, D) -> (y: (B, S, D), aux_loss: scalar f32,
    expert_rows: (ranks, E / ranks) int32 rows each expert computed, by
    the rank that holds it).

    The expert activation resolves through the activation plan (site
    ``"moe.expert:<activation>"``).  Under an active Rules mesh the layer
    always runs inside shard_map: expert-parallel when E divides the model
    extent, replicated experts otherwise (batch still shards over the data
    axes).  Sites planned ``impl="fused"`` run the grouped expert GLU as
    one Pallas kernel, on a single device directly and under a mesh inside
    the shard_map body, on each rank's own experts."""
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
        y, aux, rows = _moe_layer_shardmap(cfg, params, x, rules, act,
                                           fused_table)
    else:
        y, aux, rows = _moe_local(cfg, params, x, act, fused_table)
    if fused_table is not None:
        # sfu.guard checkpoint on the combined expert output — placed here
        # (outside the shard_map body) so collector emissions never capture
        # per-shard tracers; a NaN in any expert propagates through the
        # weighted combine, so finite-checking the combine covers the site
        y = sfu.guard.check_fused(key, y)
    return y, aux, rows


def _moe_layer_shardmap(cfg: ModelConfig, params, x, rules, act, fused_table):
    """MoE under a mesh: each rank lays out its own rows.

    Expert-parallel (`ep`) when E divides the "model" extent: expert weights
    shard over "model", each rank computes the rows routed to its slice of
    the experts, one psum combines the partial outputs.  Otherwise expert
    weights replicate over "model" and every model rank computes
    identically — still inside shard_map so a fused-planned site keeps its
    Pallas kernels per shard."""
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
        out_specs=(P(x_bspec, None, None), P(),
                   P("model" if ep else None, None)),
    )
    def run(x_loc, p_loc):
        y, aux, rows = _moe_local(cfg, p_loc, x_loc, act, fused_table,
                                  ep_axis="model" if ep else None)
        if x_bspec is not None:
            for a in batch_axes:
                aux = jax.lax.pmean(aux, a)
                rows = jax.lax.psum(rows, a)
        return y, aux, rows

    return run(x, {k: params[k] for k in pspecs})


def route(cfg: ModelConfig, router, xt):
    """Router probabilities over every expert (T, E), and each token's
    top-k weights and experts (T, K), in f32."""
    logits = xt.astype(jnp.float32) @ router.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, cfg.n_active_experts)
    if cfg.norm_topk:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    return probs, top_w, top_e


def _moe_local(cfg: ModelConfig, params, x, act, fused_table, ep_axis=None):
    """The layer on this rank's tokens.  With ``ep_axis`` set the rank holds
    the experts ``[rank * E_loc, (rank + 1) * E_loc)`` of the expert axis
    sharded over that mesh axis, computes the rows routed to them, and the
    psum over the axis adds the other ranks' rows."""
    B, S, D = x.shape
    T = B * S
    E, K = cfg.n_experts, cfg.n_active_experts
    dtype = x.dtype
    xt = x.reshape(T, D)
    probs, top_w, top_e = route(cfg, params["router"], xt)
    flat_e = top_e.reshape(-1)                       # (T*K,)
    flat_t = jnp.repeat(jnp.arange(T), K)
    flat_w = top_w.reshape(-1)

    E_loc = params["w_gate"].shape[0]
    lo = 0 if ep_axis is None else jax.lax.axis_index(ep_axis) * E_loc
    y, rows = _expert_rows(cfg, params, xt, flat_t, flat_e - lo, flat_w,
                           E_loc, act, fused_table)
    if ep_axis is not None:
        y = jax.lax.psum(y, ep_axis)

    # --- switch load-balancing loss (local stats; caller pmean's) ---
    frac_tokens = jnp.mean(
        jax.nn.one_hot(top_e[:, 0], E, dtype=jnp.float32), axis=0
    )
    frac_probs = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_tokens * frac_probs)

    return y.astype(dtype).reshape(B, S, D), aux, rows[None]


def _expert_rows(cfg: ModelConfig, params, xt, flat_t, local_e, flat_w,
                 E_loc: int, act, fused_table):
    """(T, D) f32: the sum, over every row routed to one of the ``E_loc``
    experts held here (``0 <= local_e < E_loc``), of that expert's output
    for the row's token times the row's weight; and the rows of each of
    those experts that landed inside the layout, (E_loc,) int32.

    Layout: expert ``e``'s rows, in row order, then padding to a multiple of
    the row tile ``bm``; groups in expert order, ``layout_rows`` long;
    tiles past the last group are skipped."""
    from repro.kernels import fused

    T, D = xt.shape
    K = cfg.n_active_experts
    dtype = xt.dtype
    bm = fused.moe.row_tile(T * K / cfg.n_experts)
    m_pad = layout_rows(T, K, E_loc, bm)

    mine = (local_e >= 0) & (local_e < E_loc)
    grp = jnp.where(mine, local_e, 0)
    oh = jax.nn.one_hot(grp, E_loc, dtype=jnp.int32) * mine[:, None]
    sizes = jnp.sum(oh, axis=0)                          # (E_loc,)
    padded = -(-sizes // bm) * bm
    start = jnp.cumsum(padded) - padded
    within = jnp.take_along_axis(jnp.cumsum(oh, axis=0), grp[:, None],
                                 axis=1)[:, 0] - 1
    # rows of other ranks' experts go nowhere (out of range: dropped writes)
    dest = jnp.where(mine, start[grp] + within, m_pad)
    xp = jnp.zeros((m_pad, D), dtype).at[dest].set(xt[flat_t], mode="drop")
    laid = jnp.sum(oh * (dest < m_pad)[:, None], axis=0)  # (E_loc,)

    wg = params["w_gate"].astype(dtype)
    wu = params["w_up"].astype(dtype)
    wd = params["w_down"].astype(dtype)
    if fused_table is not None:
        # grouped GLU with the PWL epilogue in one Pallas kernel, then the
        # grouped down projection; training goes through their custom VJPs
        h = fused.fused_moe_glu(xp, wg, wu, padded, row_tile=bm,
                                table=fused_table)
        out = fused.moe.moe_down(h, wd, padded, row_tile=bm)
    else:
        g = jax.lax.ragged_dot(xp, wg, padded)
        u = jax.lax.ragged_dot(xp, wu, padded)
        out = jax.lax.ragged_dot(act(g) * u, wd, padded)

    picked = out.at[dest].get(mode="fill", fill_value=0).astype(jnp.float32)
    picked = picked * jnp.where(mine, flat_w, 0.0)[:, None]
    return jnp.zeros((T, D), jnp.float32).at[flat_t].add(picked), laid


def layout_rows(T: int, K: int, E_loc: int, bm: int) -> int:
    """Rows of a rank's grouped layout: a rank can be routed at most
    ``T * min(K, E_loc)`` rows, plus up to a tile's padding per group."""
    return -(-(T * min(K, E_loc) + E_loc * (bm - 1)) // bm) * bm
