"""jit-able train / prefill / decode steps with full sharding annotations.

``build_*`` returns (fn, in_shardings, out_shardings, arg_structs) ready for
``jax.jit(fn, in_shardings=..., out_shardings=...).lower(*arg_structs)`` —
used by both the real launcher (train.py/serve.py) and the dry-run.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed.sharding import Rules, make_rules, sanitize_spec, use_rules
from repro.kernels import fused
from repro.models import Model, ShapeCell, input_specs
from repro.models.common import logical_specs, shape_structs
from repro.optim import adamw


def _named(mesh, spec_tree, struct_tree):
    """NamedShardings for arguments, sanitized against the actual shapes
    (drops mesh axes that don't divide a dim — ragged dims replicate)."""
    return jax.tree_util.tree_map(
        lambda s, st: NamedSharding(mesh, sanitize_spec(mesh, s, st.shape)),
        spec_tree,
        struct_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def batch_specs(cfg, cell: ShapeCell, rules: Rules):
    """PartitionSpec tree matching input_specs(cfg, cell)."""
    bspec = rules.spec("batch")
    sspec = rules.spec("batch", "act_seq")
    out = {}
    if cell.kind == "train":
        out = {"tokens": sspec, "targets": sspec}
        if cfg.is_encoder_decoder:
            out["frames"] = rules.spec("batch", None, None)
        if cfg.n_vision_tokens:
            out["vision_embeds"] = rules.spec("batch", None, None)
    elif cell.kind == "prefill":
        out = {"tokens": sspec}
        if cfg.is_encoder_decoder:
            out["frames"] = rules.spec("batch", None, None)
        if cfg.n_vision_tokens:
            out["vision_embeds"] = rules.spec("batch", None, None)
    else:
        out = {"tokens": rules.spec("batch", None), "pos": P()}
    return out


def make_cell_rules(cfg, mesh, cell: ShapeCell) -> Rules:
    """Rules for (arch, mesh, cell) — handles the B=1 long-context case by
    releasing the batch axis and widening sequence sharding."""
    rules = make_rules(cfg, mesh)
    batch_ways = 1
    for a in ("pod", "data"):
        if a in mesh.shape:
            batch_ways *= mesh.shape[a]
    if cell.global_batch % batch_ways != 0:
        t = dict(rules.table)
        t["batch"] = None
        t["flat_tokens"] = None
        # context parallelism: spread the KV cache / sequence over data+model
        t["cache_seq"] = ("data", "model")
        t["act_seq"] = ("data", "model")
        t["cache_kv"] = None
        rules = Rules(table=t, mesh_axes=rules.mesh_axes, mesh=rules.mesh)
    else:
        t = dict(rules.table)
        t["flat_tokens"] = t["batch"]
        # Perf H3 ("small-model full-DP", EXPERIMENTS.md Sec. Perf): when the
        # model is small enough that per-step activation volume dwarfs weight
        # volume, TP psums (row-parallel partial sums + logit partials) cost
        # far more than replicating weights.  Shard the batch over EVERY mesh
        # axis and drop tensor parallelism entirely; weights FSDP over data.
        from repro.roofline.model import total_params

        all_axes = tuple(a for a in ("pod", "data", "model") if a in mesh.shape)
        ways = 1
        for a in all_axes:
            ways *= mesh.shape[a]
        if cfg.force_dp_only is None:
            small = total_params(cfg) < 2.5e9 and cfg.n_experts == 0
        else:
            small = bool(cfg.force_dp_only)
        if small and cell.kind == "train" and cell.global_batch % ways == 0:
            t["batch"] = all_axes
            t["flat_tokens"] = all_axes
            for ax in ("heads", "kv", "mlp", "vocab", "act_heads", "act_kv",
                       "ssm_inner", "ssm_heads"):
                t[ax] = None
            t["embed"] = "data"
        rules = Rules(table=t, mesh_axes=rules.mesh_axes, mesh=rules.mesh)
    return rules


# ---------------------------------------------------------------------------
# step builders


def auto_microbatches(cfg, cell: ShapeCell, mesh) -> int:
    """Pick grad-accumulation depth so saved layer-boundary activations fit:
    n_boundaries * (B/dp/K) * S * D * 2B <= ~6 GiB per device."""
    dp = 1
    for a in ("pod", "data"):
        if a in mesh.shape:
            dp *= mesh.shape[a]
    b_loc = max(cell.global_batch // dp, 1)
    if cfg.is_encoder_decoder:
        n_bound = cfg.n_layers + cfg.n_encoder_layers
    else:
        n_bound = cfg.n_layers // max(cfg.period, 1)
    per_mb = n_bound * b_loc * cell.seq_len * cfg.d_model * 2
    budget = 6 * 2**30
    k = max(1, -(-per_mb // budget))
    while b_loc % k and k < b_loc:
        k += 1
    return int(min(k, b_loc))


def build_train_step(cfg, mesh, cell: ShapeCell, opt_cfg: Optional[adamw.AdamWConfig] = None,
                     microbatches: Optional[int] = None):
    model = Model(cfg)
    rules = make_cell_rules(cfg, mesh, cell)
    opt_cfg = opt_cfg or adamw.AdamWConfig()
    if microbatches is None:
        microbatches = auto_microbatches(cfg, cell, mesh)

    logical = model.param_logical()
    pspecs = rules.tree_specs(logical)
    state_specs = {
        "params": pspecs,
        "mu": pspecs,
        "nu": pspecs,
        "step": P(),
    }
    bspecs = batch_specs(cfg, cell, rules)

    # cfg.act_impl_bwd pins the backward implementation for every fused
    # site the loss traces ("fused" Pallas kernels, "recompute" as the jnp
    # oracle / escape hatch); None defers to the ambient use_impl_bwd
    # default.  The context is entered inside train_step because the mode
    # is read at TRACE time — this covers jit retraces too.
    impl_bwd = getattr(cfg, "act_impl_bwd", None)
    if impl_bwd is not None:
        impl_bwd = fused.resolve_impl_bwd(impl_bwd)  # validate at build

    def train_step(state, batch):
        bwd_ctx = (fused.use_impl_bwd(impl_bwd) if impl_bwd is not None
                   else contextlib.nullcontext())
        with use_rules(rules), bwd_ctx:
            if microbatches <= 1:
                (loss, metrics), grads = jax.value_and_grad(
                    lambda p: model.loss(p, batch), has_aux=True
                )(state["params"])
            else:
                # gradient accumulation: scan over K microbatches (bf16 grads
                # accumulate in f32; per-microbatch activations are K x smaller)
                def micro(carry, mb):
                    gacc, lacc = carry
                    (l, m), g = jax.value_and_grad(
                        lambda p: model.loss(p, mb), has_aux=True
                    )(state["params"])
                    gacc = jax.tree_util.tree_map(
                        lambda a, b: a + b.astype(jnp.float32), gacc, g
                    )
                    return (gacc, lacc + l), m

                mbs = jax.tree_util.tree_map(
                    lambda x: x.reshape(
                        (microbatches, x.shape[0] // microbatches) + x.shape[1:]
                    ),
                    batch,
                )
                g0 = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), state["params"]
                )
                (grads, loss), metrics = jax.lax.scan(
                    micro, (g0, jnp.float32(0.0)), mbs
                )
                grads = jax.tree_util.tree_map(lambda g: g / microbatches, grads)
                loss = loss / microbatches
                metrics = jax.tree_util.tree_map(lambda m: m[-1], metrics)
            new_state, opt_metrics = adamw.apply_updates(state, grads, opt_cfg)
            metrics = dict(metrics, loss=loss, **opt_metrics)
        return new_state, metrics

    pstructs = shape_structs(model.param_defs())
    state_structs = {
        "params": pstructs,
        "mu": pstructs,
        "nu": pstructs,
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }
    bstructs = input_specs(cfg, cell)
    in_shardings = (
        _named(mesh, state_specs, state_structs),
        _named(mesh, bspecs, bstructs),
    )
    out_shardings = (_named(mesh, state_specs, state_structs), None)
    return (
        train_step,
        in_shardings,
        out_shardings,
        (state_structs, bstructs),
        dict(donate_argnums=(0,), rules=rules),
    )


def _cache_specs_structs(model, cfg, rules, batch, max_len):
    cdefs = model.cache_defs(batch, max_len)
    cspecs = rules.tree_specs(logical_specs(cdefs))
    cstructs = shape_structs(cdefs)
    return cspecs, cstructs


def build_prefill_step(cfg, mesh, cell: ShapeCell):
    model = Model(cfg)
    rules = make_cell_rules(cfg, mesh, cell)
    pspecs = rules.tree_specs(model.param_logical())
    bspecs = batch_specs(cfg, cell, rules)
    B = cell.global_batch
    max_len = cell.seq_len + (cfg.n_vision_tokens or 0)  # VLM prefix rides in cache
    cspecs, cstructs = _cache_specs_structs(model, cfg, rules, B, max_len)

    def prefill_step(params, batch, cache):
        with use_rules(rules):
            extras = {k: v for k, v in batch.items() if k != "tokens"}
            logits, new_cache = model.prefill(params, batch["tokens"], cache, **extras)
        return logits, new_cache

    pstructs = shape_structs(model.param_defs())
    bstructs = input_specs(cfg, cell)
    in_shardings = (
        _named(mesh, pspecs, pstructs),
        _named(mesh, bspecs, bstructs),
        _named(mesh, cspecs, cstructs),
    )
    out_shardings = (None, _named(mesh, cspecs, cstructs))
    return (
        prefill_step,
        in_shardings,
        out_shardings,
        (pstructs, bstructs, cstructs),
        dict(donate_argnums=(2,)),
    )


def build_decode_step(cfg, mesh, cell: ShapeCell):
    model = Model(cfg)
    rules = make_cell_rules(cfg, mesh, cell)
    pspecs = rules.tree_specs(model.param_logical())
    bspecs = batch_specs(cfg, cell, rules)
    B = cell.global_batch
    max_len = cell.seq_len + (cfg.n_vision_tokens or 0)
    cspecs, cstructs = _cache_specs_structs(model, cfg, rules, B, max_len)

    def decode_step(params, batch, cache):
        with use_rules(rules):
            logits, new_cache = model.decode_step(
                params, batch["tokens"], cache, batch["pos"]
            )
        return logits, new_cache

    pstructs = shape_structs(model.param_defs())
    bstructs = input_specs(cfg, cell)
    in_shardings = (
        _named(mesh, pspecs, pstructs),
        _named(mesh, bspecs, bstructs),
        _named(mesh, cspecs, cstructs),
    )
    out_shardings = (None, _named(mesh, cspecs, cstructs))
    return (
        decode_step,
        in_shardings,
        out_shardings,
        (pstructs, bstructs, cstructs),
        dict(donate_argnums=(2,)),
    )


def build_step(cfg, mesh, cell: ShapeCell, microbatches: Optional[int] = None):
    if cell.kind == "train":
        return build_train_step(cfg, mesh, cell, microbatches=microbatches)
    if cell.kind == "prefill":
        return build_prefill_step(cfg, mesh, cell)
    return build_decode_step(cfg, mesh, cell)
