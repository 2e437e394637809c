"""Unified model API: dispatch per family + input_specs for every shape cell.

``Model`` wraps the family implementation behind one interface used by the
launcher, the dry-run, the train example, and the smoke tests:

    model = Model(cfg)
    params = model.init(rng)                      # real arrays
    defs   = model.param_defs()                   # ParamDef tree
    loss, metrics = model.loss(params, batch)
    logits, cache = model.prefill(params, ...)
    logits, cache = model.decode_step(params, ...)

``input_specs(cfg, shape_cell)`` produces ShapeDtypeStruct stand-ins for every
assigned (arch x shape) dry-run cell, including the stubbed modality frontends
([vlm]: patch embeddings, [audio]: frame embeddings).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.distributed.sharding import active_rules, sanitize_spec

from . import encdec, transformer
from .common import (
    ModelConfig,
    ParamDef,
    init_params,
    logical_specs,
    shape_structs,
)


@dataclasses.dataclass(frozen=True)
class ShapeCell:
    name: str          # train_4k | prefill_32k | decode_32k | long_500k
    seq_len: int
    global_batch: int
    kind: str          # train | prefill | decode


SHAPE_CELLS = {
    "train_4k": ShapeCell("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeCell("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeCell("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeCell("long_500k", 524288, 1, "decode"),
}


def materialize(defs, rng):
    """Real arrays for a ParamDef tree.  Under active rules with a mesh they
    are made under ``jit`` straight into their sharded layout (the rules
    applied to each leaf's logical axes), so no leaf ever exists whole on
    one device."""
    rules = active_rules()
    if rules is None or rules.mesh is None:
        return init_params(defs, rng)
    shardings = jax.tree_util.tree_map(
        lambda d: NamedSharding(
            rules.mesh,
            sanitize_spec(rules.mesh, rules.spec(*d.logical_axes), d.shape)),
        defs, is_leaf=lambda x: isinstance(x, ParamDef),
    )
    return jax.jit(lambda r: init_params(defs, r),
                   out_shardings=shardings)(rng)


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self._impl = encdec if cfg.is_encoder_decoder else transformer

    @property
    def plan(self):
        """The compiled activation plan this model executes (repro.sfu)."""
        from repro import sfu

        return sfu.plan_for(self.cfg)

    # -- parameters --------------------------------------------------------
    def param_defs(self):
        if self.cfg.is_encoder_decoder:
            return encdec.encdec_defs(self.cfg)
        return transformer.model_defs(self.cfg)

    def init(self, rng):
        """Real parameters, made sharded under active rules (see
        :func:`materialize`)."""
        return materialize(self.param_defs(), rng)

    def serving_params(self, params):
        """The weights as the serving steps read them: every leaf the
        forward reads at ``cfg.dtype`` cast to it, the float32-read ones
        (``ParamDef.read_f32``) passed through, in one jitted call that
        keeps each leaf's sharding (an uncommitted leaf stays uncommitted,
        free to follow a mesh's rules into the step as its master would).
        The forward's own ``astype`` then casts nothing, so the steps
        compute on the same values without casting the masters every step.
        A tree with nothing to cast comes back as it is."""
        dtype = self.cfg.dtype
        leaves, treedef = jax.tree_util.tree_flatten(params)
        defs = treedef.flatten_up_to(self.param_defs())
        idx = [i for i, (d, x) in enumerate(zip(defs, leaves))
               if not d.read_f32 and x.dtype != dtype]
        if not idx:
            return params
        xs = [leaves[i] for i in idx]
        cast = jax.jit(lambda xs: [x.astype(dtype) for x in xs],
                       out_shardings=[x.sharding if x.committed else None
                                      for x in xs])
        for i, y in zip(idx, cast(xs)):
            leaves[i] = y
        return jax.tree_util.tree_unflatten(treedef, leaves)

    def param_structs(self):
        return shape_structs(self.param_defs())

    def param_logical(self):
        return logical_specs(self.param_defs())

    # -- steps --------------------------------------------------------------
    def loss(self, params, batch):
        return self._impl.loss_fn(self.cfg, params, batch)

    def forward(self, params, batch):
        if self.cfg.is_encoder_decoder:
            return encdec.forward(self.cfg, params, batch["tokens"], batch["frames"])
        return transformer.forward(
            self.cfg, params, batch["tokens"], batch.get("vision_embeds")
        )

    def cache_defs(self, batch: int, max_len: int):
        return self._impl.cache_defs(self.cfg, batch, max_len)

    def make_cache(self, batch: int, max_len: int):
        return self._impl.make_cache(self.cfg, batch, max_len)

    def prefill(self, params, tokens, cache, **extras):
        if self.cfg.is_encoder_decoder:
            return encdec.prefill(self.cfg, params, tokens, cache, extras["frames"])
        return transformer.prefill(
            self.cfg, params, tokens, cache, extras.get("vision_embeds")
        )

    def decode_step(self, params, tokens, cache, pos):
        return self._impl.decode_step(self.cfg, params, tokens, cache, pos)

    # -- paged serving (repro.serving; decoder-only transformers) -----------
    def make_paged_cache(self, num_pages: int, page_size: int):
        """Zeroed paged KV pools, made sharded under active rules (see
        :func:`materialize`)."""
        if self.cfg.is_encoder_decoder:
            from repro.serving.resilience import UnsupportedCacheError

            raise UnsupportedCacheError(
                "paged serving covers decoder-only models"
            )
        return materialize(
            transformer.paged_cache_defs(self.cfg, num_pages, page_size),
            jax.random.PRNGKey(0))

    def prefill_paged(self, params, tokens, cache, page_table, lengths):
        return transformer.prefill_paged(
            self.cfg, params, tokens, cache, page_table, lengths
        )

    def decode_step_paged(self, params, tokens, cache, page_table, kv_len):
        return transformer.decode_step_paged(
            self.cfg, params, tokens, cache, page_table, kv_len
        )


def input_specs(cfg: ModelConfig, cell: ShapeCell) -> dict:
    """ShapeDtypeStruct stand-ins for one (arch x shape) dry-run cell."""
    B, S = cell.global_batch, cell.seq_len
    i32 = jnp.int32
    out: dict[str, Any] = {}
    if cell.kind == "train":
        out["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
        out["targets"] = jax.ShapeDtypeStruct((B, S), i32)
        if cfg.is_encoder_decoder:
            out["frames"] = jax.ShapeDtypeStruct((B, cfg.encoder_seq, cfg.d_model), cfg.dtype)
        if cfg.n_vision_tokens:
            out["vision_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.n_vision_tokens, cfg.d_model), cfg.dtype
            )
    elif cell.kind == "prefill":
        out["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
        if cfg.is_encoder_decoder:
            out["frames"] = jax.ShapeDtypeStruct((B, cfg.encoder_seq, cfg.d_model), cfg.dtype)
        if cfg.n_vision_tokens:
            out["vision_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.n_vision_tokens, cfg.d_model), cfg.dtype
            )
    else:  # decode: one new token against a seq_len-deep cache
        out["tokens"] = jax.ShapeDtypeStruct((B, 1), i32)
        out["pos"] = jax.ShapeDtypeStruct((), i32)
    return out
