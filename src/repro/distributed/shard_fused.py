"""Per-shard fused dispatch: run PWL Pallas kernels inside ``shard_map``.

GSPMD cannot partition a ``pallas_call`` — under a multi-device mesh a fused
kernel must be invoked *per shard*, with every rank seeing a local block whose
shape the kernel handles natively.  This module holds the spec derivation
shared by every fused dispatch point (``models/layers.py``, ``models/moe.py``,
``serving/kv_cache.py``):

  * batch dims shard over the rules' "batch" axes when divisible, else
    replicate (each rank redundantly computes the full batch — same FLOPs
    as the unfused GSPMD path, which also replicates non-divisible dims);
  * head / model-feature dims shard over their logical axis ("act_heads",
    "mlp", "cache_kv", ...) when the global dim divides the mesh extent,
    else replicate — again matching what ``sanitize_spec`` does to the
    unfused path's constraints;
  * PWL tables are **closed over**, never passed as shard_map operands: the
    fused kernels pack tables host-side at trace time
    (``fused/epilogue.pack_table``), which a traced operand would break.
    Tables are tiny (n_segments+1 floats) so replicating them as jaxpr
    constants is free — this is the software analogue of Flex-SFU
    broadcasting one coefficient table to every vector lane.

No psums are introduced anywhere fused math is head- or feature-local
(attention per head, GLU per d_ff column); the only collectives are the ones
the unfused math already performs (the MoE expert-parallel combine in
``models/moe.py``).  :data:`shard_map` is ``jax.shard_map`` with
``check_vma=False``, and every per-shard body in the repo uses it: fused
outputs may be replicated over mesh axes the specs don't mention, and the
varying-axes checker cannot see through a pallas_call anyway.

Gradients differentiate straight through these wrappers: the fused ops are
``jax.custom_vjp``s, so shard_map transposes them per-shard — the fused
Pallas *backward* kernels (kernels/fused/backward.py) run on the same local
blocks as the forward, and shard_map inserts the psums the transpose needs
(e.g. for a replicated-in FSDP weight).  Pinned by
tests/test_shard_fused.py::test_train_step_2x2_mesh_fused_backward_grad_parity
(2x2 mesh, warnings-as-errors, parity vs the no-mesh step and the
impl_bwd="recompute" oracle).

Design doc: docs/distributed.md.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
from jax.sharding import PartitionSpec as P

from .sharding import Rules, logical_extent, spec_axes

shard_map = functools.partial(jax.shard_map, check_vma=False)


def dim_entry(rules: Rules, logical_axis: Optional[str], dim: int):
    """The PartitionSpec entry for one array dim: the logical axis' physical
    mesh axes when their extent divides `dim`, else None (replicate)."""
    axes = spec_axes(rules, logical_axis)
    if not axes:
        return None
    if dim % logical_extent(rules, logical_axis) != 0:
        return None
    return axes if len(axes) > 1 else axes[0]


def shard_spec(rules: Rules, logical_axes, shape) -> P:
    """Per-shard PartitionSpec for an array: one logical axis per dim
    (None = replicated), with non-dividing entries dropped."""
    assert len(logical_axes) == len(shape), (logical_axes, shape)
    return P(*(dim_entry(rules, ax, d) for ax, d in zip(logical_axes, shape)))


def sharded_call(rules: Rules, in_specs, out_specs):
    """Decorator: run `fn` per-shard on the rules' mesh.

    ``fn`` receives local blocks; inputs whose current sharding disagrees
    with ``in_specs`` are resharded (collectives inserted by shard_map), so
    callers only describe the layout the kernel wants, not the layout the
    operands happen to have."""

    def wrap(fn):
        return shard_map(
            fn,
            mesh=rules.mesh,
            in_specs=in_specs,
            out_specs=out_specs,
        )

    return wrap


def run_sharded(rules: Rules, fn, operands, in_specs, out_specs):
    """Invoke `fn(*operands)` per-shard under the rules' mesh."""
    return sharded_call(rules, tuple(in_specs), out_specs)(fn)(*operands)


def batch_entry(rules: Rules, n: int):
    """Spec entry for a leading batch dim (shard over the "batch" axes when
    they divide `n`, else replicate)."""
    return dim_entry(rules, "batch", n)


def mesh_axis_sizes(rules: Rules) -> dict:
    """{axis name: size} of the rules' mesh (empty without a mesh)."""
    if rules.mesh is None:
        return {}
    return dict(rules.mesh.shape)


__all__ = [
    "batch_entry",
    "dim_entry",
    "mesh_axis_sizes",
    "run_sharded",
    "shard_spec",
    "sharded_call",
    "shard_map",
    "P",
    "jax",
]
