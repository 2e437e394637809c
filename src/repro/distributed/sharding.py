"""Logical-axis sharding rules: one table maps model-space axes to mesh axes.

The whole framework annotates arrays with *logical* axes ("batch", "heads",
"mlp", ...).  A ``Rules`` object — selected per mesh and per arch — translates
them to physical mesh axes for pjit in/out shardings and in-graph
``with_sharding_constraint``s.  This keeps DP/FSDP/TP/EP/SP decisions in ONE
place and lets the perf loop swap schemes without touching model code.

Auto-selection logic (see ``make_rules``):
  * attention TP over heads when head counts divide the model axis,
    sequence-parallel attention otherwise (no divisibility constraint);
  * experts always shard over "model" (EP);
  * FSDP shards the d_model rows of weights over "data";
  * batch shards over ("pod", "data") so pods compose data parallelism.
"""
from __future__ import annotations

import contextvars
import dataclasses
import warnings
from typing import Optional

import jax
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class Rules:
    table: dict
    mesh_axes: tuple[str, ...]
    mesh: object = None  # jax Mesh — set to emit NamedShardings in constrain()

    def spec(self, *logical_axes) -> P:
        phys = []
        used = set()
        for ax in logical_axes:
            m = self.table.get(ax) if ax is not None else None
            if m is None:
                phys.append(None)
                continue
            ms = tuple(m) if isinstance(m, (tuple, list)) else (m,)
            ms = tuple(a for a in ms if a in self.mesh_axes and a not in used)
            used.update(ms)
            phys.append(ms if len(ms) > 1 else (ms[0] if ms else None))
        return P(*phys)

    def tree_specs(self, logical_tree):
        return jax.tree_util.tree_map(
            lambda axes: self.spec(*axes),
            logical_tree,
            is_leaf=lambda x: isinstance(x, tuple)
            and all(a is None or isinstance(a, str) for a in x),
        )


_ACTIVE: contextvars.ContextVar[Optional[Rules]] = contextvars.ContextVar(
    "active_rules", default=None
)


def active_rules() -> Optional[Rules]:
    """The Rules activated by the innermost ``use_rules`` (or None)."""
    return _ACTIVE.get()


def active_mesh_rules() -> Optional[Rules]:
    """The active Rules when they carry a real multi-device mesh.

    This is the shard-aware dispatch predicate: fused (Pallas) call sites ask
    for it and, when non-None, wrap the kernel in ``shard_map`` with per-shard
    specs derived from the rules (see ``repro.distributed.shard_fused``).
    Returns None for no rules, no mesh, or a 1-device mesh — those cases run
    the kernel directly (GSPMD has nothing to partition)."""
    rules = _ACTIVE.get()
    if rules is not None and rules.mesh is not None and rules.mesh.size > 1:
        return rules
    return None


def spec_axes(rules: Rules, logical_axis: Optional[str]) -> tuple[str, ...]:
    """Physical mesh axes one logical axis maps to under `rules` (may be ())."""
    if logical_axis is None:
        return ()
    entry = rules.spec(logical_axis)[0]
    if entry is None:
        return ()
    return entry if isinstance(entry, tuple) else (entry,)


def logical_extent(rules: Rules, logical_axis: Optional[str]) -> int:
    """Product of mesh-axis sizes a logical axis shards over (1 = replicated)."""
    if rules.mesh is None:
        return 1
    return _axis_size(rules.mesh, spec_axes(rules, logical_axis) or None)


class use_rules:
    """Context manager activating a Rules table for `constrain` calls."""

    def __init__(self, rules: Optional[Rules]):
        self.rules = rules

    def __enter__(self):
        self._tok = _ACTIVE.set(self.rules)
        return self.rules

    def __exit__(self, *exc):
        _ACTIVE.reset(self._tok)


def _axis_size(mesh, entry) -> int:
    if entry is None:
        return 1
    names = entry if isinstance(entry, tuple) else (entry,)
    n = 1
    for a in names:
        n *= mesh.shape[a]
    return n


# (axis entry, shape) pairs sanitize_spec already reported — dropping a spec
# entry silently replicates the array, which for params is a real perf bug
# the user should see exactly once, not a warning storm on every trace.
_SANITIZE_WARNED: set = set()


def sanitize_spec(mesh, spec: P, shape) -> P:
    """Drop spec entries whose mesh-axis product doesn't divide the dim —
    keeps ragged dims (1500-frame encoders, S=1 decode, odd vocabs when
    unpadded) compiling instead of erroring, at the cost of replication.
    Each dropped (axis entry, shape) pair is reported once per process so
    mis-sharded params are visible instead of silently replicated."""
    out = []
    for dim, entry in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if entry is not None and dim % _axis_size(mesh, entry) != 0:
            key = (
                tuple(entry) if isinstance(entry, (tuple, list)) else entry,
                tuple(shape),
            )
            # dim 1 is trivially unshardable (B=1 prefill, S=1 decode):
            # replicating it is a no-op, not a mis-sharding worth a warning
            if dim > 1 and key not in _SANITIZE_WARNED:
                _SANITIZE_WARNED.add(key)
                warnings.warn(
                    f"sharding spec entry {entry!r} (mesh extent "
                    f"{_axis_size(mesh, entry)}) does not divide dim {dim} of "
                    f"shape {tuple(shape)}; replicating that dim instead",
                    stacklevel=2,
                )
            entry = None
        out.append(entry)
    return P(*out)


def reset_sanitize_warnings() -> None:
    """Clear the sanitize_spec warn-once state (tests)."""
    _SANITIZE_WARNED.clear()


def constrain(x, *logical_axes):
    """with_sharding_constraint via the active logical rules (no-op if none)."""
    rules = _ACTIVE.get()
    if rules is None:
        return x
    spec = rules.spec(*logical_axes)
    if rules.mesh is not None:
        from jax.sharding import NamedSharding

        spec = sanitize_spec(rules.mesh, spec, x.shape)
        return jax.lax.with_sharding_constraint(x, NamedSharding(rules.mesh, spec))
    return jax.lax.with_sharding_constraint(x, spec)


def make_rules(
    cfg,
    mesh,
    *,
    fsdp: bool = True,
    seq_parallel_attn: Optional[bool] = None,
    shard_vocab: bool = True,
) -> Rules:
    """Build the rules table for (arch config, mesh)."""
    axes = mesh.axis_names
    model_size = mesh.shape["model"] if "model" in mesh.shape else 1
    batch_axes = tuple(a for a in ("pod", "data") if a in axes)

    heads_divisible = (
        cfg.n_heads % model_size == 0 and cfg.n_kv_heads % model_size == 0
    )
    if seq_parallel_attn is None:
        seq_parallel_attn = not heads_divisible

    table = {
        "batch": batch_axes,
        "embed": "data" if fsdp else None,
        "mlp": "model",
        "vocab": "model" if shard_vocab else None,
        "experts": "model",
        "flat_tokens": batch_axes,
        "layers": None,
        "state": None,
        "conv": None,
        # activation-space axes.  act_seq stays unsharded by default: A/B
        # probes (EXPERIMENTS.md Sec. Perf) showed sequence-sharded activations
        # force per-gemm all-gathers against model-sharded weights (~6.4GB/layer
        # on qwen2.5-32b) — costlier than replicating attention compute across
        # the model axis for non-divisible head counts.
        "act_embed": None,
        "act_heads": None if seq_parallel_attn else "model",
        "act_kv": None if seq_parallel_attn else "model",
        "act_seq": None,
        # cache axes (decode): kv-heads over model when divisible, else cache
        # sequence over model (flash-decoding style partial attention)
        "cache_seq": "model" if seq_parallel_attn else None,
        "cache_kv": None if seq_parallel_attn else "model",
        # weight-space attention axes (replicated over model when heads don't
        # divide; FSDP over data still applies via "embed")
        "heads": None if seq_parallel_attn else "model",
        "kv": None if seq_parallel_attn else "model",
        # ssm inner dim: always feature-sharded over model (no head grouping
        # constraint — heads*head_dim divides cleanly)
        "ssm_inner": "model",
        "ssm_heads": "model",
    }
    return Rules(table=table, mesh_axes=tuple(axes), mesh=mesh)


def specs_for_params(rules: Rules, logical_tree):
    """Physical PartitionSpec tree for a logical-axes tree."""
    return rules.tree_specs(logical_tree)
