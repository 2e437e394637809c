"""Weights from the seed, made on the device in one jitted call.

The program says what tree of parameters it takes (names, shapes, dtypes);
the values are the benchmark's own, drawn from ``--seed`` by the rule below,
so that the plain reference can be handed the very same arrays without
taking anything the program made.  The rule keeps a random model tame: each
matrix has std 1/sqrt(fan-in), the embedding 0.02, norm scales their
identity.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# fan-in of each matrix, by leaf name, from its shape without the leading
# layer axis: (D, H, dh) projections sum over D; the output projection
# (H, dh, D) over H*dh; (in, out) matrices over their first axis.
_FAN_IN = {
    "wq": lambda s: s[0], "wk": lambda s: s[0], "wv": lambda s: s[0],
    "wo": lambda s: s[0] * s[1],
    "w_gate": lambda s: s[-2], "w_up": lambda s: s[-2],
    "w_down": lambda s: s[-2], "unembed": lambda s: s[0],
}


def _leaf_name(path) -> str:
    last = path[-1]
    return getattr(last, "key", getattr(last, "name", str(last)))


def _draw(name: str, struct, key, stacked: bool):
    shape = struct.shape
    if name == "scale":        # rmsnorm (1 + scale) and layernorm scale
        return jnp.zeros(shape, struct.dtype)
    if name == "embed":
        std = 0.02
    elif name in _FAN_IN:
        std = 1.0 / math.sqrt(_FAN_IN[name](shape[1:] if stacked else shape))
    else:
        raise ValueError(f"no rule for parameter {name!r} {shape}")
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(
        struct.dtype)


def builder(structs):
    """The function from a PRNG key to arrays shaped as ``structs`` (a tree
    of ShapeDtypeStruct), to be traced inside a jitted call."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(structs)

    def build(key):
        keys = jax.random.split(key, len(flat))
        out = []
        for k, (path, st) in zip(keys, flat):
            out.append(_draw(_leaf_name(path), st, k, stacked(path)))
        return jax.tree_util.tree_unflatten(treedef, out)

    return build


def stacked(path) -> bool:
    """Whether a leaf carries the leading layer axis of a scanned stack."""
    return any(getattr(p, "key", None) == "layers" for p in path)


def make(structs, seed: int, shardings=None):
    """Arrays shaped as ``structs``, drawn from ``seed`` in one jitted call,
    placed as ``shardings`` says (a matching tree) or on the default
    device."""
    return jax.jit(builder(structs), out_shardings=shardings)(seed_key(seed))


def slice_norms(tree):
    """The norm of every leaf, and of every layer's slice of a stacked leaf,
    as one flat float32 vector in a fixed order (traceable)."""
    out = []
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = x.astype(jnp.float32)
        if stacked(path):
            out.append(jnp.sqrt(jnp.sum(jnp.square(x).reshape(x.shape[0], -1),
                                        axis=1)))
        else:
            out.append(jnp.sqrt(jnp.sum(jnp.square(x)))[None])
    return jnp.concatenate(out)


def change_norms(params, structs, seed: int):
    """``slice_norms(params - initial)``, where the initial arrays are drawn
    again from ``seed`` inside the same jitted call."""
    build = builder(structs)

    def go(p, key):
        p0 = build(key)
        return slice_norms(jax.tree_util.tree_map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p, p0))

    return jax.jit(go)(params, seed_key(seed))


def seed_key(seed: int):
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)
