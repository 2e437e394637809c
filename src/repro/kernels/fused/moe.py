"""Grouped MoE-expert kernels over rows sorted by expert.

The MoE layer (``models/moe.py``) routes every (token, choice) row to one
expert and drops none.  It lays the rows out grouped by expert, each group
padded with zero rows to a multiple of the row tile ``bm``, so that every
row tile belongs to one expert.  Two kernels then run over that layout:

* :func:`fused_moe_glu`, ``act(x_r @ Wg[e]) * (x_r @ Wu[e])`` for each row
  ``r`` of expert ``e``.  Both gemms share the row tile, and the non-uniform
  PWL decode (``fused/epilogue.pwl_eval_tile``) evaluates on the gate
  product and multiplies with the up product before the single writeback,
  so the ``(rows, F)`` pre-activations never round-trip HBM (the paper's
  Sec. V: the SFU evaluates the nonlinearity beside the MAC array).
* :func:`moe_down`, the grouped down projection ``h_r @ Wd[e]``.

The group sizes arrive as scalar prefetch, turned into one expert index per
row tile: a row tile's weight blocks are its own expert's, and an expert
that took no row is never read.  The grid is ``(N/bn, row tiles)`` with the
row tiles innermost and the whole contraction in one block, so consecutive
tiles of one expert keep the same weight block and the pipeline fetches it
once.  Tiles past the last group (the layout is sized for the most rows a
call can take) read nothing new and write zeros.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.pwl import PWLTable

from .._backend import should_interpret
from .backward import resolve_impl_bwd
from .epilogue import EpiloguePlan, plan_and_operands, plan_value_and_slope

BLOCK_N = 512


def row_tile(rows_per_group: float) -> int:
    """The row tile for groups of about ``rows_per_group`` rows: a power of
    two from 16 (the bf16 sublane tile) to 128 (the MXU), at least half a
    group, so that padding costs at most about half a tile per group."""
    bm = 16
    while bm < 128 and bm < rows_per_group / 2:
        bm *= 2
    return bm


def tile_experts(group_sizes, n_tiles: int, bm: int):
    """The expert of each of ``n_tiles`` row tiles, and how many tiles hold
    rows, for groups of ``group_sizes`` rows (each a multiple of ``bm``)
    laid out in order.  Tiles past the last group keep the last group's
    expert, so the pipeline fetches no new weights for them."""
    ends = jnp.cumsum(group_sizes // bm)
    n_used = ends[-1]
    t = jnp.minimum(jnp.arange(n_tiles, dtype=jnp.int32),
                    jnp.maximum(n_used - 1, 0))
    # the groups that end at or before a tile come before its own
    te = jnp.sum(ends[None, :] <= t[:, None], axis=1, dtype=jnp.int32)
    te = jnp.minimum(te, group_sizes.shape[0] - 1)
    return te, n_used.reshape(1).astype(jnp.int32)


def _block_n(n: int) -> int:
    return BLOCK_N if n % BLOCK_N == 0 else n


def _grid_spec(n_in_w: int, n_tab_specs, m: int, k: int, n: int, bm: int,
               bn: int, extra_row_inputs: int = 0, n_out: int = 1):
    def rows(j, t, te, nu):
        return (jnp.minimum(t, jnp.maximum(nu[0] - 1, 0)), 0)

    def rows_j(j, t, te, nu):
        return (jnp.minimum(t, jnp.maximum(nu[0] - 1, 0)), j)

    in_specs = [pl.BlockSpec((bm, k), rows)]
    in_specs += [pl.BlockSpec((1, k, bn), lambda j, t, te, nu: (te[t], 0, j))
                 ] * n_in_w
    in_specs += [pl.BlockSpec((bm, bn), rows_j)] * extra_row_inputs
    in_specs += [pl.BlockSpec(shape, lambda j, t, te, nu: (0, 0))
                 for shape in n_tab_specs]
    out = pl.BlockSpec((bm, bn), lambda j, t, te, nu: (t, j))
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(n // bn, m // bm), in_specs=in_specs,
        out_specs=out if n_out == 1 else [out] * n_out)


def _when_used(nu_ref, compute, outs):
    t = pl.program_id(1)

    @pl.when(t < nu_ref[0])
    def _():
        compute()

    @pl.when(t >= nu_ref[0])
    def _():
        for o in outs:
            o[...] = jnp.zeros_like(o)


def _glu_kernel(te_ref, nu_ref, x_ref, wg_ref, wu_ref, *rest,
                plan: EpiloguePlan):
    tab_refs, o_ref = rest[:plan.n_operands], rest[plan.n_operands]

    def compute():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        o_ref[...] = (plan.apply(g, *tab_refs) * u).astype(o_ref.dtype)

    _when_used(nu_ref, compute, [o_ref])


def _glu_dz_kernel(te_ref, nu_ref, x_ref, wg_ref, wu_ref, g_ref, *rest,
                   plan: EpiloguePlan):
    tab_refs = rest[:plan.n_operands]
    dzg_ref, dzu_ref = rest[plan.n_operands], rest[plan.n_operands + 1]

    def compute():
        x = x_ref[...]
        zg = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        zu = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        act_zg, slope = plan.apply_value_and_slope(zg, *tab_refs)
        gf = g_ref[...].astype(jnp.float32)
        dzg_ref[...] = gf * zu * slope
        dzu_ref[...] = gf * act_zg

    _when_used(nu_ref, compute, [dzg_ref, dzu_ref])


def _down_kernel(te_ref, nu_ref, x_ref, w_ref, o_ref):
    def compute():
        o_ref[...] = jnp.dot(x_ref[...], w_ref[0],
                             preferred_element_type=jnp.float32
                             ).astype(o_ref.dtype)

    _when_used(nu_ref, compute, [o_ref])


@functools.partial(jax.jit, static_argnames=("plan", "bm", "interpret"))
def _glu_call(x, wg, wu, tables, te, nu, *, plan, bm, interpret):
    (m, k), n = x.shape, wg.shape[2]
    bn = _block_n(n)
    return pl.pallas_call(
        functools.partial(_glu_kernel, plan=plan),
        grid_spec=_grid_spec(2, plan.table_specs(), m, k, n, bm, bn),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret, name="moe_glu",
    )(te, nu, x, wg, wu, *tables)


@functools.partial(jax.jit, static_argnames=("plan", "bm", "interpret"))
def _glu_dz_call(x, wg, wu, g, tables, te, nu, *, plan, bm, interpret):
    """(dzg, dzu) of the grouped GLU in one pass; each (M, N) f32."""
    (m, k), n = x.shape, wg.shape[2]
    bn = _block_n(n)
    return pl.pallas_call(
        functools.partial(_glu_dz_kernel, plan=plan),
        grid_spec=_grid_spec(2, plan.table_specs(), m, k, n, bm, bn,
                             extra_row_inputs=1, n_out=2),
        out_shape=[jax.ShapeDtypeStruct((m, n), jnp.float32)] * 2,
        interpret=interpret, name="moe_glu_dz",
    )(te, nu, x, wg, wu, g.astype(jnp.float32), *tables)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def _down_call(x, w, te, nu, *, bm, interpret):
    (m, k), n = x.shape, w.shape[2]
    bn = _block_n(n)
    return pl.pallas_call(
        _down_kernel,
        grid_spec=_grid_spec(1, (), m, k, n, bm, bn),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret, name="moe_down",
    )(te, nu, x, w)


# --- autodiff: fused forward; (dzg, dzu) fused or recomputed; dx and the
# expert weights' gradients from the grouped product's own transpose
# (``jax.lax.ragged_dot`` over the same groups: padding rows are zero, so
# they add nothing)


def _ragged_vjp(x, w, sizes, dz):
    """(dx, dw) of ``ragged_dot(x, w, sizes)`` at cotangent ``dz``."""
    _, vjp = jax.vjp(lambda a, b: jax.lax.ragged_dot(
        a, b, sizes, preferred_element_type=jnp.float32), x, w)
    return vjp(dz)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _glu_op(x, wg, wu, tables, sizes, tiles, plan, bm, interpret, impl_bwd):
    return _glu_call(x, wg, wu, tables, *tiles, plan=plan, bm=bm,
                     interpret=interpret)


def _glu_op_fwd(x, wg, wu, tables, sizes, tiles, plan, bm, interpret,
                impl_bwd):
    y = _glu_op(x, wg, wu, tables, sizes, tiles, plan, bm, interpret,
                impl_bwd)
    return y, (x, wg, wu, tables, sizes, tiles)


def _glu_op_bwd(plan, bm, interpret, impl_bwd, res, g):
    x, wg, wu, tables, sizes, tiles = res
    xf, wgf, wuf = (a.astype(jnp.float32) for a in (x, wg, wu))
    if impl_bwd == "fused":
        dzg, dzu = _glu_dz_call(x, wg, wu, g, tables, *tiles, plan=plan,
                                bm=bm, interpret=interpret)
    else:
        zg = jax.lax.ragged_dot(xf, wgf, sizes)
        zu = jax.lax.ragged_dot(xf, wuf, sizes)
        act_zg, slope = plan_value_and_slope(plan, tables, zg)
        gf = g.astype(jnp.float32)
        dzg, dzu = gf * zu * slope, gf * act_zg
    dxg, dwg = _ragged_vjp(xf, wgf, sizes, dzg)
    dxu, dwu = _ragged_vjp(xf, wuf, sizes, dzu)
    dtables = jax.tree_util.tree_map(jnp.zeros_like, tables)
    return ((dxg + dxu).astype(x.dtype), dwg.astype(wg.dtype),
            dwu.astype(wu.dtype), dtables, None, None)


_glu_op.defvjp(_glu_op_fwd, _glu_op_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _down_op(x, w, sizes, tiles, bm, interpret):
    return _down_call(x, w, *tiles, bm=bm, interpret=interpret)


def _down_op_fwd(x, w, sizes, tiles, bm, interpret):
    return _down_op(x, w, sizes, tiles, bm, interpret), (x, w, sizes)


def _down_op_bwd(bm, interpret, res, g):
    x, w, sizes = res
    dx, dw = _ragged_vjp(x.astype(jnp.float32), w.astype(jnp.float32),
                         sizes, g.astype(jnp.float32))
    return dx.astype(x.dtype), dw.astype(w.dtype), None, None


_down_op.defvjp(_down_op_fwd, _down_op_bwd)


def _check_layout(x, group_sizes, bm: int):
    if x.shape[0] % bm:
        raise ValueError(f"{x.shape[0]} rows are not a multiple of the row "
                         f"tile {bm}")
    return tile_experts(group_sizes.astype(jnp.int32), x.shape[0] // bm, bm)


def fused_moe_glu(
    x: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    group_sizes: jax.Array,
    *,
    row_tile: int,
    table: PWLTable | None = None,
    act: str | None = None,
    interpret: bool | None = None,
    impl_bwd: str | None = None,
) -> jax.Array:
    """Grouped ``act(x_r @ w_gate[e]) * (x_r @ w_up[e])`` in one pass.

    x: (M, K) rows grouped by expert: expert ``e``'s ``group_sizes[e]`` rows
    follow expert ``e - 1``'s, every group size a multiple of ``row_tile``
    (pad groups with zero rows), rows past the last group are ignored and
    come back zero.  w_gate/w_up: (E, K, N).  Epilogue selection as in
    :func:`fused_glu` (table -> PWL, act -> exact, neither -> identity).
    ``impl_bwd`` as in :func:`fused_linear`.  Returns (M, N).
    """
    if interpret is None:
        interpret = should_interpret()
    plan, tables = plan_and_operands(table, act)
    tiles = _check_layout(x, group_sizes, row_tile)
    return _glu_op(x, w_gate, w_up, tables, group_sizes.astype(jnp.int32),
                   tiles, plan, row_tile, interpret,
                   resolve_impl_bwd(impl_bwd))


def moe_down(
    x: jax.Array,
    w: jax.Array,
    group_sizes: jax.Array,
    *,
    row_tile: int,
    interpret: bool | None = None,
) -> jax.Array:
    """Grouped ``x_r @ w[e]`` over the layout :func:`fused_moe_glu` takes:
    x (M, K), w (E, K, N).  Returns (M, N)."""
    if interpret is None:
        interpret = should_interpret()
    tiles = _check_layout(x, group_sizes, row_tile)
    return _down_op(x, w, group_sizes.astype(jnp.int32), tiles, row_tile,
                    interpret)
