"""Split-KV flash decoding over a paged KV cache, PWL-exp online softmax.

The flash kernel (``fused/attention.py``) serves wide *dense* decode caches,
but its KV axis is innermost-sequential: one (head, q-row) cell walks the
whole cache serially, and a single-token query gives the grid no parallel
q-axis to hide that walk behind.  Flash *decoding* (lite_llama's
``flash_decoding`` + ``softmax_split`` surface) splits the KV axis across
the grid instead: each split produces softmax *partials* and a tiny merge
combines them — same math, KV-parallel.

This kernel additionally gathers K/V **through a page table**
(``repro.serving.kv_cache`` layout: the layers' stacked pools
``(n_layers, Hkv, P, page_size, dh)`` and a layer index, table
``(B, n_pages)``), so it reads exactly the pages a request owns —
the grid is sized by the page table's *column count* (which the serving
engine buckets to the live maximum), not by the logical cache capacity:
a 500k-capacity cache holding 2k valid tokens does work proportional to
ceil(2k / page_size) pages.

Structure:

* grid ``(B * Hkv, n_splits, pages_per_split)`` — page axis innermost, so
  the f32 (m, l, acc) accumulators live in VMEM scratch across the pages
  of one split (exactly the PR-5 online-softmax chain, PWL-exp on both the
  shifted scores and the correction factor);
* grouped query heads fold into the *sublane* axis: the q tile per
  (request, kv-head) cell is ``(G, dh)`` padded to 8 sublanes, so GQA
  groups ride for free instead of multiplying the grid;
* the K/V block index maps read the scalar-prefetched layer and page
  table — ``(layer, h, page_table[b, split * pps + p], 0, 0)`` — so
  fragmented (non-contiguous) page IDs cost nothing, and the pools are
  DMA'd page by page at their own dtype (a bf16 page is cast to f32 in
  VMEM, exactly): no copy of a layer's pool is made outside the kernel;
* splits/pages past a request's valid length are skipped outright
  (no gather target is touched beyond the sentinel page, no matmul);
* per split the kernel emits ``(m, l, acc)`` partials; the cross-split
  merge (:func:`merge_split_partials`, the ``softmax_split`` analogue)
  rescales by ``PWL_exp(m_s - max_s m_s)`` and renormalizes — through the
  SAME non-uniform PWL decode as the in-split exp, so the approximation
  story is uniform end to end:

      m    = max_s m_s
      e_s  = max(PWL_exp(clamp(m_s - m)), 0)
      out  = (sum_s acc_s * e_s) / max(sum_s l_s * e_s, 1e-30)

  Empty splits contribute ``l_s = 0`` partials, so they vanish from both
  sums regardless of what the clamped PWL exp decodes to; a request with
  ``kv_len == 0`` (inactive batch slot) returns exact zeros.

Inference-only: decode steps are never differentiated, so there is no
custom VJP (the train-time attention paths keep theirs).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.pwl import PWLTable

from .._backend import should_interpret
from .epilogue import plan_and_operands
from .linear import _round_up
from .softmax import _NEG_FILL, _SHIFT_CLAMP

# target number of key positions per KV split: small enough to spread a long
# cache across the grid, large enough that each split amortizes its partial
DEFAULT_SPLIT_KEYS = 2048


def _decode_kernel(layer_ref, pt_ref, kvl_ref, *refs, plan, pps: int,
                   ps: int, scale: float, hkv: int):
    del layer_ref  # consumed by the index maps
    n_tab = plan.n_operands
    q_ref, k_ref, v_ref = refs[0], refs[1], refs[2]
    tab_refs = refs[3: 3 + n_tab]
    mo_ref, lo_ref, ao_ref = refs[3 + n_tab: 6 + n_tab]
    m_ref, l_ref, acc_ref = refs[6 + n_tab: 9 + n_tab]

    a = pl.program_id(0)   # b * Hkv + h
    s = pl.program_id(1)   # KV split
    p = pl.program_id(2)   # page within split
    gp = q_ref.shape[1]

    @pl.when(p == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, jnp.float32(_NEG_FILL))
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kvl = kvl_ref[a // hkv]
    page0 = (s * pps + p) * ps  # first key position this page covers

    @pl.when(page0 < kvl)
    def _():
        q = q_ref[0]        # (Gp, dh)
        k = k_ref[0, 0, 0].astype(jnp.float32)  # (ps, dh)
        sc = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale           # (Gp, ps)
        kpos = page0 + jax.lax.broadcasted_iota(jnp.int32, (gp, ps), 1)
        keep = kpos < kvl   # ragged tail of the last live page
        keepf = keep.astype(jnp.float32)
        sc = jnp.where(keep, sc, jnp.float32(_NEG_FILL))

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        shifted = jnp.maximum(sc - m_new, jnp.float32(_SHIFT_CLAMP))
        pr = jnp.maximum(plan.apply(shifted, *tab_refs), 0.0) * keepf
        corr = jnp.maximum(
            plan.apply(
                jnp.maximum(m_prev - m_new, jnp.float32(_SHIFT_CLAMP)),
                *tab_refs,
            ),
            0.0,
        )
        l_new = l_prev * corr + jnp.sum(pr, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            pr, v_ref[0, 0, 0].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(p == pps - 1)
    def _():
        mo_ref[0, 0] = m_ref[...]
        lo_ref[0, 0] = l_ref[...]
        ao_ref[0, 0] = acc_ref[...]


def merge_split_partials(m_p, l_p, acc_p, plan, tables):
    """``softmax_split``-style reduction of per-split (m, l, acc) partials.

    m_p/l_p: (..., n_splits, G);  acc_p: (..., n_splits, G, dh); split axis
    is -2 (resp. -3).  The rescale exp runs through the same epilogue plan
    (PWL decode or exact) as the in-split online softmax.
    """
    m_max = jnp.max(m_p, axis=-2, keepdims=True)
    e = jnp.maximum(
        plan.apply(jnp.maximum(m_p - m_max, jnp.float32(_SHIFT_CLAMP)),
                   *tables),
        0.0,
    )
    l = jnp.sum(l_p * e, axis=-2)
    acc = jnp.sum(acc_p * e[..., None], axis=-3)
    return acc / jnp.maximum(l[..., None], jnp.float32(1e-30))


@functools.partial(jax.jit, static_argnames=(
    "plan", "g", "pps", "interpret"))
def _paged_decode(q, k_pages, v_pages, layer, page_table, kv_len, tables, *,
                  plan, g, pps, interpret):
    """q: (B*Hkv, Gp, dh) f32;  pools: (n_layers, Hkv, P, ps, dh) at their
    own dtype;  layer: (1,) i32;  page_table: (B, n_cols) i32 padded to a
    multiple of pps;  kv_len: (B,) i32.  Returns (B*Hkv, Gp, dh) f32."""
    A, gp, dh = q.shape
    _, Hkv, P, ps, _ = k_pages.shape
    n_splits = page_table.shape[1] // pps
    grid = (A, n_splits, pps)
    scale = 1.0 / math.sqrt(dh)

    def page_map(a, s, p, ly, pt, kvl):
        return (ly[0], a % Hkv, pt[a // Hkv, s * pps + p], 0, 0)

    def split_map(a, s, p, ly, pt, kvl):
        return (a, s, 0, 0)

    page_spec = pl.BlockSpec((1, 1, 1, ps, dh), page_map)
    in_specs = [
        pl.BlockSpec((1, gp, dh), lambda a, s, p, ly, pt, kvl: (a, 0, 0)),
        page_spec,
        page_spec,
    ]
    for rows, cols in plan.table_specs():
        in_specs.append(
            pl.BlockSpec((rows, cols), lambda a, s, p, ly, pt, kvl: (0, 0))
        )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, gp, 128), split_map),
            pl.BlockSpec((1, 1, gp, 128), split_map),
            pl.BlockSpec((1, 1, gp, dh), split_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((gp, 128), jnp.float32),  # running row max
            pltpu.VMEM((gp, 128), jnp.float32),  # running row sum
            pltpu.VMEM((gp, dh), jnp.float32),   # output accumulator
        ],
    )
    m_p, l_p, acc_p = pl.pallas_call(
        functools.partial(_decode_kernel, plan=plan, pps=pps, ps=ps,
                          scale=scale, hkv=Hkv),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((A, n_splits, gp, 128), jnp.float32),
            jax.ShapeDtypeStruct((A, n_splits, gp, 128), jnp.float32),
            jax.ShapeDtypeStruct((A, n_splits, gp, dh), jnp.float32),
        ],
        interpret=interpret,
    )(layer, page_table, kv_len, q, k_pages, v_pages, *tables)
    # (A, ns, Gp, 128) -> (A, ns, Gp): partials are lane-broadcast
    return merge_split_partials(m_p[..., 0], l_p[..., 0], acc_p, plan, tables)


def paged_flash_decode(
    q: jax.Array,           # (B, 1, H, dh) — single-token decode queries
    k_pages: jax.Array,     # (n_layers, Hkv, P, page_size, dh)
    v_pages: jax.Array,     # (n_layers, Hkv, P, page_size, dh)
    page_table: jax.Array,  # (B, n_pages) int32 (0 = sentinel/unallocated)
    kv_len: jax.Array,      # (B,) int32 valid prefix length (0 = inactive)
    layer,                  # int or int32 scalar: which layer's pool
    *,
    table: PWLTable | None = None,
    act: str | None = None,
    pages_per_split: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Split-KV flash decoding through a page table (see module docstring),
    over layer ``layer`` of the stacked pools, read at their own dtype.

    table: PWL exp table (the ``attn.softmax:exp`` site); ``act="exp"``
    (default when neither is given) runs the exact exponential through the
    identical split/merge datapath.  ``pages_per_split`` defaults to
    ``DEFAULT_SPLIT_KEYS / page_size`` keys per split.  Requests with
    ``kv_len == 0`` return zeros.  Returns (B, 1, H, dh) in ``q.dtype``.
    """
    if interpret is None:
        interpret = should_interpret()
    if table is None and act is None:
        act = "exp"
    plan, tables = plan_and_operands(table, act)

    B, S, H, dh = q.shape
    if S != 1:
        raise ValueError(f"paged_flash_decode takes single-token queries, got S={S}")
    _, Hkv, P, ps, _ = k_pages.shape
    G = H // Hkv
    gp = _round_up(G, 8)
    pps = pages_per_split or max(1, DEFAULT_SPLIT_KEYS // ps)
    pps = min(pps, max(1, page_table.shape[1]))

    # pad table columns to a whole number of splits (sentinel page 0 —
    # the padded cells are skipped, position >= kv_len always)
    n_cols = _round_up(page_table.shape[1], pps)
    pt = jnp.pad(page_table.astype(jnp.int32),
                 ((0, 0), (0, n_cols - page_table.shape[1])))

    # (B, 1, H, dh) -> (B*Hkv, Gp, dh): GQA group folds into sublanes
    qf = (q.astype(jnp.float32).reshape(B, Hkv, G, dh)
          .reshape(B * Hkv, G, dh))
    qf = jnp.pad(qf, ((0, 0), (0, gp - G), (0, 0)))

    out = _paged_decode(
        qf, k_pages, v_pages, jnp.asarray(layer, jnp.int32).reshape(1), pt,
        kv_len.astype(jnp.int32), tables, plan=plan, g=G, pps=pps,
        interpret=interpret,
    )
    return out[:, :G].reshape(B, 1, H, dh).astype(q.dtype)
