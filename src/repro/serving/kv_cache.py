"""Paged/block KV cache: a fixed pool of page-sized KV blocks plus a
per-request page table (the vLLM layout, lite_llama's ``update_kv_buffer``
surface re-expressed in Pallas).

Why paged: the dense serving cache allocates ``(B, max_len, Hkv, dh)`` per
request up front — a 500k-slot cache holding 2k live tokens wastes 250x its
working set and pins the batch to one worst-case length.  Here every layer
owns a pool of ``num_pages`` pages of ``page_size`` token slots,

    k_pages, v_pages : (Hkv, num_pages, page_size, head_dim)

(head-major so each kernel tile is a natural ``(page_size, head_dim)``
sublane x lane block), and a request maps logical token position ``t`` to
physical slot ``(page_table[r, t // page_size], t % page_size)``.  Pages are
allocated on demand and recycled on eviction, so cache memory scales with
*live* tokens and requests of wildly different lengths share one pool.

The page table is host-owned (``PageAllocator`` — a plain free-list; the
scheduler decides admission/eviction between device steps) and enters
jitted code as an ordinary int32 operand.  **Page 0 is reserved as a
sentinel**: unallocated table entries are 0, so inactive batch slots write
into (and skipped grid cells gather from) a page that is never handed out —
no masked scatter needed anywhere.

Writes are in-place Pallas kernels (``input_output_aliases`` pins the
output pool to the input pool buffer, so decode-step appends never
re-materialize the cache):

* :func:`write_prompt_pages` — prefill: grid ``(B, Hkv, S/page_size)``,
  each step copies one full page of fresh K/V into the pool page the
  (scalar-prefetched) page table names.  Full-block writes, no read-back.
* :func:`append_kv` — decode: grid ``(B, Hkv)``, each step read-modify-
  writes ONE page as a whole tile: the resident page with row
  ``kv_len % ps`` replaced by the new token's K/V.  One page per (request,
  head) per step is the whole write traffic.

Validity is always a *position* prefix (``kv_len`` per request) even when
the page IDs are fragmented — fragmentation lives entirely in the table's
value space, which is what keeps the flash/decoding kernels' prefix-mask
logic (PR 5) valid unchanged.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.distributed import shard_fused as shf
from repro.distributed.sharding import active_mesh_rules
from repro.kernels._backend import should_interpret

from .resilience import PagePoolExhausted

# page 0 is the sentinel: never allocated, target of every unallocated
# page-table entry (inactive slots append here; skipped splits gather here)
SENTINEL_PAGE = 0


def make_page_pool(num_pages: int, page_size: int, n_kv_heads: int,
                   head_dim: int, dtype) -> jax.Array:
    """One layer's K (or V) pool: (Hkv, num_pages, page_size, head_dim)."""
    if num_pages < 2:
        raise ValueError("num_pages must be >= 2 (page 0 is the sentinel)")
    return jnp.zeros((n_kv_heads, num_pages, page_size, head_dim), dtype)


# ---------------------------------------------------------------------------
# host-side page accounting


@dataclasses.dataclass
class PageAllocator:
    """Free-list page allocator (host side, plain python).

    LIFO recycling is deliberate: freed pages are reused immediately, so a
    realistic admit/evict workload produces *fragmented* (non-contiguous,
    non-monotone) page tables — the case the parity tests pin.

    ``faults`` optionally holds a :class:`repro.serving.faults.FaultInjector`
    whose armed ``alloc_exhaust`` specs make :meth:`alloc` raise even with
    free pages — the deterministic trigger for the engine's preemption path.
    Exhaustion (real or injected) raises the typed
    :class:`~repro.serving.resilience.PagePoolExhausted` (a ``RuntimeError``
    subclass, message unchanged).
    """

    num_pages: int
    faults: object = None

    def __post_init__(self):
        # page 0 reserved as the sentinel
        self._free = list(range(self.num_pages - 1, SENTINEL_PAGE, -1))

    @property
    def num_free(self) -> int:
        return len(self._free)

    def alloc(self, n: int, scope: str = "") -> list[int]:
        if n == 0:
            return []
        if self.faults is not None and self.faults.alloc_should_fail(scope):
            raise PagePoolExhausted(
                f"page pool exhausted (injected fault, scope={scope or 'any'}):"
                f" asked {n}, {len(self._free)} free of {self.num_pages}"
            )
        if n > len(self._free):
            raise PagePoolExhausted(
                f"page pool exhausted: asked {n}, {len(self._free)} free of "
                f"{self.num_pages} (admission control should prevent this)"
            )
        pages = self._free[-n:][::-1]
        self._free = self._free[:-n]
        return pages

    def free(self, pages: list[int]) -> None:
        for p in pages:
            if p == SENTINEL_PAGE:
                raise ValueError("attempt to free the sentinel page")
            self._free.append(p)


# ---------------------------------------------------------------------------
# in-place write kernels


def _prompt_write_kernel(pt_ref, nk_ref, nv_ref, kin_ref, vin_ref,
                         ko_ref, vo_ref):
    del pt_ref, kin_ref, vin_ref  # table is consumed by the index maps only
    ko_ref[...] = nk_ref[...]
    vo_ref[...] = nv_ref[...]


def write_prompt_pages(k_pages, v_pages, k_new, v_new, page_table, *,
                       interpret: bool | None = None):
    """Write a fresh prompt's K/V into the pool pages the table names.

    k_new/v_new: (B, S, Hkv, dh) with ``S % page_size == 0`` (prompts are
    bucketed by the engine); token ``s`` of request ``b`` lands in page
    ``page_table[b, s // page_size]`` slot ``s % page_size``.  Pages are
    written whole (prefill always starts at position 0 of a fresh request),
    so the kernel never reads the pool.  Returns the updated (aliased)
    pools.

    Under a multi-device mesh the write kernel runs per-shard: pools shard
    over KV heads (the "cache_kv" axis), batch stays replicated so every
    data rank applies ALL requests' writes — pool replicas over the data
    axes never diverge.
    """
    rules = active_mesh_rules()
    if rules is not None:
        hk = shf.dim_entry(rules, "cache_kv", k_pages.shape[0])
        pool = shf.P(hk, None, None, None)
        new = shf.P(None, None, hk, None)

        def body(kp, vp, kn, vn, pt):
            return _write_prompt_pages(kp, vp, kn, vn, pt,
                                       interpret=interpret)

        return shf.run_sharded(
            rules, body, (k_pages, v_pages, k_new, v_new, page_table),
            (pool, pool, new, new, shf.P(None, None)), (pool, pool),
        )
    return _write_prompt_pages(k_pages, v_pages, k_new, v_new, page_table,
                               interpret=interpret)


def _write_prompt_pages(k_pages, v_pages, k_new, v_new, page_table, *,
                        interpret: bool | None = None):
    if interpret is None:
        interpret = should_interpret()
    Hkv, P, ps, dh = k_pages.shape
    B, S = k_new.shape[0], k_new.shape[1]
    if S % ps:
        raise ValueError(f"prompt length {S} not a multiple of page_size {ps}")
    npg = S // ps
    if page_table.shape[1] < npg:
        raise ValueError("page table too narrow for this prompt")
    pt = page_table[:, :npg].astype(jnp.int32)
    # (B, S, Hkv, dh) -> (B, Hkv, S, dh): tiles become (page_size, head_dim)
    nk = k_new.astype(k_pages.dtype).transpose(0, 2, 1, 3)
    nv = v_new.astype(v_pages.dtype).transpose(0, 2, 1, 3)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, npg),
        in_specs=[
            pl.BlockSpec((1, 1, ps, dh), lambda b, h, j, pt: (b, h, j, 0)),
            pl.BlockSpec((1, 1, ps, dh), lambda b, h, j, pt: (b, h, j, 0)),
            pl.BlockSpec((1, 1, ps, dh), lambda b, h, j, pt: (h, pt[b, j], 0, 0)),
            pl.BlockSpec((1, 1, ps, dh), lambda b, h, j, pt: (h, pt[b, j], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, ps, dh), lambda b, h, j, pt: (h, pt[b, j], 0, 0)),
            pl.BlockSpec((1, 1, ps, dh), lambda b, h, j, pt: (h, pt[b, j], 0, 0)),
        ],
    )
    return pl.pallas_call(
        _prompt_write_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ],
        input_output_aliases={3: 0, 4: 1},  # pools update in place
        interpret=interpret,
    )(pt, nk, nv, k_pages, v_pages)


def _append_kernel(pidx_ref, slot_ref, nk_ref, nv_ref, kin_ref, vin_ref,
                   ko_ref, vo_ref):
    del pidx_ref  # consumed by the index maps
    s = slot_ref[pl.program_id(0)]
    # read-modify-write the one resident page as a whole tile: select the new
    # row over the page by iota.  A one-row store at a dynamic slot is not
    # sublane-aligned, and Mosaic refuses it.
    rows = jax.lax.broadcasted_iota(jnp.int32, ko_ref.shape[2:], 0)
    ko_ref[0, 0] = jnp.where(rows == s, nk_ref[0, 0], kin_ref[0, 0])
    vo_ref[0, 0] = jnp.where(rows == s, nv_ref[0, 0], vin_ref[0, 0])


def append_kv(k_pages, v_pages, k_new, v_new, page_table, kv_len, *,
              interpret: bool | None = None):
    """Append one decode-step token's K/V per request, in place.

    k_new/v_new: (B, 1, Hkv, dh); ``kv_len``: (B,) current valid length —
    the new token lands at logical position ``kv_len[b]``, i.e. page
    ``page_table[b, kv_len // ps]`` slot ``kv_len % ps``.  Inactive slots
    (all-zero table rows) write harmlessly into the sentinel page.

    Under a multi-device mesh the append runs per-shard with the same
    layout as :func:`write_prompt_pages`: pools over KV heads, batch
    replicated (every data rank appends all requests' tokens, keeping pool
    replicas identical).
    """
    rules = active_mesh_rules()
    if rules is not None:
        hk = shf.dim_entry(rules, "cache_kv", k_pages.shape[0])
        pool = shf.P(hk, None, None, None)
        new = shf.P(None, None, hk, None)

        def body(kp, vp, kn, vn, pt, kl):
            return _append_kv(kp, vp, kn, vn, pt, kl, interpret=interpret)

        return shf.run_sharded(
            rules, body, (k_pages, v_pages, k_new, v_new, page_table, kv_len),
            (pool, pool, new, new, shf.P(None, None), shf.P(None)),
            (pool, pool),
        )
    return _append_kv(k_pages, v_pages, k_new, v_new, page_table, kv_len,
                      interpret=interpret)


def _append_kv(k_pages, v_pages, k_new, v_new, page_table, kv_len, *,
               interpret: bool | None = None):
    if interpret is None:
        interpret = should_interpret()
    Hkv, P, ps, dh = k_pages.shape
    B = k_new.shape[0]
    kv_len = kv_len.astype(jnp.int32)
    pidx = jnp.take_along_axis(
        page_table.astype(jnp.int32), (kv_len // ps)[:, None], axis=1
    )[:, 0]
    slot = kv_len % ps
    nk = k_new.astype(k_pages.dtype).transpose(0, 2, 1, 3)  # (B, Hkv, 1, dh)
    nv = v_new.astype(v_pages.dtype).transpose(0, 2, 1, 3)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv),
        in_specs=[
            pl.BlockSpec((1, 1, 1, dh), lambda b, h, pidx, slot: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, dh), lambda b, h, pidx, slot: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, ps, dh), lambda b, h, pidx, slot: (h, pidx[b], 0, 0)),
            pl.BlockSpec((1, 1, ps, dh), lambda b, h, pidx, slot: (h, pidx[b], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, ps, dh), lambda b, h, pidx, slot: (h, pidx[b], 0, 0)),
            pl.BlockSpec((1, 1, ps, dh), lambda b, h, pidx, slot: (h, pidx[b], 0, 0)),
        ],
    )
    return pl.pallas_call(
        _append_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ],
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
    )(pidx, slot, nk, nv, k_pages, v_pages)


# ---------------------------------------------------------------------------
# dense view (fallback path + parity oracle)


def gather_pages(pages, page_table):
    """Materialize the dense per-request cache a page table describes.

    pages: (Hkv, P, ps, dh);  page_table: (B, n_pages) int32.  Returns
    (B, n_pages * ps, Hkv, dh) — logical position order, whatever the
    physical page IDs.  This is the unfused fallback (plans without a fused
    softmax site) and the parity oracle for the split-KV decode kernel; the
    fused path never materializes it.
    """
    Hkv, P, ps, dh = pages.shape
    B, npg = page_table.shape
    g = pages[:, page_table]  # (Hkv, B, npg, ps, dh)
    return g.transpose(1, 2, 3, 0, 4).reshape(B, npg * ps, Hkv, dh)
