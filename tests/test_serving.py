"""repro.serving: paged KV cache, split-KV decoding, continuous batching
(ISSUE 6).

Covers the acceptance criteria: the in-place page-write kernels round-trip
exactly against a dense reference over fragmented page tables; the
split-KV flash-decoding kernel matches the dense-cache reference to
flash-kernel tolerances across GQA/ragged/page-size {16, 128} cases and is
invariant to the split count and to physical page placement (bitwise); a
paged generation session reproduces dense-cache greedy decoding token for
token; an eviction-then-readmit round trip produces identical logits; and
a full continuous-batching session on the fused plan runs with ZERO
``warn_fused_fallback`` hits.
"""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro  # noqa: F401
from repro import sfu
from repro.configs import get_reduced_config
from repro.kernels import fused
from repro.models import Model, layers
from repro.serving import (
    GenRequest,
    PageAllocator,
    PagedServingEngine,
    append_kv,
    gather_pages,
    make_page_pool,
    write_prompt_pages,
)

# kernel-vs-dense-PWL-softmax bounds.  Not pure chaining error (that is
# pinned at 1e-5 by the exact-exp test): PWL exp does not factorize
# (pwl(a+b) != pwl(a)*pwl(b)), so the online correction-factor chain
# differs from the one-shot dense PWL softmax by the table's own
# approximation error — ~5e-4 for the 32-breakpoint f32 exp table.
BOUNDS = {"f32": 2e-3, "bf16": 0.08, "f16": 0.02}


def _table(dtype="f32", n_bp=32):
    return sfu.get_store().get(fn="exp", n_breakpoints=n_bp, dtype=dtype)


@pytest.fixture(autouse=True)
def _fresh_fallback_state():
    sfu.reset_fused_fallback_warnings()
    yield
    sfu.reset_fused_fallback_warnings()


def _fragmented_table(alloc: PageAllocator, n_requests: int, pages_each: int):
    """Interleave allocations across requests so page IDs are
    non-contiguous and non-monotone per row."""
    rows = [[] for _ in range(n_requests)]
    for _ in range(pages_each):
        for r in range(n_requests):
            rows[r].extend(alloc.alloc(1))
    return np.asarray(rows, np.int32)


def _dense_decode_ref(q, k, v, kv_len, exp_fn=np.exp):
    """Single-token GQA attention over a ragged dense cache, with a
    pluggable softmax exp (the PWL closure for table cases, so the bound
    measures kernel-vs-reference chaining error, not the table's
    approximation error against true exp)."""
    B, _, H, dh = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    qr = np.asarray(q, np.float64).reshape(B, Hkv, G, dh)
    kr = np.asarray(k, np.float64).transpose(0, 2, 1, 3)
    vr = np.asarray(v, np.float64).transpose(0, 2, 1, 3)
    sc = np.einsum("bhgd,bhtd->bhgt", qr, kr) / np.sqrt(dh)
    T = k.shape[1]
    mask = np.arange(T)[None, :] < np.asarray(kv_len)[:, None]
    sc = np.where(mask[:, None, None, :], sc, -1e30)
    sc = sc - sc.max(-1, keepdims=True)
    p = np.asarray(exp_fn(jnp.asarray(sc, jnp.float32)), np.float64)
    p = np.where(mask[:, None, None, :], p, 0.0)
    denom = p.sum(-1, keepdims=True)
    p = np.where(denom > 0, p / np.maximum(denom, 1e-300), 0.0)
    out = np.einsum("bhgt,bhtd->bhgd", p, vr)
    return out.reshape(B, 1, H, dh).astype(np.float32)


# ---------------------------------------------------------------------------
# page pool + write kernels


class TestPageAllocator:
    def test_lifo_reuse_fragments(self):
        a = PageAllocator(8)
        first = a.alloc(3)
        a.free(first[:2])
        again = a.alloc(2)
        assert set(again) == set(first[:2])  # recycled, not fresh
        assert a.num_free == 8 - 1 - 3      # sentinel + 3 held

    def test_exhaustion_raises(self):
        a = PageAllocator(4)
        a.alloc(3)
        with pytest.raises(RuntimeError, match="exhausted"):
            a.alloc(1)

    def test_sentinel_never_allocated_or_freed(self):
        a = PageAllocator(4)
        assert 0 not in a.alloc(3)
        with pytest.raises(ValueError):
            a.free([0])


class TestWriteKernels:
    @pytest.mark.parametrize("ps", [16, 128])
    def test_prompt_write_roundtrip_fragmented(self, ps):
        B, Hkv, dh, npg = 2, 2, 16, 2
        pool = 2 * B * npg + 1
        kp = make_page_pool(pool, ps, Hkv, dh, jnp.float32)[None]
        vp = make_page_pool(pool, ps, Hkv, dh, jnp.float32)[None]
        pt = jnp.asarray(_fragmented_table(PageAllocator(pool), B, npg))
        S = npg * ps
        k1, k2 = jax.random.split(jax.random.PRNGKey(0))
        kn = jax.random.normal(k1, (B, S, Hkv, dh))
        vn = jax.random.normal(k2, (B, S, Hkv, dh))
        kp, vp = write_prompt_pages(kp, vp, kn, vn, pt, 0)
        np.testing.assert_array_equal(np.asarray(gather_pages(kp[0], pt)), kn)
        np.testing.assert_array_equal(np.asarray(gather_pages(vp[0], pt)), vn)

    def test_append_crosses_page_boundary(self):
        B, Hkv, dh, ps = 2, 2, 8, 8
        kp = make_page_pool(8, ps, Hkv, dh, jnp.float32)[None]
        vp = make_page_pool(8, ps, Hkv, dh, jnp.float32)[None]
        alloc = PageAllocator(8)
        pt = np.zeros((B, 2), np.int32)
        pt[:, 0] = alloc.alloc(B)
        ref_k = np.zeros((B, 2 * ps, Hkv, dh), np.float32)
        kv_len = np.array([ps - 1, 3], np.int32)  # row 0 one short of a page
        for step in range(4):
            for b in range(B):
                if kv_len[b] % ps == 0 and pt[b, kv_len[b] // ps] == 0:
                    pt[b, kv_len[b] // ps] = alloc.alloc(1)[0]
            kn = jax.random.normal(jax.random.PRNGKey(step), (B, 1, Hkv, dh))
            kp, vp = append_kv(kp, vp, kn, kn, jnp.asarray(pt),
                               jnp.asarray(kv_len), 0)
            for b in range(B):
                ref_k[b, kv_len[b]] = np.asarray(kn[b, 0])
            kv_len += 1
        got = np.asarray(gather_pages(kp[0], jnp.asarray(pt)))
        for b in range(B):
            np.testing.assert_array_equal(got[b, : kv_len[b]],
                                          ref_k[b, : kv_len[b]])

    def test_append_preserves_other_pages(self):
        """input_output_aliases semantics: pages not visited by the grid
        keep their contents across an in-place append."""
        Hkv, dh, ps = 2, 8, 8
        kp = make_page_pool(6, ps, Hkv, dh, jnp.float32)[None]
        kp = kp + jax.random.normal(jax.random.PRNGKey(7), kp.shape)
        before = np.asarray(kp)[0]
        pt = jnp.asarray([[3, 0]], jnp.int32)
        kn = jnp.ones((1, 1, Hkv, dh))
        kp2, _ = append_kv(kp, kp, kn, kn, pt, jnp.asarray([2], jnp.int32), 0)
        after = np.asarray(kp2)[0]
        untouched = [p for p in range(6) if p != 3]
        np.testing.assert_array_equal(after[:, untouched], before[:, untouched])
        np.testing.assert_array_equal(after[:, 3, 2], np.ones((Hkv, dh)))


# ---------------------------------------------------------------------------
# split-KV flash decoding kernel


class TestPagedFlashDecode:
    @pytest.mark.parametrize("ps", [16, 128])
    @pytest.mark.parametrize("dtype", ["f32", "bf16"])
    def test_matches_dense_ref_gqa_ragged(self, ps, dtype):
        B, H, Hkv, dh, npg = 3, 4, 2, 16, 3
        pool = B * npg + 1
        pt = jnp.asarray(_fragmented_table(PageAllocator(pool), B, npg))
        kp = jax.random.normal(jax.random.PRNGKey(1), (1, Hkv, pool, ps, dh))
        vp = jax.random.normal(jax.random.PRNGKey(2), (1, Hkv, pool, ps, dh))
        q = jax.random.normal(jax.random.PRNGKey(3), (B, 1, H, dh))
        # ragged: full, mid-page, and single-token requests
        kv_len = jnp.asarray([npg * ps, ps + 3, 1], jnp.int32)
        table = _table(dtype)
        out = fused.paged_flash_decode(q, kp, vp, pt, kv_len, 0, table=table)
        ref = _dense_decode_ref(q, gather_pages(kp[0], pt),
                                gather_pages(vp[0], pt), kv_len,
                                exp_fn=layers.pwl_exp_fn(table))
        assert np.abs(np.asarray(out) - ref).max() < BOUNDS[dtype]

    def test_exact_exp_tight_parity(self):
        B, H, Hkv, dh, ps, npg = 2, 4, 4, 32, 16, 4
        pool = B * npg + 1
        pt = jnp.asarray(_fragmented_table(PageAllocator(pool), B, npg))
        kp = jax.random.normal(jax.random.PRNGKey(4), (1, Hkv, pool, ps, dh))
        vp = jax.random.normal(jax.random.PRNGKey(5), (1, Hkv, pool, ps, dh))
        q = jax.random.normal(jax.random.PRNGKey(6), (B, 1, H, dh))
        kv_len = jnp.asarray([npg * ps, 2 * ps - 5], jnp.int32)
        out = fused.paged_flash_decode(q, kp, vp, pt, kv_len, 0, act="exp")
        ref = _dense_decode_ref(q, gather_pages(kp[0], pt),
                                gather_pages(vp[0], pt), kv_len)
        assert np.abs(np.asarray(out) - ref).max() < 1e-5

    def test_split_count_invariance(self):
        B, H, Hkv, dh, ps, npg = 2, 4, 2, 16, 16, 4
        pool = B * npg + 1
        pt = jnp.asarray(_fragmented_table(PageAllocator(pool), B, npg))
        kp = jax.random.normal(jax.random.PRNGKey(8), (1, Hkv, pool, ps, dh))
        vp = jax.random.normal(jax.random.PRNGKey(9), (1, Hkv, pool, ps, dh))
        q = jax.random.normal(jax.random.PRNGKey(10), (B, 1, H, dh))
        kv_len = jnp.asarray([npg * ps - 7, 9], jnp.int32)
        # exact exp: split count only reassociates f32 math -> tight bound
        outs = [
            np.asarray(fused.paged_flash_decode(
                q, kp, vp, pt, kv_len, 0, act="exp", pages_per_split=pps))
            for pps in (1, 2, 4)
        ]
        for o in outs[1:]:
            np.testing.assert_allclose(o, outs[0], atol=2e-6)
        # PWL exp: split boundaries move which positions chain through
        # correction factors vs the merge rescale -> table-error bound
        touts = [
            np.asarray(fused.paged_flash_decode(
                q, kp, vp, pt, kv_len, 0, table=_table(),
                pages_per_split=pps))
            for pps in (1, 4)
        ]
        np.testing.assert_allclose(touts[1], touts[0], atol=BOUNDS["f32"])

    def test_physical_placement_invariance_bitwise(self):
        """Moving pages to different physical slots (and updating the table)
        cannot change anything — the kernel walks logical order."""
        B, H, Hkv, dh, ps, npg = 2, 2, 2, 16, 16, 2
        pool = 2 * B * npg + 1
        pt = _fragmented_table(PageAllocator(pool), B, npg)
        kp = jax.random.normal(jax.random.PRNGKey(11), (1, Hkv, pool, ps, dh))
        vp = jax.random.normal(jax.random.PRNGKey(12), (1, Hkv, pool, ps, dh))
        q = jax.random.normal(jax.random.PRNGKey(13), (B, 1, H, dh))
        kv_len = jnp.asarray([npg * ps, ps + 1], jnp.int32)
        out1 = fused.paged_flash_decode(q, kp, vp, jnp.asarray(pt), kv_len,
                                        0, table=_table())
        # relocate every used page to a fresh physical slot
        perm = {old: new for old, new in
                zip(sorted(pt.ravel()), range(pool - 1, pool - 1 - pt.size, -1))}
        kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
        for old, new in perm.items():
            kp2[:, :, new] = kp2[:, :, old]
            vp2[:, :, new] = vp2[:, :, old]
        pt2 = np.vectorize(perm.get)(pt).astype(np.int32)
        out2 = fused.paged_flash_decode(q, jnp.asarray(kp2), jnp.asarray(vp2),
                                        jnp.asarray(pt2), kv_len, 0,
                                        table=_table())
        np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))

    def test_inactive_request_returns_zeros(self):
        Hkv, dh, ps = 2, 16, 16
        kp = jax.random.normal(jax.random.PRNGKey(14), (1, Hkv, 3, ps, dh))
        q = jax.random.normal(jax.random.PRNGKey(15), (1, 1, 2, dh))
        pt = jnp.zeros((1, 2), jnp.int32)
        out = fused.paged_flash_decode(q, kp, kp, pt, jnp.asarray([0]), 0,
                                       table=_table())
        np.testing.assert_array_equal(np.asarray(out), 0.0)


# ---------------------------------------------------------------------------
# the layers' stacked pools: every kernel addresses one layer in place


def _stacked_case(seed=0, L=3, B=2, H=4, Hkv=2, dh=16, ps=16, npg=2):
    """bf16 stacked pools (L, Hkv, P, ps, dh) of random contents, a
    fragmented table, queries, fresh prompt K/V and ragged lengths."""
    pool = 2 * B * npg + 1
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    shape = (L, Hkv, pool, ps, dh)
    return dict(
        kp=jax.random.normal(ks[0], shape).astype(jnp.bfloat16),
        vp=jax.random.normal(ks[1], shape).astype(jnp.bfloat16),
        pt=jnp.asarray(_fragmented_table(PageAllocator(pool), B, npg)),
        q=jax.random.normal(ks[2], (B, 1, H, dh)),
        kn=jax.random.normal(ks[3], (B, npg * ps, Hkv, dh)),
        vn=jax.random.normal(ks[4], (B, npg * ps, Hkv, dh)),
        kv_len=jnp.asarray([npg * ps - 1, ps + 3], jnp.int32)[:B],
    )


def _softmax_kw(softmax):
    return {"act": "exp"} if softmax == "exp" else {"table": _table()}


def _expected_writes(c, layer):
    """The stacks after write_prompt_pages then append_kv at ``layer``,
    written page by page in numpy: every other layer and page unchanged."""
    kp = np.array(c["kp"].astype(jnp.float32))
    vp = np.array(c["vp"].astype(jnp.float32))
    pt = np.asarray(c["pt"])
    ps = kp.shape[3]
    kn = np.asarray(c["kn"].astype(jnp.bfloat16).astype(jnp.float32))
    vn = np.asarray(c["vn"].astype(jnp.bfloat16).astype(jnp.float32))
    for b in range(pt.shape[0]):
        for j in range(pt.shape[1]):
            rows = slice(j * ps, (j + 1) * ps)
            kp[layer, :, pt[b, j]] = kn[b, rows].transpose(1, 0, 2)
            vp[layer, :, pt[b, j]] = vn[b, rows].transpose(1, 0, 2)
        t = int(c["kv_len"][b])
        kp[layer, :, pt[b, t // ps], t % ps] = -kn[b, 0]
        vp[layer, :, pt[b, t // ps], t % ps] = -vn[b, 0]
    return kp, vp


def _write_both(c, layer):
    """write_prompt_pages then append_kv (the prompt's first token's K/V,
    negated, at position kv_len) at ``layer``, jitted, layer traced."""
    def run(kp, vp, ly):
        kp, vp = write_prompt_pages(kp, vp, c["kn"], c["vn"], c["pt"], ly)
        return append_kv(kp, vp, -c["kn"][:, :1], -c["vn"][:, :1], c["pt"],
                         c["kv_len"], ly)
    return jax.jit(run)(c["kp"], c["vp"], layer)


class TestStackedPools:
    @pytest.mark.parametrize("layer", [0, 2])
    @pytest.mark.parametrize("softmax", ["exp", "pwl"])
    def test_decode_at_layer_bitwise_equals_f32_slice(self, softmax, layer):
        """The stacked bf16 pool read at ``layer`` (a traced index) gives
        the very numbers of that layer's pool cast to f32 before the
        kernel, the formulation the kernel had before it took the stack."""
        c = _stacked_case()
        kw = _softmax_kw(softmax)

        def decode(kp, vp, ly):
            return fused.paged_flash_decode(c["q"], kp, vp, c["pt"],
                                            c["kv_len"], ly, **kw)

        got = jax.jit(decode)(c["kp"], c["vp"], layer)
        sl = slice(layer, layer + 1)
        ref = jax.jit(decode)(c["kp"][sl].astype(jnp.float32),
                              c["vp"][sl].astype(jnp.float32), 0)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        assert np.abs(np.asarray(got)).max() > 0

    @pytest.mark.parametrize("layer", [0, 2])
    def test_writes_change_only_their_layer(self, layer):
        c = _stacked_case()
        kp, vp = _write_both(c, layer)
        assert kp.dtype == vp.dtype == jnp.bfloat16
        exp_k, exp_v = _expected_writes(c, layer)
        np.testing.assert_array_equal(np.asarray(kp.astype(jnp.float32)),
                                      exp_k)
        np.testing.assert_array_equal(np.asarray(vp.astype(jnp.float32)),
                                      exp_v)

    @pytest.mark.mesh
    def test_2x2_mesh_matches_single_device(self):
        """On a 2x2 (data x model) mesh the pool kernels run per shard with
        the layer axis unsharded: the writes equal the single-device ones
        and the per-shard split-KV decode (PWL table) and the gather
        fallback (exact exp) at a traced layer equal the per-layer f32
        slice, bitwise."""
        from mesh_utils import run_py

        r = run_py("""
            import sys
            sys.path.insert(0, "tests")
            import jax, jax.numpy as jnp, numpy as np
            import repro
            from repro.configs import get_reduced_config
            from repro.distributed.sharding import make_rules, use_rules
            from repro.launch.mesh import make_host_mesh
            from repro.models import layers
            from test_serving import _stacked_case, _table, _write_both

            cfg = get_reduced_config("repro-100m", force_dp_only=False)
            rules = make_rules(cfg, make_host_mesh(model=2))
            assert rules.table["cache_kv"] == "model", rules.table
            c = _stacked_case(seed=1)
            layer = 1

            def decode(kp, vp, ly, table):
                return layers.paged_decode_attention(
                    c["q"], kp, vp, c["pt"], c["kv_len"], ly,
                    softmax_table=table)

            ref_w = _write_both(c, layer)
            for table in (_table(), None):
                f = jax.jit(lambda kp, vp, ly: decode(kp, vp, ly, table))
                sl = slice(layer, layer + 1)
                ref = f(c["kp"][sl].astype(jnp.float32),
                        c["vp"][sl].astype(jnp.float32), 0)
                with use_rules(rules):
                    got = jax.jit(
                        lambda kp, vp, ly: decode(kp, vp, ly, table))(
                            c["kp"], c["vp"], layer)
                np.testing.assert_array_equal(np.asarray(got),
                                              np.asarray(ref))
            with use_rules(rules):
                got_w = _write_both(c, layer)
            for g, w in zip(got_w, ref_w):
                np.testing.assert_array_equal(
                    np.asarray(g.astype(jnp.float32)),
                    np.asarray(w.astype(jnp.float32)))
            print("OK")
        """, devices=4)
        assert r.returncode == 0, r.stderr[-3000:]
        assert "OK" in r.stdout


def _walk_eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in its params."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _walk_eqns(inner)


class TestPoolsRideTheScanCarry:
    @pytest.mark.parametrize("step", ["decode_step_paged", "prefill_paged"])
    def test_pools_in_carry_never_sliced_or_cast(self, step):
        """The layer scan of the paged steps carries the stacked pools (the
        step's own pool inputs, uncopied), slices no layer's pool out as
        xs, stacks none back as ys, and no convert_element_type anywhere
        takes a pool-shaped operand."""
        cfg = _cfg()
        model = Model(cfg)
        params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
        pages, ps, B = 9, 16, 2
        cache = jax.eval_shape(lambda: model.make_paged_cache(pages, ps))
        i32 = jnp.int32
        if step == "decode_step_paged":
            args = (jax.ShapeDtypeStruct((B, 1), i32), cache,
                    jax.ShapeDtypeStruct((B, 4), i32),
                    jax.ShapeDtypeStruct((B,), i32))
        else:
            args = (jax.ShapeDtypeStruct((1, 2 * ps), i32), cache,
                    jax.ShapeDtypeStruct((1, 2), i32),
                    jax.ShapeDtypeStruct((1,), i32))
        closed = jax.make_jaxpr(getattr(model, step))(params, *args)
        stack = cache[0]["k_pages"].shape
        pool_shapes = {stack, stack[1:]}
        n_pools = len(jax.tree_util.tree_leaves(cache))
        n_params = len(jax.tree_util.tree_leaves(params))
        pool_in = closed.jaxpr.invars[n_params + 1: n_params + 1 + n_pools]
        assert all(v.aval.shape == stack for v in pool_in)

        scans = [e for e in closed.jaxpr.eqns if e.primitive.name == "scan"]
        assert len(scans) == 1
        sc = scans[0]
        nc, ncarry = sc.params["num_consts"], sc.params["num_carry"]
        carry_in = sc.invars[nc: nc + ncarry]
        xs = sc.invars[nc + ncarry:]
        ys = sc.outvars[ncarry:]
        assert all(any(v is c for c in carry_in) for v in pool_in)
        assert not [v for v in xs if v.aval.shape in pool_shapes]
        assert not [v for v in ys if v.aval.shape in pool_shapes]

        casts = [e for e in _walk_eqns(closed.jaxpr)
                 if e.primitive.name == "convert_element_type"
                 and any(getattr(v.aval, "shape", None) in pool_shapes
                         for v in e.invars)]
        assert not casts, casts

    @pytest.mark.parametrize("step", ["decode_step_paged", "prefill_paged"])
    def test_unrolled_layers_match_the_scan_bitwise(self, step):
        """scan_layers=False indexes the carried pools with a static layer
        and gives the scan's logits and pools, bitwise.  In f32: in bf16
        XLA rounds the unrolled layers' fusions apart from the scan body's
        by an ulp, with or without the pools in the carry."""
        outs = []
        for scan in (True, False):
            model = Model(_cfg(scan_layers=scan, dtype=jnp.float32))
            params = model.init(jax.random.PRNGKey(0))
            cache = model.make_paged_cache(9, 16)
            pt = jnp.asarray([[3, 5], [4, 6]], jnp.int32)
            if step == "prefill_paged":
                toks = jax.random.randint(jax.random.PRNGKey(1), (1, 32),
                                          1, 500)
                args = (toks, cache, pt[:1], jnp.asarray([29], jnp.int32))
            else:
                cache = jax.tree_util.tree_map(
                    lambda x: jax.random.normal(
                        jax.random.PRNGKey(2), x.shape).astype(x.dtype),
                    cache)
                args = (jnp.asarray([[7], [11]], jnp.int32), cache, pt,
                        jnp.asarray([20, 9], jnp.int32))
            outs.append(jax.jit(getattr(model, step))(params, *args))
        for a, b in zip(jax.tree_util.tree_leaves(outs[0]),
                        jax.tree_util.tree_leaves(outs[1])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# model-level paged vs dense parity


def _cfg(act_impl="fused", **kw):
    return dataclasses.replace(get_reduced_config("repro-100m"),
                               act_impl=act_impl, **kw)


def _dense_greedy(model, params, prompt, n_new, max_len=192):
    toks = jnp.asarray([prompt], jnp.int32)
    cache = model.make_cache(1, max_len)
    logits, cache = model.prefill(params, toks, cache)
    out, pos = [], len(prompt)
    for i in range(n_new):
        nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
        out.append(int(nxt[0]))
        if i + 1 == n_new:
            break
        logits, cache = model.decode_step(params, nxt[:, None], cache, pos)
        pos += 1
    return out


class TestModelPagedParity:
    @pytest.mark.parametrize("ps", [16, 128])
    def test_session_matches_dense_greedy(self, ps):
        cfg = _cfg()
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        reqs = [
            GenRequest("a", rng.integers(1, 500, size=11).tolist(), 4),
            GenRequest("b", rng.integers(1, 500, size=27).tolist(), 6),
            GenRequest("c", rng.integers(1, 500, size=5).tolist(), 5),
        ]
        ref = {r.request_id: _dense_greedy(model, params, r.prompt,
                                           r.max_new_tokens)
               for r in reqs}
        engine = PagedServingEngine(model, params, max_slots=2, page_size=ps,
                                    max_context=4 * ps)
        got = {r.request_id: r.tokens for r in engine.run(reqs)}
        assert got == ref
        # every page returned to the pool
        assert (engine.sched.allocator.num_free
                == engine.sched.allocator.num_pages - 1)

    def test_evict_then_readmit_identical_tokens(self):
        """Round trip: serve prompt P, let it finish (pages freed), serve
        other traffic over the recycled pages, then readmit P — identical
        greedy tokens, i.e. nothing stale leaks through recycled pages."""
        cfg = _cfg()
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(1)
        p = rng.integers(1, 500, size=13).tolist()
        other = rng.integers(1, 500, size=21).tolist()
        engine = PagedServingEngine(model, params, max_slots=2, page_size=16,
                                    max_context=64)
        first = engine.run([GenRequest("p1", p, 5)])[0].tokens
        engine.run([GenRequest("noise", other, 7)])
        again = engine.run([GenRequest("p2", p, 5)])
        assert again[-1].tokens == first

    def test_continuous_batching_zero_fused_fallbacks(self):
        """Acceptance: a full continuous-batching session on the fused plan
        (prefill flash + split-KV decode) never falls back."""
        cfg = _cfg()
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(2)
        reqs = [GenRequest(f"r{i}", rng.integers(1, 500, size=n).tolist(), m)
                for i, (n, m) in enumerate([(9, 4), (33, 3)])]
        with warnings.catch_warnings():
            warnings.simplefilter("error")  # any fallback warning -> failure
            engine = PagedServingEngine(model, params, max_slots=2,
                                        page_size=16, max_context=64)
            results = engine.run(reqs)
        assert sorted(r.request_id for r in results) == ["r0", "r1"]
        assert all(len(r.tokens) == req.max_new_tokens
                   for r, req in zip(sorted(results,
                                            key=lambda r: r.request_id), reqs))

    def test_unfused_plan_gather_fallback_matches_dense(self):
        """Plans without a fused softmax site decode through the
        gather-pages fallback — identical greedy tokens to the dense-cache
        loop under the SAME plan."""
        rng = np.random.default_rng(3)
        p = rng.integers(1, 500, size=10).tolist()
        model = Model(_cfg("jnp"))
        params = model.init(jax.random.PRNGKey(0))
        ref = _dense_greedy(model, params, p, 4)
        engine = PagedServingEngine(model, params, max_slots=1,
                                    page_size=16, max_context=64)
        assert engine.run([GenRequest("x", p, 4)])[0].tokens == ref

    def test_paged_cache_rejects_non_attn_stacks(self):
        cfg = dataclasses.replace(get_reduced_config("gemma3-1b"),
                                  act_impl="jnp")
        with pytest.raises(ValueError, match="global-attention"):
            Model(cfg).make_paged_cache(8, 16)
