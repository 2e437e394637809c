"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached: Mosaic refuses here what the chip would refuse (unaligned
slices, too much VMEM), at no chip time.

The topology is described inside a module fixture, never at import, and the
tests skip where it cannot be described.  Code that asks
``jax.default_backend()`` sees the CPU here and would run every kernel in
interpret mode, so each test steers ``should_interpret`` to False.  Every
compile must hold a Mosaic call (``tpu_custom_call``).
"""
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro  # noqa: F401
from repro import sfu
from repro.configs import get_config
from repro.kernels import _backend, fused
from repro.models import Model
from repro.serving import kv_cache


@pytest.fixture(scope="module")
def v5e_2x2():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    old_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip are written to the persistent cache but
    # cannot be read back without one: keep the cache off around them
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()
    if old_log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Every kernel wrapper lowers through Mosaic, not the interpreter."""
    original = _backend.should_interpret
    for mod in list(sys.modules.values()):
        if not getattr(mod, "__name__", "").startswith("repro."):
            continue
        for name in ("should_interpret", "_should_interpret"):
            if getattr(mod, name, None) is original:
                monkeypatch.setattr(mod, name, lambda: False)


def _struct(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _tree_structs(sharding, tree):
    return jax.tree_util.tree_map(
        lambda s: _struct(sharding, s.shape, s.dtype), tree)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _table(fn):
    return sfu.get_store().get(fn=fn, n_breakpoints=32)


# --------------------------------------------------------------------------
# paged KV pool writes


@pytest.mark.parametrize("page_size", [16, 128])
@pytest.mark.parametrize("kernel", ["append_kv", "write_prompt_pages"])
def test_pool_write_kernels_compile_bf16(one_chip, mosaic, kernel, page_size):
    # the layers' stacked pools, written at a traced layer index
    layers, hkv, pages, dh, batch = 4, 16, 17, 128, 4
    pool = _struct(one_chip, (layers, hkv, pages, page_size, dh),
                   jnp.bfloat16)
    layer = _struct(one_chip, (), jnp.int32)
    if kernel == "append_kv":
        new = _struct(one_chip, (batch, 1, hkv, dh), jnp.bfloat16)
        args = (pool, pool, new, new,
                _struct(one_chip, (batch, 4), jnp.int32),
                _struct(one_chip, (batch,), jnp.int32), layer)
        text = _compiled_text(kv_cache.append_kv, *args)
    else:
        new = _struct(one_chip, (1, 2 * page_size, hkv, dh), jnp.bfloat16)
        args = (pool, pool, new, new, _struct(one_chip, (1, 4), jnp.int32),
                layer)
        text = _compiled_text(kv_cache.write_prompt_pages, *args)
    assert "tpu_custom_call" in text


# --------------------------------------------------------------------------
# olmo-1b paged serving steps at published widths


@pytest.fixture(scope="module")
def olmo_1b():
    cfg = get_config("olmo-1b", act_impl="fused", pwl_softmax=True)
    return Model(cfg)


@pytest.mark.parametrize("step", ["prefill_paged", "decode_step_paged"])
def test_olmo_1b_paged_step_compiles(one_chip, mosaic, olmo_1b, step):
    """Both steps compile through Mosaic with the layer-indexed pool kernels
    on bf16 pages of 128; neither slices a layer's pool out of the stack
    nor casts one to f32 (the decode kernel reads pages at their own
    dtype)."""
    slots, page_size, pages, prompt = 4, 128, 17, 256
    params = _tree_structs(one_chip, olmo_1b.param_structs())
    cache = _tree_structs(
        one_chip, jax.eval_shape(lambda: olmo_1b.make_paged_cache(pages,
                                                                  page_size)))
    i32 = jnp.int32
    if step == "prefill_paged":
        args = (_struct(one_chip, (1, prompt), i32), cache,
                _struct(one_chip, (1, prompt // page_size), i32),
                _struct(one_chip, (1,), i32))
    else:
        args = (_struct(one_chip, (slots, 1), i32), cache,
                _struct(one_chip, (slots, 4), i32),
                _struct(one_chip, (slots,), i32))
    text = _compiled_text(getattr(olmo_1b, step), params, *args)
    assert "tpu_custom_call" in text
    stack = cache[0]["k_pages"]
    assert stack.dtype == jnp.bfloat16
    layer_pool = ",".join(map(str, stack.shape[1:]))
    assert f"f32[{layer_pool}]" not in text
    assert f"bf16[{layer_pool}]" not in text
    assert f"f32[{stack.shape[0]},{layer_pool}]" not in text
    if step == "decode_step_paged":
        assert "%_paged_decode" in text


def _serving_structs(model, sharding_of):
    """The serving copy's ShapeDtypeStructs (``Model.serving_params``):
    float32 where the forward reads the leaf at float32, ``cfg.dtype``
    elsewhere; ``sharding_of(def)`` places each leaf."""
    from repro.models.common import ParamDef

    return jax.tree_util.tree_map(
        lambda d: jax.ShapeDtypeStruct(
            d.shape, jnp.float32 if d.read_f32 else model.cfg.dtype,
            sharding=sharding_of(d)),
        model.param_defs(), is_leaf=lambda x: isinstance(x, ParamDef))


@pytest.mark.parametrize("step", ["prefill_paged", "decode_step_paged"])
def test_olmo_1b_paged_steps_on_serving_copy_hold_no_weight_copy(
        one_chip, mosaic, olmo_1b, step):
    """At the chat cell's shapes (32 slots, pages of 128, a 1024-token
    bucket, 16 columns) the steps on the engine's bf16 copy need no
    temporary copy of the weights.  On the f32 masters they held the 16
    layers' bf16 cast, 2,148,354,560 temp bytes (decode) and 2,464,097,280
    (prefill); on the copy 548,352 and 206,956,544."""
    params = _serving_structs(olmo_1b, lambda d: one_chip)
    cache = _tree_structs(
        one_chip, jax.eval_shape(lambda: olmo_1b.make_paged_cache(128, 128)))
    i32 = jnp.int32
    if step == "prefill_paged":
        args = (_struct(one_chip, (1, 1024), i32), cache,
                _struct(one_chip, (1, 8), i32), _struct(one_chip, (1,), i32))
    else:
        args = (_struct(one_chip, (32, 1), i32), cache,
                _struct(one_chip, (32, 16), i32),
                _struct(one_chip, (32,), i32))
    compiled = jax.jit(getattr(olmo_1b, step)).lower(params, *args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 0.3e9


# --------------------------------------------------------------------------
# training kernels: forward and fused backward


def test_fused_glu_grad_compiles(one_chip, mosaic):
    table = _table("silu")
    x = _struct(one_chip, (4096, 2048), jnp.bfloat16)
    w = _struct(one_chip, (2048, 8192), jnp.bfloat16)

    def loss(x, wg, wu):
        y = fused.fused_glu(x, wg, wu, table=table)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), x, w, w)
    assert "tpu_custom_call" in text


def test_fused_flash_attention_grad_compiles(one_chip, mosaic):
    table = _table("exp")
    qkv = _struct(one_chip, (2, 512, 16, 128), jnp.bfloat16)

    def loss(q, k, v):
        y = fused.fused_flash_attention(q, k, v, table=table, causal=True)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv)
    assert "tpu_custom_call" in text


def _grouped_glu_text(one_chip, rows):
    """The grouped expert GLU at olmoe-1b-7b widths (d_model 2048, expert
    d_ff 1024) over 16 of its 64 experts (one chip's share on a model=4
    mesh), on ``rows`` rows laid out as the MoE layer lays them out."""
    from repro.kernels.fused import moe as M

    table = _table("silu")
    bm = M.row_tile(rows / 16)
    m = -(-(rows + 16 * (bm - 1)) // bm) * bm
    x = _struct(one_chip, (m, 2048), jnp.bfloat16)
    w = _struct(one_chip, (16, 2048, 1024), jnp.bfloat16)
    wd = _struct(one_chip, (16, 1024, 2048), jnp.bfloat16)
    sizes = _struct(one_chip, (16,), jnp.int32)

    def ffn(x, wg, wu, wd, sizes):
        h = fused.fused_moe_glu(x, wg, wu, sizes, row_tile=bm, table=table)
        return M.moe_down(h, wd, sizes, row_tile=bm)

    return _compiled_text(ffn, x, w, w, wd, sizes)


def test_fused_moe_glu_compiles_at_olmoe_widths(one_chip, mosaic):
    # decode: 64 slots x top-8 = 512 rows, ~128 of them on one rank
    text = _grouped_glu_text(one_chip, 128)
    assert "%moe_glu" in text and "%moe_down" in text
    assert "tpu_custom_call" in text


def test_fused_moe_glu_compiles_at_olmoe_prefill(one_chip, mosaic):
    # a 1024-token prefill: 8192 rows, ~2048 of them on one rank
    text = _grouped_glu_text(one_chip, 2048)
    assert "%moe_glu" in text and "%moe_down" in text
    assert "tpu_custom_call" in text


# --------------------------------------------------------------------------
# olmoe-1b-7b served over a (data=1, model=4) mesh at published widths


def test_olmoe_paged_decode_compiles_on_model4_mesh(v5e_2x2, mosaic):
    """The paged decode step of the whole model (QK-norm on, dropless
    top-8 of 64 experts, 16 per chip) compiles for four described chips
    on the engine's bf16 copy of the weights: the grouped kernels run per
    shard, the expert combine is an all-reduce, the step returns its rows
    per expert, and it holds no temporary copy of the weights.  At these
    shapes a chip's temp bytes read 3,424,521,216 on the f32 masters (the
    bf16 cast of its share of the weights) and 135,636,992 on the copy."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.distributed.sharding import make_rules, sanitize_spec, use_rules
    from repro.launch.mesh import make_mesh
    from repro.models import transformer
    from repro.models.common import logical_specs, shape_structs

    cfg = get_config("olmoe-1b-7b", act_impl="fused", pwl_softmax=True)
    assert cfg.qk_norm and not cfg.norm_topk
    model = Model(cfg)
    mesh = make_mesh((1, 4), ("data", "model"), devices=v5e_2x2.devices)
    rules = make_rules(cfg, mesh)

    def placed(structs, logical):
        return jax.tree_util.tree_map(
            lambda s, ax: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=(
                NamedSharding(mesh, sanitize_spec(mesh, rules.spec(*ax),
                                                  s.shape)))),
            structs, logical, is_leaf=lambda x: isinstance(x, tuple))

    slots, page_size, pages = 8, 128, 33
    params = _serving_structs(model, lambda d: NamedSharding(
        mesh, sanitize_spec(mesh, rules.spec(*d.logical_axes), d.shape)))
    pool_defs = transformer.paged_cache_defs(cfg, pages, page_size)
    cache = placed(shape_structs(pool_defs), logical_specs(pool_defs))
    rep = NamedSharding(mesh, PartitionSpec())
    i32 = jnp.int32

    def step(params, toks, cache, pt, lens):
        with use_rules(rules):
            return model.decode_step_paged(params, toks, cache, pt, lens)

    lowered = jax.jit(step).lower(
        params, jax.ShapeDtypeStruct((slots, 1), i32, sharding=rep), cache,
        jax.ShapeDtypeStruct((slots, 4), i32, sharding=rep),
        jax.ShapeDtypeStruct((slots,), i32, sharding=rep))
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "%moe_glu" in text and "%moe_down" in text
    assert "%_paged_decode" in text
    assert "all-reduce" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.3e9
    rows = lowered.out_info[2]
    assert rows.shape == (cfg.n_layers, 4, cfg.n_experts // 4)
    assert rows.dtype == i32
