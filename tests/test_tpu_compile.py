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
def one_chip():
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
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()
    if old_log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)


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
    hkv, pages, dh, batch = 16, 17, 128, 4
    pool = _struct(one_chip, (hkv, pages, page_size, dh), jnp.bfloat16)
    if kernel == "append_kv":
        new = _struct(one_chip, (batch, 1, hkv, dh), jnp.bfloat16)
        args = (pool, pool, new, new,
                _struct(one_chip, (batch, 4), jnp.int32),
                _struct(one_chip, (batch,), jnp.int32))
        text = _compiled_text(kv_cache.append_kv, *args)
    else:
        new = _struct(one_chip, (1, 2 * page_size, hkv, dh), jnp.bfloat16)
        args = (pool, pool, new, new, _struct(one_chip, (1, 4), jnp.int32))
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


def test_fused_moe_glu_compiles_at_olmoe_widths(one_chip, mosaic):
    # olmoe-1b-7b: d_model 2048, expert d_ff 1024; 16 of its 64 experts (one
    # chip's share on a model=4 mesh), 256-token capacity buckets
    table = _table("silu")
    x = _struct(one_chip, (16, 256, 2048), jnp.bfloat16)
    w = _struct(one_chip, (16, 2048, 1024), jnp.bfloat16)
    text = _compiled_text(
        lambda x, wg, wu: fused.fused_moe_glu(x, wg, wu, table=table), x, w, w)
    assert "tpu_custom_call" in text
