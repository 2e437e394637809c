"""The serving engine's weights: a copy cast once at set-up.

``Model.serving_params`` casts every leaf the forward reads at
``cfg.dtype`` and passes the float32-read ones (``ParamDef.read_f32``)
through; ``PagedServingEngine`` keeps only that copy.  The forward's own
``astype`` then has nothing left to cast, so:

* the dense forward of every reduced configuration, and the paged prefill
  and decode steps of olmo-1b and olmoe-1b-7b (also on a 4-device host
  mesh), give the masters' logits bit for bit on the copy;
* neither paged step on the copy casts a weight;
* the float32-read leaves stay float32, every leaf keeps its master's
  sharding, and a tree already cast comes back as it is;
* ``serving.init`` counts the leaves cast and the bytes the steps read.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro  # noqa: F401
from repro import tracing
from repro.configs import ARCH_IDS, get_reduced_config
from repro.models import Model
from repro.models.common import ParamDef
from repro.serving import PagedServingEngine

from mesh_utils import run_py
from test_serving import _walk_eqns


def _defs(model):
    return jax.tree_util.tree_leaves(model.param_defs(),
                                     is_leaf=lambda d: isinstance(d, ParamDef))


def perturbed(params, key=5):
    """Every leaf moved off its init, placed as it was: a norm scale starts
    at zero, where a wrong cast would change nothing."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    move = jax.jit(
        lambda xs, keys: [x + 0.05 * jax.random.normal(k, x.shape, x.dtype)
                          for x, k in zip(xs, keys)],
        out_shardings=[x.sharding for x in leaves])
    keys = jax.random.split(jax.random.PRNGKey(key), len(leaves))
    return treedef.unflatten(move(leaves, list(keys)))


def paged_steps(eng, params, n_decode=2):
    """A prefill of 13 tokens into two pages of slot 0, then ``n_decode``
    greedy decode steps over every slot, through the engine's own jitted
    programs on ``params``.  Returns each step's logits (and rows per
    expert, for an MoE model) and the final pools."""
    ps, slots = eng.page_size, eng.max_slots
    n = 13
    toks = np.zeros((1, 2 * ps), np.int32)
    toks[0, :n] = np.arange(1, n + 1)
    pt = np.zeros((slots, eng.max_cols), np.int32)
    pt[0, :3] = (1, 2, 3)
    logits, cache, _, rows = eng._fns["prefill"](
        params, jnp.asarray(toks), eng.cache, jnp.asarray(pt[:1, :2]),
        jnp.asarray([n], jnp.int32))
    outs = [logits, rows]
    kv_len = np.zeros((slots,), np.int32)
    kv_len[0] = n
    cur = np.zeros((slots, 1), np.int32)
    cur[0, 0] = int(jnp.argmax(logits[0, 0]))
    for _ in range(n_decode):
        logits, cache, _, rows = eng._fns["decode"](
            params, jnp.asarray(cur), cache, jnp.asarray(pt),
            jnp.asarray(kv_len))
        outs += [logits, rows]
        cur[:, 0] = np.asarray(jnp.argmax(logits[:, 0], axis=-1))
        kv_len[0] += 1
    return [np.asarray(o) for o in outs if o is not None], [
        np.asarray(x.astype(jnp.float32))
        for x in jax.tree_util.tree_leaves(cache)]


def assert_same(a, b):
    (la, ca), (lb, cb) = a, b
    assert len(la) == len(lb) and len(ca) == len(cb)
    for x, y in zip(la + ca, lb + cb):
        np.testing.assert_array_equal(x, y)


def _engine(cfg, params, **kw):
    return PagedServingEngine(Model(cfg), params, max_slots=3, page_size=8,
                              max_context=32, num_pages=16, **kw)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_on_serving_copy_is_bitwise(arch):
    """The dense forward gives the masters' logits on the copy, bit for
    bit, for every reduced configuration: a leaf cast that the forward
    reads at float32 changes them (a leaf left at float32 that the forward
    casts is the cast the jaxpr walk below finds)."""
    cfg = get_reduced_config(arch)
    model = Model(cfg)
    masters = perturbed(model.init(jax.random.PRNGKey(0)))
    served = model.serving_params(masters)
    key = jax.random.PRNGKey(1)
    batch = {"tokens": jax.random.randint(key, (2, 16), 0, cfg.vocab_size)}
    if cfg.is_encoder_decoder:
        batch["frames"] = jax.random.normal(
            key, (2, cfg.encoder_seq, cfg.d_model), cfg.dtype)
    if cfg.n_vision_tokens:
        batch["vision_embeds"] = jax.random.normal(
            key, (2, cfg.n_vision_tokens, cfg.d_model), cfg.dtype)
    fwd = jax.jit(model.forward)
    np.testing.assert_array_equal(np.asarray(fwd(served, batch)[0]),
                                  np.asarray(fwd(masters, batch)[0]))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_serving_copy_dtypes(arch):
    """Masters stay float32 on every leaf; the copy holds the float32-read
    leaves at float32 (the very arrays) and every other at cfg.dtype."""
    cfg = get_reduced_config(arch)
    model = Model(cfg)
    assert {s.dtype for s in jax.tree_util.tree_leaves(
        model.param_structs())} == {jnp.dtype(jnp.float32)}
    masters = model.init(jax.random.PRNGKey(0))
    served = model.serving_params(masters)
    for d, m, s in zip(_defs(model), jax.tree_util.tree_leaves(masters),
                       jax.tree_util.tree_leaves(served)):
        if d.read_f32:
            assert s is m
        else:
            assert s.dtype == cfg.dtype and s.shape == m.shape


def test_serving_copy_of_a_copy_is_itself():
    """A tree with nothing left to cast (an engine built from another
    engine's params) comes back as it is, and counts no cast."""
    cfg = get_reduced_config("olmo-1b", act_impl="jnp")
    model = Model(cfg)
    served = model.serving_params(model.init(jax.random.PRNGKey(0)))
    assert model.serving_params(served) is served
    t0 = time.perf_counter()
    eng = _engine(cfg, served)
    assert eng.params is served
    init = [r for r in tracing.records(since=t0) if r.name == "serving.init"]
    assert init[-1].attrs["weights_cast"] == 0


@pytest.mark.parametrize("arch", ["olmo-1b", "olmoe-1b-7b"])
def test_engine_counts_the_cast_at_set_up(arch):
    """``serving.init`` records ``weights_cast``, the leaves the copy holds
    in another dtype, and ``step_weight_bytes``, the copy's bytes; the
    engine keeps the copy, not the masters."""
    cfg = get_reduced_config(arch, act_impl="jnp")
    model = Model(cfg)
    masters = model.init(jax.random.PRNGKey(0))
    t0 = time.perf_counter()
    eng = _engine(cfg, masters)
    init = [r for r in tracing.records(since=t0) if r.name == "serving.init"]
    assert len(init) == 1
    n_cast = sum(not d.read_f32 for d in _defs(model))
    served = jax.tree_util.tree_leaves(eng.params)
    assert init[0].attrs["weights_cast"] == n_cast > 0
    assert init[0].attrs["step_weight_bytes"] == sum(x.nbytes for x in served)
    assert sum(x.dtype == cfg.dtype for x in served) == n_cast
    assert all(a is not b for a, b in zip(
        served, jax.tree_util.tree_leaves(masters))
        if a.dtype != jnp.float32)


@pytest.mark.parametrize("arch", ["olmo-1b", "olmoe-1b-7b"])
def test_paged_steps_on_serving_copy_are_bitwise(arch):
    """Paged prefill and decode (fused plan, Pallas interpret mode) give
    the masters' logits, rows per expert and pools on the engine's copy,
    bit for bit."""
    cfg = get_reduced_config(arch, act_impl="fused", pwl_softmax=True)
    masters = perturbed(Model(cfg).init(jax.random.PRNGKey(0)))
    eng = _engine(cfg, masters)
    assert_same(paged_steps(eng, eng.params), paged_steps(eng, masters))


@pytest.mark.mesh
def test_olmoe_serving_copy_on_4_devices():
    """On a (1, 4) host mesh (experts and heads over the model axis) every
    leaf of the copy keeps its master's sharding, and the sharded paged
    steps give the masters' logits and pools on it, bit for bit."""
    r = run_py("""
        import sys
        sys.path.insert(0, "tests")
        import jax, jax.numpy as jnp
        from repro.configs import get_reduced_config
        from repro.distributed.sharding import make_rules, use_rules
        from repro.launch.mesh import make_mesh
        from repro.models import Model
        from test_serving_weights import (_engine, assert_same, paged_steps,
                                          perturbed)

        cfg = get_reduced_config("olmoe-1b-7b", act_impl="fused",
                                 pwl_softmax=True)
        rules = make_rules(cfg, make_mesh((1, 4), ("data", "model")))
        with use_rules(rules):
            masters = perturbed(Model(cfg).init(jax.random.PRNGKey(0)))
        eng = _engine(cfg, masters, rules=rules)
        pairs = list(zip(jax.tree_util.tree_leaves(masters),
                         jax.tree_util.tree_leaves(eng.params)))
        assert any(len(m.sharding.device_set) == 4
                   and not m.sharding.is_fully_replicated for m, _ in pairs)
        assert sum(m.dtype != s.dtype for m, s in pairs) > 0
        for m, s in pairs:
            assert s.sharding == m.sharding, (m.sharding, s.sharding)
        assert_same(paged_steps(eng, eng.params, n_decode=1),
                    paged_steps(eng, masters, n_decode=1))
        print("OK")
    """, devices=4)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def _weight_casts(model, params, step):
    """The convert_element_type equations of one paged step on ``params``
    whose operand is an f32 array shaped as a cast-read weight (stacked, or
    one layer's slice of the stack)."""
    ps, B = 8, 2
    cache = jax.eval_shape(lambda: model.make_paged_cache(9, ps))
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
    shapes = set()
    for d in _defs(model):
        if not d.read_f32:
            shapes.add(d.shape)
            if d.logical_axes[0] == "layers":
                shapes.add(d.shape[1:])
    return [e for e in _walk_eqns(closed.jaxpr)
            if e.primitive.name == "convert_element_type"
            and any(getattr(v.aval, "dtype", None) == jnp.float32
                    and getattr(v.aval, "shape", None) in shapes
                    for v in e.invars)]


@pytest.mark.parametrize("arch", ["olmo-1b", "olmoe-1b-7b"])
@pytest.mark.parametrize("step", ["decode_step_paged", "prefill_paged"])
def test_paged_steps_on_serving_copy_cast_no_weight(arch, step):
    """On the masters each paged step casts the weights (the walk finds
    them); on the copy it casts none."""
    model = Model(get_reduced_config(arch, act_impl="fused",
                                     pwl_softmax=True))
    masters = model.init(jax.random.PRNGKey(0))
    assert _weight_casts(model, masters, step)
    served = model.serving_params(masters)
    assert not _weight_casts(model, served, step)
