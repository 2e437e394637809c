"""Per-arch smoke tests: reduced same-family configs, one forward/train step
on CPU, shape + finiteness assertions, and prefill/decode consistency."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro  # noqa: F401
from repro.configs import ARCH_IDS, get_reduced_config
from repro.models import Model, input_specs

B, S = 2, 16


def _batch_for(cfg, rng):
    k1, k2 = jax.random.split(rng)
    batch = {
        "tokens": jax.random.randint(k1, (B, S), 0, cfg.vocab_size),
        "targets": jax.random.randint(k2, (B, S), 0, cfg.vocab_size),
    }
    if cfg.is_encoder_decoder:
        batch["frames"] = jax.random.normal(k1, (B, cfg.encoder_seq, cfg.d_model), cfg.dtype)
    if cfg.n_vision_tokens:
        batch["vision_embeds"] = jax.random.normal(
            k1, (B, cfg.n_vision_tokens, cfg.d_model), cfg.dtype
        )
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_and_loss(arch):
    cfg = get_reduced_config(arch)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch_for(cfg, jax.random.PRNGKey(1))
    logits, aux = model.forward(params, batch)
    expect_s = S + (cfg.n_vision_tokens or 0)
    assert logits.shape == (B, expect_s, cfg.vocab_size), logits.shape
    assert bool(jnp.all(jnp.isfinite(logits))), "non-finite logits"
    loss, metrics = model.loss(params, batch)
    assert jnp.isfinite(loss)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_grads_finite(arch):
    cfg = get_reduced_config(arch)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch_for(cfg, jax.random.PRNGKey(1))
    (loss, _), grads = jax.value_and_grad(
        lambda p: model.loss(p, batch), has_aux=True
    )(params)
    assert jnp.isfinite(loss)
    flat = jax.tree_util.tree_leaves(grads)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in flat), "non-finite grads"


@pytest.mark.parametrize(
    "arch",
    ["qwen2.5-32b", "gemma3-1b", "olmoe-1b-7b", "mamba2-2.7b", "jamba-v0.1-52b", "whisper-small"],
)
def test_prefill_decode_matches_forward(arch):
    """Decode path must reproduce teacher-forcing logits position by position."""
    # MoE routing is dropless, so a token's experts do not depend on how many
    # tokens share its call (prefill of S-2 tokens, forward of S).
    # f32: the test checks algorithmic equivalence of the train/prefill/decode
    # paths, not bf16 rounding divergence between them.
    cfg = get_reduced_config(arch, dtype=jnp.float32)
    if cfg.n_vision_tokens:
        cfg = dataclasses.replace(cfg, n_vision_tokens=0)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab_size)
    extras = {}
    if cfg.is_encoder_decoder:
        extras["frames"] = jax.random.normal(
            jax.random.PRNGKey(3), (B, cfg.encoder_seq, cfg.d_model), cfg.dtype
        )
    # teacher forcing
    batch = {"tokens": tokens, **extras}
    full_logits, _ = model.forward(params, batch)

    # prefill on the first S-2 tokens, then decode two steps
    cache = model.make_cache(B, max_len=S)
    pre = S - 2
    logits_pre, cache = model.prefill(params, tokens[:, :pre], cache, **extras)
    np.testing.assert_allclose(
        np.asarray(logits_pre[:, 0]), np.asarray(full_logits[:, pre - 1]),
        rtol=2e-2, atol=2e-2,
    )
    lg, cache = model.decode_step(params, tokens[:, pre : pre + 1], cache, jnp.int32(pre))
    np.testing.assert_allclose(
        np.asarray(lg[:, 0]), np.asarray(full_logits[:, pre]), rtol=2e-2, atol=2e-2
    )
    lg2, cache = model.decode_step(
        params, tokens[:, pre + 1 : pre + 2], cache, jnp.int32(pre + 1)
    )
    np.testing.assert_allclose(
        np.asarray(lg2[:, 0]), np.asarray(full_logits[:, pre + 1]), rtol=2e-2, atol=2e-2
    )


@pytest.mark.parametrize("arch", ["qwen2.5-32b", "olmoe-1b-7b"])
def test_pwl_activation_modes_close(arch):
    """Swapping exact->PWL activations must barely move the logits."""
    cfg_exact = get_reduced_config(arch, act_impl="exact")
    cfg_pwl = get_reduced_config(arch, act_impl="jnp", act_breakpoints=32)
    model_e, model_p = Model(cfg_exact), Model(cfg_pwl)
    params = model_e.init(jax.random.PRNGKey(0))
    batch = _batch_for(cfg_exact, jax.random.PRNGKey(1))
    le, _ = model_e.forward(params, batch)
    lp, _ = model_p.forward(params, batch)
    if cfg_exact.n_experts:
        # MoE: a PWL-perturbed residual stream can flip discrete top-k routing
        # for a few tokens — compare the bulk of the distribution instead
        diff = jnp.quantile(jnp.abs(le - lp), 0.95)
        assert float(diff) < 0.25, float(diff)
    else:
        diff = jnp.max(jnp.abs(le - lp))
        assert float(diff) < 0.25, float(diff)
