"""Data-parallel trainer with int8 error-feedback gradient compression
(distributed/compression.py) via shard_map — the cross-pod (DCI) sync tier.

Runs on however many devices the host exposes; the test suite runs it on 8
fake devices (tests/test_distributed.py).

    PYTHONPATH=src python examples/train_compressed.py [--steps 30]
"""
import argparse
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import repro  # noqa: F401
from repro.configs import get_reduced_config
from repro.data.pipeline import DataConfig, SyntheticLMData
from repro.distributed import compression
from repro.distributed.shard_fused import shard_map
from repro.launch.mesh import make_mesh
from repro.models import Model
from repro.optim import adamw


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--compress", action="store_true", default=True)
    args = ap.parse_args(argv)

    cfg = get_reduced_config("repro-100m", act_impl="jnp")
    model = Model(cfg)
    n_dev = len(jax.devices())
    mesh = make_mesh((n_dev,), ("dp",))
    B = 2 * n_dev

    params = model.init(jax.random.PRNGKey(0))
    state = adamw.init_state(params)
    residuals = compression.init_residuals(params)
    opt = adamw.AdamWConfig(lr=1e-3, total_steps=args.steps, warmup_steps=3)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), P(), P("dp")),
        out_specs=(P(), P(), P()),
    )
    def dp_step(state, residuals, batch):
        # local grads on this worker's shard
        (loss, _), grads = jax.value_and_grad(
            lambda p: model.loss(p, batch), has_aux=True
        )(state["params"])
        # int8 error-feedback all-reduce across the dp axis
        grads, residuals = compression.compressed_grad_sync(grads, residuals, "dp")
        new_state, metrics = adamw.apply_updates(state, grads, opt)
        loss = jax.lax.pmean(loss, "dp")
        return new_state, residuals, loss

    jstep = jax.jit(dp_step)
    data = SyntheticLMData(DataConfig(vocab_size=cfg.vocab_size, seq_len=128, global_batch=B))
    losses = []
    for step in range(args.steps):
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(step).items()}
        state, residuals, loss = jstep(state, residuals, batch)
        losses.append(float(loss))
        if step % 10 == 0:
            print(f"[dp-compressed] step={step} loss={losses[-1]:.4f}", flush=True)
    print(f"[dp-compressed] {losses[0]:.4f} -> {losses[-1]:.4f}")
    assert losses[-1] < losses[0], "compressed training must reduce loss"
    return 0


if __name__ == "__main__":
    main()
