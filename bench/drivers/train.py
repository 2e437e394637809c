"""Training: the program's jitted train step (``launch.steps.
build_train_step``, AdamW, the fused backward kernels), fed batches of
``data.pipeline.SyntheticLMData`` from the seed through its
``PrefetchIterator``, as ``launch.train.run`` does.

Set-up builds the one step and its state, compiles it, and drives it
through its first three steps on the window's own call and feed; the
window then continues from that state.  It keeps about ``AHEAD_S`` seconds
of steps dispatched ahead of the one whose loss it reads, so that a stall
of the host does not idle the chip; when its time is up it sends nothing
more, waits for every step sent, and reads the clock after that wait: all
of those steps count, over all of that time.  The reference follows the
first three steps.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import time

import numpy as np

from harness import core, serving, weights

FIRST = 3   # steps the reference follows
AHEAD_S = 6.0   # seconds of steps in flight ahead of the loss read


def build(ctx):
    """The jitted step, its state made from the seed, and the feed."""
    import jax

    from repro.data.pipeline import DataConfig, PrefetchIterator, SyntheticLMData
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import build_train_step
    from repro.models import Model, ShapeCell
    from repro.optim import adamw

    job, config = ctx.traffic, ctx.config
    cfg = serving.program_config(config)
    opt = {k: v for k, v in config["optimizer"].items() if k != "name"}
    mesh = make_mesh((1, 1), ("data", "model"), devices=ctx.devices[:1])
    cell = ShapeCell("bench", job["seq"], job["batch"], "train")
    fn, ins, outs, _, kw = build_train_step(
        cfg, mesh, cell, opt_cfg=adamw.AdamWConfig(**opt), microbatches=1)
    step = jax.jit(fn, in_shardings=ins, out_shardings=outs,
                   donate_argnums=kw["donate_argnums"])
    structs = Model(cfg).param_structs()
    params = weights.make(structs, ctx.args.seed, ins[0]["params"])
    state = adamw.init_state(params)
    data = SyntheticLMData(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=job["seq"],
        global_batch=job["batch"], seed=ctx.args.seed))
    return cfg, step, state, PrefetchIterator(data), structs


def run(ctx) -> core.Outcome:
    import jax
    import jax.numpy as jnp

    job, config = ctx.traffic, ctx.config
    cfg, step, state, feed, structs = build(ctx)
    core.log(f"built at {time.perf_counter() - ctx.t_start:.3f} s")
    b1 = config["optimizer"]["b1"]
    norms = jax.jit(weights.slice_norms)

    losses, fed = [], []
    for k in range(FIRST):
        batch = next(feed)
        fed.append(batch)
        t0 = time.perf_counter()
        with ctx.spans("train_step"):
            state, m = step(state, {n: jnp.asarray(v) for n, v in batch.items()})
            losses.append(float(m["loss"]))
        step_s = time.perf_counter() - t0
        if k == 0:
            # the first gradient as the optimizer got it (clipped):
            # mu after one step is (1 - b1) * g
            grad_norms = np.asarray(norms(state["mu"])) / (1.0 - b1)
    change = np.asarray(weights.change_norms(state["params"], structs,
                                             ctx.args.seed))
    core.log(f"first losses {losses}; first steps done at "
             f"{time.perf_counter() - ctx.t_start:.3f} s")

    ahead = max(1, math.ceil(AHEAD_S / step_s))
    core.log(f"last first step {step_s:.4f} s: {ahead} steps in flight")

    t_open = ctx.open_window()
    steps, ends, pending = 0, [t_open], collections.deque()
    # one span over the window: the steps in flight run on the chip while
    # the host dispatches later ones
    with ctx.spans("train_step"):
        while time.perf_counter() - t_open < ctx.args.seconds:
            batch = next(feed)
            state, m = step(state, {n: jnp.asarray(v) for n, v in batch.items()})
            pending.append(m["loss"])
            steps += 1
            if len(pending) > ahead:
                float(pending.popleft())
                ends.append(time.perf_counter())
        while pending:
            float(pending.popleft())
            ends.append(time.perf_counter())
    ctx.close_window()
    secs = ctx.window[1] - ctx.window[0]
    tokens = steps * job["batch"] * job["seq"]
    ctx.memory_peak = core.memory_peak_bytes(ctx.devices)
    feed.close()
    del state, m
    took = np.diff(ends)
    core.log(f"window: {steps} steps, {tokens} tokens in {secs:.3f} s; loss "
             f"reads p50 {np.median(took):.4f} s apart, longest "
             f"{took.max():.4f} s (read {int(took.argmax())})")

    prog = Readings(losses, grad_norms, change)
    ref = reference_readings(ctx, fed, "f32")
    checks = compare(prog, ref, job["check"])
    counts = {"steps": steps, "batch": job["batch"], "seq": job["seq"]}
    return core.Outcome(attempted=steps, failed=0,
                        end_to_end={"train_tokens_per_s": tokens / secs},
                        checks=checks, counts=counts)


@dataclasses.dataclass
class Readings:
    """Losses of the first steps, the norms of the first (clipped) gradient
    and of the parameters' change after the first steps, per leaf and per
    layer of a stacked leaf."""

    losses: list
    grad_norms: np.ndarray
    change_norms: np.ndarray


def reference_readings(ctx, batches, precision: str) -> Readings:
    """The plain reference through the same first steps, from the same
    seed's weights and the same batches: gradients on the chip, AdamW on
    the host in float32 numpy, so the chip holds only the weights and one
    gradient."""
    import jax

    ref = core.load_module(core.BENCH / "references" /
                           f"{ctx.config['reference']}.py")
    cfg = serving.program_config(ctx.config)
    from repro.models import Model

    structs = Model(cfg).param_structs()
    opt = ctx.config["optimizer"]
    params = weights.make(structs, ctx.args.seed)
    p0 = jax.tree_util.tree_map(np.asarray, params)
    p = jax.tree_util.tree_map(np.array, p0)
    mu = jax.tree_util.tree_map(np.zeros_like, p0)
    nu = jax.tree_util.tree_map(np.zeros_like, p0)
    losses, grad_norms = [], None
    norms = jax.jit(weights.slice_norms)
    for k, batch in enumerate(batches, start=1):
        loss, g = ref.loss_and_grad(ctx.config, params, batch["tokens"],
                                    batch["targets"], precision)
        losses.append(loss)
        g = jax.tree_util.tree_map(np.asarray, g)
        scale = ref.host_clip_scale(opt, g)
        if k == 1:
            grad_norms = np.asarray(norms(g)) * scale
        p, mu, nu = ref.host_adamw(opt, p, g, mu, nu, k, scale)
        del params, g
        params = jax.tree_util.tree_map(jax.numpy.asarray, p)
    change = np.asarray(norms(jax.tree_util.tree_map(
        lambda a, b: a - b, p, p0)))
    return Readings(losses, grad_norms, change)


def _gap(prog: np.ndarray, ref: np.ndarray, keep=None) -> float:
    """Worst leaf: the gap between the program's norm and the reference's,
    over the larger of the reference's norm of that leaf and the median
    leaf's."""
    floor = max(float(np.median(ref)), 1e-30)
    gap = np.abs(prog - ref) / np.maximum(ref, floor)
    if keep is not None:
        gap = gap[keep]
    return float(gap.max()) if gap.size else float("nan")


def gaps(prog: Readings, ref: Readings) -> dict[str, float]:
    """The three numbers compared.  Leaves whose reference gradient is under
    a thousandth of the median leaf's move under Adam by round-off alone:
    they are left out of the change."""
    keep = ref.grad_norms >= 1e-3 * np.median(ref.grad_norms)
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog.losses, ref.losses))
    return {"loss_rel_gap": float(loss),
            "grad_norm_gap": _gap(prog.grad_norms, ref.grad_norms),
            "update_norm_gap": _gap(prog.change_norms, ref.change_norms, keep)}


def compare(prog: Readings, ref: Readings, limits: dict) -> list[core.Check]:
    g = gaps(prog, ref)
    core.log("train readings: " + ", ".join(f"{k} {v!r}" for k, v in g.items()))
    return [core.Check(k, v, limits[k]) for k, v in g.items()]
