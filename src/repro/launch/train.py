"""Production training launcher: config -> mesh -> sharded train loop with
checkpoint/restart, preemption handling, and straggler monitoring.

Usage (host-scale example; the same code path drives the pod-scale mesh):

  PYTHONPATH=src python -m repro.launch.train --arch repro-100m --steps 200 \
      --batch 8 --seq 512 --ckpt-dir /tmp/ckpt --plan plan.json

On a real fleet this process runs once per host (jax.distributed.initialize
picks up the cluster env); here it drives however many devices the host has.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp

from repro import sfu
from repro.checkpoint.manager import CheckpointManager, install_sigterm_save
from repro.configs import get_config, get_reduced_config
from repro.data.pipeline import DataConfig, IteratorState, PrefetchIterator, SyntheticLMData
from repro.distributed.monitor import StepMonitor
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import build_train_step
from repro.models import ShapeCell
from repro.optim import adamw


def run(argv=None) -> dict:
    """Parse ``argv`` and train; returns ``rc`` (0 when the loss fell),
    the per-step ``losses`` and ``step_seconds``, and the jitted ``step``
    with its argument shapes ``structs``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="repro-100m")
    ap.add_argument("--reduced", action="store_true", help="reduced config (CI)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument(
        "--plan", default=None, metavar="PATH",
        help="load an ActivationPlan JSON (repro.sfu); default: the arch "
        "config's own plan",
    )
    ap.add_argument(
        "--dump-plan", default=None, metavar="PATH",
        help="write the exact activation plan this run uses as JSON",
    )
    ap.add_argument(
        "--impl-bwd", default=None, choices=["fused", "recompute"],
        help="backward implementation for fused activation sites: 'fused' "
        "(Pallas backward kernels, the default) or 'recompute' (jnp "
        "rematerialization oracle — escape hatch; see docs/plans.md)",
    )
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--model-parallel", type=int, default=1)
    # removed flags, kept one release as hard errors with a pointer
    ap.add_argument("--act-impl", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--act-breakpoints", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.act_impl is not None or args.act_breakpoints is not None:
        ap.error(
            "--act-impl/--act-breakpoints were removed: pass --plan "
            "<plan.json> instead (dump one with --dump-plan or "
            "sfu.dump_plan(sfu.compile_plan(cfg), path); see docs/plans.md)"
        )

    getter = get_reduced_config if args.reduced else get_config
    if args.plan:
        loaded = sfu.load_plan(args.plan)
        cfg = getter(args.arch, act_plan=loaded)
        missing = sfu.plan_missing_sites(cfg, loaded)
        if missing:
            ap.error(
                f"--plan {args.plan} lacks specs for activation sites "
                f"{missing} that arch '{args.arch}' instantiates — dump one "
                "from this arch's config with --dump-plan"
            )
    else:
        cfg = getter(args.arch)
    if args.impl_bwd is not None:
        import dataclasses

        cfg = dataclasses.replace(cfg, act_impl_bwd=args.impl_bwd)
    plan = sfu.plan_for(cfg)
    print(f"[train] activation plan {plan.fingerprint}: "
          f"{ {k: s.impl for k, s in plan.items()} }", flush=True)
    print(f"[train] fused backward impl: "
          f"{cfg.act_impl_bwd or 'fused (ambient default)'}", flush=True)
    if args.dump_plan:
        print(f"[train] plan -> {sfu.dump_plan(plan, args.dump_plan)}", flush=True)
    mesh = make_host_mesh(model=args.model_parallel)
    cell = ShapeCell("host", args.seq, args.batch, "train")
    opt_cfg = adamw.AdamWConfig(lr=args.lr, total_steps=args.steps, warmup_steps=max(args.steps // 20, 5))

    step_fn, in_sh, out_sh, structs, extra = build_train_step(
        cfg, mesh, cell, opt_cfg=opt_cfg, microbatches=1
    )
    jstep = jax.jit(step_fn, in_shardings=in_sh, out_shardings=out_sh,
                    donate_argnums=extra["donate_argnums"])

    from repro.distributed.sharding import use_rules
    from repro.models import Model

    model = Model(cfg)
    data = SyntheticLMData(
        DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq, global_batch=args.batch)
    )

    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    start_step = 0
    state = None
    it_state = None
    if ckpt and ckpt.latest_step() is not None:
        with use_rules(extra["rules"]):
            params = model.init(jax.random.PRNGKey(0))
        proto = adamw.init_state(params)
        state, extra_meta = ckpt.restore(like=proto)
        start_step = int(extra_meta.get("step", 0))
        it_state = IteratorState.from_dict(extra_meta["iterator"]) if "iterator" in extra_meta else None
        print(f"[train] resumed from step {start_step}", flush=True)
    if state is None:
        with use_rules(extra["rules"]):
            params = model.init(jax.random.PRNGKey(0))
        state = adamw.init_state(params)

    it = PrefetchIterator(data, state=it_state)
    monitor = StepMonitor()

    def emergency_save():
        if ckpt:
            ckpt.save(start_step, state, extra={"step": start_step, "iterator": it.state.to_dict()})
            print("[train] SIGTERM: checkpoint saved", flush=True)

    install_sigterm_save(emergency_save)

    losses, step_seconds = [], []
    for step in range(start_step, args.steps):
        batch = next(it)
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        monitor.start_step()
        t0 = time.perf_counter()
        state, metrics = jstep(state, batch)
        loss = float(metrics["loss"])
        step_seconds.append(time.perf_counter() - t0)
        monitor.end_step(step)
        losses.append(loss)
        if step % args.log_every == 0 or step == args.steps - 1:
            print(
                f"[train] step={step} loss={loss:.4f} "
                f"lr={float(metrics['lr']):.2e} gnorm={float(metrics['grad_norm']):.3f}",
                flush=True,
            )
        if ckpt and step > 0 and step % args.ckpt_every == 0:
            ckpt.save(step, state, extra={"step": step, "iterator": it.state.to_dict()})
        if monitor.should_evict:
            print("[train] persistent straggler: checkpoint + exit for reschedule", flush=True)
            emergency_save()
            return {"rc": 17, "losses": losses, "step_seconds": step_seconds,
                    "step": jstep, "structs": structs}
    if ckpt:
        ckpt.save(args.steps, state, extra={"step": args.steps, "iterator": it.state.to_dict()})
    it.close()
    print(f"[train] done. first loss {losses[0]:.4f} -> last {losses[-1]:.4f}", flush=True)
    return {"rc": 0 if losses[-1] < losses[0] else 2, "losses": losses,
            "step_seconds": step_seconds, "step": jstep, "structs": structs}


def train(argv=None) -> int:
    return run(argv)["rc"]


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(train())
