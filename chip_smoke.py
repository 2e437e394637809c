#!/usr/bin/env python3
"""Bring-up smoke of the main path on a TPU, through the normal entry points.

    python chip_smoke.py              # one chip: olmo-1b serving, repro-100m training
    python chip_smoke.py --chips 4    # four chips: olmoe-1b-7b serving on a (1, 4) mesh

Run it from the root of a checkout.  The phases, in one process:

* device -- JAX must find a TPU; there is no CPU fallback.
* serve  -- olmo-1b at its published widths through ``repro.launch.serve
  --mode paged`` with the fused plan (fused SwiGLU epilogue, fused PWL
  softmax in prefill, split-KV paged decode, both pool-write kernels), a
  cold session
  and a warm one; its prefill logits are compared with the ``jnp`` plan (the
  same PWL tables, no Pallas).
* flash  -- the fused flash-attention kernel at olmo-1b's head widths,
  forward against the dense fused softmax and fused backward against the
  jnp recompute.  The launchers take it only for prompts past ~2.9k tokens.
* train  -- repro-100m at its published widths through ``repro.launch.train``
  with a fused plan and the fused backward kernels.
* mesh   -- only with ``--chips 4``, and then alone: olmoe-1b-7b at its
  published widths served by ``PagedServingEngine(rules=...)`` on a
  (data=1, model=4) mesh, parameters and KV pools made sharded, compared with
  the ``jnp`` plan on the same mesh.

A phase fails on a request that does not finish with ``length``, a fused
fallback, a uniform-breakpoint table, a guard recovery, a step that holds no
Pallas kernel, a disagreement with the ``jnp`` plan beyond its tolerance, or
a training loss that is not finite and falling.  Every line of stdout but the
last is a report; the last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
import warnings

ROOT = pathlib.Path(__file__).resolve().parent
OUT = ROOT / "chiprun_out" / "chip_smoke"

# warnings that mean the device path was not the one planned
BAD_WARNINGS = ("falling back", "uniform-breakpoint", "non-finite")

# Prefill logits, fused plan against jnp plan (same tables, same params).
# Both round activations to bf16 at every layer, but at different points: the
# fused kernels keep f32 in VMEM and round once per kernel, the jnp path
# rounds each matmul output.  One bf16 rounding is 2^-9 relative; over 16
# layers of residual updates a few such roundings per layer compound to
# percent level of the logit scale.  A wrong page, mask or table moves logits
# by their own scale, so 5% of max |logit| separates the two.
LOGIT_TOL_REL = 0.05

# One attention layer, flash kernel against the dense fused softmax (forward)
# and its fused backward against the jnp recompute: the same math summed in
# other orders, outputs rounded to bf16, whose ulp is 2^-8 relative at the top
# of a binade.  2% of the largest magnitude is a few such ulps; a wrong mask or
# block moves outputs by their own scale.
FLASH_TOL_REL = 0.02


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class Failed(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise Failed(what)


class CompileMeter:
    """Seconds of backend (XLA and Mosaic) compilation, the part the
    persistent cache saves: a hit counts its retrieval time.  Tracing and
    lowering are left out, since nested jits report them once per level."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self):
        return self.seconds, self.cache_hits

    def since(self, mark):
        return self.seconds - mark[0], self.cache_hits - mark[1]


def check_warnings(caught, phase: str) -> None:
    bad = [str(w.message) for w in caught
           if any(b in str(w.message) for b in BAD_WARNINGS)]
    for msg in bad:
        log(f"{phase}: warning: {msg}")
    check(not bad, f"{phase}: {len(bad)} fallback/guard warnings")


def check_tables(plan, phase: str) -> None:
    """Every PWL table the plan needs loads from its fitted artifact."""
    from repro import sfu

    store = sfu.get_store()
    for key, spec in plan.items():
        if spec.impl == "exact":
            continue
        fn, n_bp, _, fit = spec.table_key
        path = store.artifact_path(fn, n_bp, fit)
        check(path.exists(), f"{phase}: no fitted table for {key} at {path}")


def pallas_calls(jitted, *args) -> int:
    """Pallas (Mosaic) kernels in the compiled program of ``jitted(*args)``."""
    return jitted.lower(*args).compile().as_text().count("tpu_custom_call")


def peak_hbm_gib(devices) -> list[float]:
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append(round(stats.get("peak_bytes_in_use", 0) / 2**30, 3))
    return out


def step_args(engine, prompt):
    """Prefill arguments for one prompt on free pages of ``engine``."""
    import jax.numpy as jnp
    import numpy as np

    n = len(prompt)
    bucket = max(engine.page_size, 1 << (n - 1).bit_length())
    npg = bucket // engine.page_size
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :n] = prompt
    row = np.arange(1, npg + 1, dtype=np.int32)[None]
    return (jnp.asarray(toks), engine.cache, jnp.asarray(row),
            jnp.asarray([n], jnp.int32))


def compare_prefill(engine_f, engine_j, prompts, vocab: int, phase: str):
    """Prefill logits at the last prompt position, fused plan vs jnp plan."""
    import numpy as np

    worst, agree, ties = 0.0, 0, 0
    for i, prompt in enumerate(prompts):
        args = step_args(engine_f, prompt)
        lf = np.asarray(engine_f._fns["prefill"](engine_f.params, *args)[0],
                        np.float32)[0, 0, :vocab]
        lj = np.asarray(engine_j._fns["prefill"](engine_j.params, *args)[0],
                        np.float32)[0, 0, :vocab]
        check(np.all(np.isfinite(lf)) and np.all(np.isfinite(lj)),
              f"{phase}: non-finite prefill logits for request {i}")
        scale = float(np.max(np.abs(lj)))
        delta = float(np.max(np.abs(lf - lj)))
        worst = max(worst, delta / scale)
        top2 = np.sort(lj)[-2:]
        gap = float(top2[1] - top2[0])
        same = int(np.argmax(lf)) == int(np.argmax(lj))
        log(f"{phase}: request {i}: max|dlogit| {delta:.5g} "
            f"(max|logit| {scale:.5g}), jnp top-2 gap {gap:.5g}, "
            f"top-1 {'agrees' if same else 'differs'}")
        check(delta <= LOGIT_TOL_REL * scale,
              f"{phase}: request {i} logits differ by {delta:.5g} > "
              f"{LOGIT_TOL_REL} x {scale:.5g}")
        if same:
            agree += 1
        else:
            # a top-1 may only differ where the jnp plan itself has a tie
            # within the measured difference
            check(gap <= 2 * delta,
                  f"{phase}: request {i} top-1 differs with a top-2 gap "
                  f"{gap:.5g} > 2 x max|dlogit| {delta:.5g}")
            ties += 1
    log(f"{phase}: fused vs jnp prefill: worst max|dlogit|/max|logit| "
        f"{worst:.5g} (tolerance {LOGIT_TOL_REL}), top-1 agrees on "
        f"{agree}/{len(prompts)} requests, {ties} at near-ties")
    return worst, agree


def check_results(results, n: int, max_new: int, phase: str):
    check(len(results) == n, f"{phase}: {len(results)} results for {n} requests")
    for r in results:
        check(r.finish_reason == "length" and len(r.tokens) == max_new,
              f"{phase}: {r.request_id} finished {r.finish_reason!r} with "
              f"{len(r.tokens)} tokens")


def check_health(engine, phase: str) -> None:
    h = engine.health_summary()
    check(not h["nonfinite_recoveries"] and not h["incidents"],
          f"{phase}: guard recoveries {h['nonfinite_recoveries']}, "
          f"incidents {h['incidents']}")


# ---------------------------------------------------------------------------
# phases


def serve_phase(meter):
    """olmo-1b paged serving through ``repro.launch.serve``."""
    import jax
    import numpy as np

    from repro import sfu
    from repro.configs import get_config
    from repro.launch import serve
    from repro.models import Model
    from repro.serving import GenRequest, PagedServingEngine

    arch, requests, prompt_len, max_new, slots, page_size = (
        "olmo-1b", 6, 256, 24, 4, 128)
    cfg = get_config(arch, act_impl="fused", pwl_softmax=True)
    plan = sfu.plan_for(cfg)
    check(all(s.impl == "fused" for _, s in plan.items()),
          f"serve: plan is not fused everywhere: {plan}")
    check_tables(plan, "serve")
    plan_path = sfu.dump_plan(plan, OUT / f"{arch}_fused_plan.json")
    log(f"serve: {arch} d_model={cfg.d_model} layers={cfg.n_layers} "
        f"heads={cfg.n_heads} d_ff={cfg.d_ff} vocab={cfg.vocab_size}, "
        f"{requests} requests x {prompt_len} prompt + {max_new} new tokens, "
        f"{slots} slots, page {page_size}")

    argv = ["--arch", arch, "--mode", "paged", "--plan", str(plan_path),
            "--batch", str(requests), "--prompt-len", str(prompt_len),
            "--max-new", str(max_new), "--max-slots", str(slots),
            "--page-size", str(page_size)]
    mark = meter.mark()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        out = serve.run(argv)
    check_warnings(caught, "serve")
    check(out["rc"] == 0, f"serve: rc {out['rc']} (fused fallbacks "
          f"{out.get('warnings')})")
    check(out["mode"] == "paged", f"serve: ran {out['mode']!r}, not paged")
    cold_compile, cold_hits = meter.since(mark)
    engine = out["engine"]
    check_results(out["results"], requests, max_new, "serve")
    check_health(engine, "serve")
    cold = {r.request_id: r.tokens for r in out["results"]}
    log(f"serve: cold session {out['seconds']:.3f}s, compile "
        f"{cold_compile:.3f}s ({cold_hits} persistent-cache hits)")

    prompts = [r.prompt for r in out["requests"]]
    warm_reqs = [GenRequest(f"warm{i}", p, max_new_tokens=max_new)
                 for i, p in enumerate(prompts)]
    mark = meter.mark()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        gen0 = engine.generated
        t0 = time.perf_counter()
        warm = [r for r in engine.run(warm_reqs)
                if r.request_id.startswith("warm")]
        warm_s = time.perf_counter() - t0
        tokens = engine.generated - gen0
    check_warnings(caught, "serve")
    warm_compile, _ = meter.since(mark)
    check_results(warm, requests, max_new, "serve warm")
    check_health(engine, "serve")
    for r in warm:
        check(r.tokens == cold[f"req{r.request_id[4:]}"],
              f"serve: warm {r.request_id} tokens differ from the cold run")
    log(f"serve: warm session {tokens} tokens in {warm_s:.4f}s = "
        f"{tokens / warm_s:.2f} tok/s (compile inside it {warm_compile:.3f}s)")

    n_cols = engine.max_cols
    dec = (jax.numpy.asarray(np.zeros((slots, 1), np.int32)), engine.cache,
           jax.numpy.asarray(np.zeros((slots, n_cols), np.int32)),
           jax.numpy.asarray(np.zeros((slots,), np.int32)))
    n_dec = pallas_calls(engine._fns["decode"], engine.params, *dec)
    n_pre = pallas_calls(engine._fns["prefill"], engine.params,
                         *step_args(engine, prompts[0]))
    log(f"serve: Pallas calls in the compiled decode step {n_dec}, "
        f"prefill step {n_pre}")
    # decode: pool append, split-KV decode, fused GLU; prefill: prompt write,
    # flash attention, fused GLU
    check(n_dec >= 3 and n_pre >= 3, "serve: steps hold too few Pallas calls")

    cfg_j = get_config(arch, act_impl="jnp", pwl_softmax=True)
    engine_j = PagedServingEngine(
        Model(cfg_j), engine.params, max_slots=slots, page_size=page_size,
        max_context=engine.max_cols * page_size)
    worst, agree = compare_prefill(engine, engine_j, prompts, cfg.vocab_size,
                                   "serve")
    return {"tok_s": tokens / warm_s, "cold_compile_s": cold_compile,
            "decode_pallas_calls": n_dec, "prefill_pallas_calls": n_pre,
            "worst_rel_dlogit": worst, "top1_agree": agree}


def flash_phase(meter):
    """The fused flash-attention kernel at olmo-1b's head widths, forward and
    fused backward.  The launchers pick it over the dense fused-softmax kernel
    only past ``layers.DENSE_FUSED_SOFTMAX_MAX_SCORES`` scores (prompts of
    ~2.9k tokens at 16 heads), which the serve and train phases stay under;
    this phase runs it on the chip directly."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import sfu
    from repro.configs import get_config
    from repro.kernels import fused
    from repro.models import layers

    seq = 512
    cfg = get_config("olmo-1b", act_impl="fused", pwl_softmax=True)
    table = sfu.plan_for(cfg).fused_table(sfu.site_key(sfu.SITE_SOFTMAX,
                                                       "exp"))
    shape = (1, seq, cfg.n_heads, cfg.resolved_head_dim)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, g = (jax.random.normal(key, shape).astype(jnp.bfloat16)
                  for key in keys)

    def flash(q, k, v, impl_bwd="fused"):
        return fused.fused_flash_attention(q, k, v, table=table, causal=True,
                                           impl_bwd=impl_bwd)

    def grads(impl_bwd):
        return jax.jit(lambda q, k, v: jax.vjp(
            lambda *a: flash(*a, impl_bwd=impl_bwd), q, k, v)[1](g))

    def rel(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        check(np.all(np.isfinite(a)), "flash: non-finite output")
        return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))

    mark = meter.mark()
    jflash = jax.jit(flash)
    y = jflash(q, k, v)
    y_dense = jax.jit(lambda q, k, v: layers.dense_pwl_attention(
        q, k, v, table=table, causal=True))(q, k, v)
    d_fwd = rel(y, y_dense)
    d_bwd = max(rel(a, b) for a, b in zip(grads("fused")(q, k, v),
                                          grads("recompute")(q, k, v)))
    compile_s, _ = meter.since(mark)
    n_fwd = pallas_calls(jflash, q, k, v)
    n_bwd = pallas_calls(grads("fused"), q, k, v)
    log(f"flash: {shape} bf16 causal: forward vs dense fused softmax "
        f"max|d|/max {d_fwd:.5g}, fused vs recompute backward {d_bwd:.5g} "
        f"(tolerance {FLASH_TOL_REL}); Pallas calls forward {n_fwd}, "
        f"backward {n_bwd}; compile {compile_s:.3f}s")
    check(n_fwd >= 1 and n_bwd >= 2, "flash: too few Pallas calls")
    check(d_fwd <= FLASH_TOL_REL and d_bwd <= FLASH_TOL_REL,
          f"flash: disagreement beyond {FLASH_TOL_REL}")
    return {"fwd_rel": d_fwd, "bwd_rel": d_bwd}


def train_phase(meter):
    """repro-100m training through ``repro.launch.train``."""
    import math

    from repro import sfu
    from repro.configs import get_config
    from repro.launch import train

    arch, steps, batch, seq = "repro-100m", 8, 8, 1024
    cfg = get_config(arch, act_impl="fused", pwl_softmax=True)
    plan = sfu.plan_for(cfg)
    check_tables(plan, "train")
    plan_path = sfu.dump_plan(plan, OUT / f"{arch}_fused_plan.json")
    log(f"train: {arch} d_model={cfg.d_model} layers={cfg.n_layers} "
        f"mlp={cfg.mlp_type} vocab={cfg.vocab_size}, {steps} steps of "
        f"batch {batch} x seq {seq}")
    argv = ["--arch", arch, "--steps", str(steps), "--batch", str(batch),
            "--seq", str(seq), "--plan", str(plan_path), "--impl-bwd",
            "fused", "--log-every", "1"]
    mark = meter.mark()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        out = train.run(argv)
    check_warnings(caught, "train")
    compile_s, hits = meter.since(mark)
    losses = out["losses"]
    check(len(losses) == steps and all(math.isfinite(x) for x in losses),
          f"train: losses {losses}")
    check(losses[-1] < losses[0] and out["rc"] == 0,
          f"train: loss did not fall: {losses}")
    n_step = pallas_calls(out["step"], *out["structs"])
    check(n_step >= 2, f"train: the step holds {n_step} Pallas calls")
    warm = out["step_seconds"][1:]
    log(f"train: losses {[round(x, 5) for x in losses]}")
    log(f"train: compile {compile_s:.3f}s ({hits} persistent-cache hits), "
        f"warm step mean {sum(warm) / len(warm):.4f}s, Pallas calls in the "
        f"compiled step {n_step}")
    return {"losses": losses, "compile_s": compile_s}


def mesh_phase(meter):
    """olmoe-1b-7b paged serving on a (data=1, model=4) mesh, sharded end to
    end."""
    import jax
    import numpy as np

    from repro import sfu
    from repro.configs import get_config
    from repro.distributed.sharding import make_rules, use_rules
    from repro.launch.mesh import make_mesh
    from repro.models import Model
    from repro.serving import GenRequest, PagedServingEngine

    arch, requests, prompt_len, max_new, slots, page_size, shape = (
        "olmoe-1b-7b", 4, 256, 16, 4, 128, (1, 4))
    cfg = get_config(arch, act_impl="fused", pwl_softmax=True)
    check_tables(sfu.plan_for(cfg), "mesh")
    mesh = make_mesh(shape, ("data", "model"))
    rules = make_rules(cfg, mesh)
    tp = shape[1]
    log(f"mesh: {arch} on {dict(mesh.shape)}: {cfg.n_experts // tp} of "
        f"{cfg.n_experts} experts and {cfg.n_heads // tp} of {cfg.n_heads} "
        f"heads per chip, {requests} requests x {prompt_len} prompt + "
        f"{max_new} new tokens")
    check(rules.table["experts"] == "model" and rules.table["cache_kv"] == "model"
          and cfg.n_experts % tp == 0 and cfg.n_kv_heads % tp == 0,
          f"mesh: rules do not shard experts and KV heads: {rules.table}")

    with use_rules(rules):
        params = Model(cfg).init(jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(params)
    total = sum(p.nbytes for p in leaves)
    replicated = sum(p.nbytes for p in leaves if p.sharding.is_fully_replicated)
    on_chip0 = sum(p.addressable_shards[0].data.nbytes for p in leaves)
    peak0 = peak_hbm_gib(mesh.devices.flat[:1])[0]
    log(f"mesh: params {total / 2**30:.3f} GiB in all, {replicated / 2**30:.4f} "
        f"GiB replicated, {on_chip0 / 2**30:.3f} GiB on chip 0 (peak HBM "
        f"{peak0} GiB)")
    check(on_chip0 <= (total - replicated) / tp + replicated,
          "mesh: sharded parameters hold more than a 1/tp share on chip 0")
    # only norms and the router are replicated, and the whole model never
    # passed through one chip
    check(replicated < 0.01 * total and peak0 * 2**30 < total / 2,
          "mesh: parameters were not made sharded")

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=prompt_len).tolist()
               for _ in range(requests)]
    max_context = prompt_len + max_new + page_size
    engine = PagedServingEngine(Model(cfg), params, max_slots=slots,
                                page_size=page_size, max_context=max_context,
                                rules=rules)
    pool = jax.tree_util.tree_leaves(engine.cache)[0]
    check(pool.addressable_shards[0].data.shape[1] == cfg.n_kv_heads // tp,
          f"mesh: KV pool not sharded over heads: {pool.sharding}")
    mark = meter.mark()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        t0 = time.perf_counter()
        results = engine.run([GenRequest(f"r{i}", p, max_new_tokens=max_new)
                              for i, p in enumerate(prompts)])
        dt = time.perf_counter() - t0
    check_warnings(caught, "mesh")
    compile_s, hits = meter.since(mark)
    check_results(results, requests, max_new, "mesh")
    check_health(engine, "mesh")
    log(f"mesh: session {engine.generated} tokens in {dt:.3f}s including "
        f"compile {compile_s:.3f}s ({hits} persistent-cache hits)")
    n_dec = pallas_calls(
        engine._fns["decode"], engine.params,
        jax.numpy.zeros((slots, 1), jax.numpy.int32), engine.cache,
        jax.numpy.zeros((slots, engine.max_cols), jax.numpy.int32),
        jax.numpy.zeros((slots,), jax.numpy.int32))
    log(f"mesh: Pallas calls in the compiled decode step {n_dec}")
    check(n_dec >= 3, "mesh: decode step holds too few Pallas calls")

    cfg_j = get_config(arch, act_impl="jnp", pwl_softmax=True)
    engine_j = PagedServingEngine(Model(cfg_j), params, max_slots=slots,
                                  page_size=page_size, max_context=max_context,
                                  rules=rules)
    worst, agree = compare_prefill(engine, engine_j, prompts, cfg.vocab_size,
                                   "mesh")
    return {"worst_rel_dlogit": worst, "top1_agree": agree}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve + train phases on one chip; 4: the mesh "
                    "phase alone on four chips")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}; run from "
              "the root of a checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    from repro.kernels._backend import should_interpret
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    log(f"device: {device}, jax {jax.__version__}, compile cache {cache_dir}")
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1
    if should_interpret():
        print("chip_smoke: Pallas kernels would run in interpret mode",
              file=sys.stderr)
        return 1
    OUT.mkdir(parents=True, exist_ok=True)
    meter = CompileMeter()
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            summary = {"mesh": mesh_phase(meter)}
        else:
            summary = {"serve": serve_phase(meter)}
            log(f"peak HBM after serve (GiB): {peak_hbm_gib(devices[:1])}")
            summary["flash"] = flash_phase(meter)
            summary["train"] = train_phase(meter)
        log(f"peak HBM (GiB): {peak_hbm_gib(devices[:args.chips])}")
    except Failed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s, compile "
        f"{meter.seconds:.3f}s in all")
    (OUT / f"summary_{args.chips}chip.json").write_text(
        json.dumps({"device": device, **summary}, indent=1) + "\n")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
