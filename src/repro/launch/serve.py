"""Serving launcher: continuous batching over the paged KV cache.

Drives :class:`repro.serving.PagedServingEngine` end to end — prompts are
admitted into fixed batch slots between decode steps, prefill runs through
the fused flash kernel, decode runs through the split-KV paged flash-
decoding kernel, and finished requests release their pages immediately
(``--mode paged``, the default).  ``--mode dense`` keeps the plain
dense-cache batched loop (:func:`generate`) as the reference path: one
prefill, then one cache-append + attend per token — never a prompt re-run.
Configs whose layer stacks cannot back a paged cache (sliding-window, SSM,
encoder-decoder) fall back from ``--mode paged`` to dense with a warning
instead of dying (typed ``UnsupportedCacheError``).

Resilience surfaces (docs/serving.md "Resilience"):

* ``--policy optimistic`` admits on current free pages and recovers from
  pool pressure by recompute preemption (default ``reserved`` keeps the
  worst-case-reservation guarantee).
* ``--guard`` compiles the engine with the ``sfu.guard`` clamp/finite
  counters and the non-finite degradation re-run.
* ``--deadline-ticks N`` gives every request an N-decode-step budget.
* ``--chaos SEED`` runs the seeded chaos session (allocator exhaustion +
  NaN injection + one deadline expiry) against a fault-free reference run
  and exits non-zero unless every non-faulted request is byte-identical
  and the health summary reports the injected incidents — the CI
  ``chaos-smoke`` contract.

The ``--plan`` surface is unchanged: pass an ActivationPlan JSON to pin
exactly which sites run PWL/fused, ``--dump-plan`` to record the plan a
run used.
"""
from __future__ import annotations

import argparse
import sys
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro import sfu
from repro.configs import get_config, get_reduced_config
from repro.models import Model


def generate(model: Model, params, prompts: jnp.ndarray, max_new: int = 32):
    """Greedy-decode ``max_new`` tokens for a batch of prompts over a DENSE
    per-request cache: prefill once, then one ``decode_step`` per token
    (each step appends the token's K/V at its position and attends the
    valid prefix — the prompt is never recomputed)."""
    B, S = prompts.shape
    cache = model.make_cache(B, max_len=S + max_new)
    logits, cache = jax.jit(model.prefill)(params, prompts, cache)
    out = []
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    decode = jax.jit(model.decode_step)
    for i in range(max_new):
        out.append(tok)
        logits, cache = decode(params, tok, cache, jnp.int32(S + i))
        tok = jnp.argmax(logits[:, -1:, :], axis=-1)[..., 0][:, None].astype(jnp.int32)
    return jnp.concatenate(out, axis=1)


def _serve_dense(model: Model, params, prompts: np.ndarray, args) -> dict:
    t0 = time.time()
    toks = generate(model, params, jnp.asarray(prompts), max_new=args.max_new)
    dt = time.time() - t0
    n = len(prompts) * args.max_new
    print(f"[serve] generated {n} tokens in {dt:.2f}s ({n/dt:.1f} tok/s)")
    print("[serve] sample:", np.asarray(toks[0, :12]))
    return {"rc": 0, "mode": "dense", "seconds": dt}


def _make_engine(model: Model, params, args, *, policy=None, guard=None,
                 faults=None):
    from repro.serving import PagedServingEngine

    return PagedServingEngine(
        model, params,
        max_slots=args.max_slots,
        page_size=args.page_size,
        max_context=args.prompt_len + args.max_new + args.page_size,
        policy=policy if policy is not None else args.policy,
        guard=args.guard if guard is None else guard,
        faults=faults,
    )


def _print_health(health: dict) -> None:
    print(f"[serve] health: policy={health['policy']} "
          f"guard={health['guard']} preemptions={health['preemptions']} "
          f"replayed_prefill_tokens={health['replayed_prefill_tokens']} "
          f"timeouts={health['timeouts']} retries={health['step_retries']} "
          f"dropped_ticks={health['dropped_ticks']}")
    if health["clamped"]:
        print(f"[serve] health: clamped-per-site {health['clamped']}")
    if health["nonfinite_recoveries"]:
        print(f"[serve] health: nonfinite recoveries "
              f"{health['nonfinite_recoveries']}")
    for rec in health["rejected"]:
        # rejected requests are surfaced per-request; the session lives on
        print(f"[serve] rejected {rec['request_id']}: {rec['reason']}",
              file=sys.stderr)
    for inc in health["incidents"]:
        print(f"[serve] incident: {inc}")


def _serve_paged(model: Model, params, prompts: np.ndarray, args) -> dict:
    """One continuous-batching session.  Returns ``rc`` (1 on any fused
    fallback), the ``engine`` (its jitted steps, cache and health stay
    inspectable), the ``results``, the session's wall ``seconds`` and the
    fallback ``warnings``."""
    from repro.serving import GenRequest, UnsupportedCacheError

    try:
        engine = _make_engine(model, params, args)
    except UnsupportedCacheError as e:
        warnings.warn(f"paged serving unsupported for arch {args.arch!r}: "
                      f"{e}; falling back to --mode dense")
        print(f"[serve] paged cache unsupported ({e}); running dense mode",
              file=sys.stderr)
        return _serve_dense(model, params, prompts, args)
    requests = [
        GenRequest(request_id=f"req{i}", prompt=list(map(int, prompts[i])),
                   max_new_tokens=args.max_new,
                   deadline_ticks=args.deadline_ticks)
        for i in range(len(prompts))
    ]
    sfu.reset_all_warnings()
    t0 = time.time()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        results = engine.run(
            requests,
            on_result=lambda r: print(
                f"[serve]   {r.request_id}: {len(r.tokens)} tokens "
                f"({r.finish_reason}), steps {r.admitted_at_step}"
                f"-{r.finished_at_step}"
            ),
        )
    dt = time.time() - t0
    fallbacks = [str(w.message) for w in caught
                 if "fused" in str(w.message).lower()]
    print(f"[serve] {len(results)} requests, {engine.generated} tokens in "
          f"{dt:.2f}s ({engine.generated / dt:.1f} tok/s, "
          f"{engine.decode_steps} batched decode steps, "
          f"{engine.sched.allocator.num_free} pages free at exit)")
    by_id = {r.request_id: r for r in results}
    print("[serve] sample:", by_id["req0"].tokens[:12])
    _print_health(engine.health_summary())
    print(f"[serve] fused fallbacks during session: {len(fallbacks)}")
    # a fused plan that silently fell back mid-session is a perf
    # regression CI must catch, not a warning to scroll past
    for msg in fallbacks:
        print(f"[serve]   fallback: {msg}", file=sys.stderr)
    return {"rc": 1 if fallbacks else 0, "mode": "paged", "engine": engine,
            "requests": requests, "results": results, "seconds": dt,
            "warnings": fallbacks}


def _serve_chaos(model: Model, params, prompts: np.ndarray, args, cfg) -> int:
    """Seeded chaos session (CI ``chaos-smoke``): inject allocator
    exhaustion + one NaN at the MLP plan site, expire one request's
    deadline, and require (a) no crash, (b) every non-faulted request
    byte-identical to a fault-free reference run, (c) the injected
    incidents visible in the health summary, (d) zero fused fallbacks."""
    from repro.serving import FaultInjector, GenRequest, chaos_specs

    nan_site = sfu.site_key(sfu.SITE_MLP, cfg.activation)
    victim = f"req{len(prompts) - 1}"

    def make_requests(with_deadline: bool):
        reqs = []
        for i in range(len(prompts)):
            rid = f"req{i}"
            deadline = 2 if (with_deadline and rid == victim) else None
            reqs.append(GenRequest(
                request_id=rid, prompt=list(map(int, prompts[i])),
                max_new_tokens=args.max_new, deadline_ticks=deadline))
        return reqs

    # fault-free reference (same policy/guard/pages: only the faults and the
    # victim's deadline differ)
    ref_engine = _make_engine(model, params, args, policy="optimistic",
                              guard=True)
    ref = {r.request_id: list(r.tokens)
           for r in ref_engine.run(make_requests(False))}

    injector = FaultInjector(
        chaos_specs(args.chaos, nan_site, max_step=max(2, args.max_new - 1)))
    engine = _make_engine(model, params, args, policy="optimistic",
                          guard=True, faults=injector)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        results = engine.run(make_requests(True))
    health = engine.health_summary()
    _print_health(health)

    failures = []
    fallbacks = [str(w.message) for w in caught
                 if "fused" in str(w.message).lower()]
    if fallbacks:
        failures.append(f"fused fallbacks during chaos session: {fallbacks}")
    by_id = {r.request_id: r for r in results}
    if set(by_id) != {f"req{i}" for i in range(len(prompts))}:
        failures.append(f"missing results: got {sorted(by_id)}")
    else:
        if by_id[victim].finish_reason != "timeout":
            failures.append(
                f"deadline victim {victim} finished "
                f"{by_id[victim].finish_reason!r}, expected 'timeout'")
        for rid, res in sorted(by_id.items()):
            if rid == victim:
                continue
            if list(res.tokens) != ref[rid]:
                failures.append(
                    f"{rid} diverged from the fault-free run: "
                    f"{res.tokens} != {ref[rid]}")
    fired_kinds = {f["kind"] for f in health["faults_fired"]}
    if fired_kinds != {"alloc_exhaust", "nan"}:
        failures.append(f"injected faults did not all fire: {fired_kinds}")
    if health["preemptions"] < 1:
        failures.append("injected allocator exhaustion caused no preemption")
    if not health["nonfinite_recoveries"]:
        failures.append("NaN injection was not recovered by the guard")
    if health["timeouts"] < 1:
        failures.append("deadline expiry produced no timeout")
    incident_kinds = {i["kind"] for i in health["incidents"]}
    for want in ("preemption", "nan_injected", "nonfinite_output",
                 "deadline_expired"):
        if want not in incident_kinds:
            failures.append(f"health summary missing incident kind {want!r}")

    print(f"[serve] chaos seed {args.chaos}: "
          f"{len(results)} results, faults fired: {sorted(fired_kinds)}")
    if failures:
        for msg in failures:
            print(f"[serve] CHAOS FAILURE: {msg}", file=sys.stderr)
        return 1
    print("[serve] chaos session OK: non-faulted requests byte-identical, "
          "incidents recorded")
    return 0


def run(argv=None) -> dict:
    """Parse ``argv`` and serve; returns the session record (``rc``,
    ``mode``, and for ``--mode paged`` the engine and its results)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="repro-100m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="number of requests to serve")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--mode", choices=("paged", "dense"), default="paged",
                    help="paged: continuous batching over the paged KV cache "
                    "(repro.serving); dense: static-batch dense-cache loop")
    ap.add_argument("--max-slots", type=int, default=4,
                    help="[paged] concurrent batch slots (fixed decode shape)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="[paged] tokens per KV page")
    ap.add_argument("--policy", choices=("reserved", "optimistic"),
                    default="reserved",
                    help="[paged] admission policy: reserved = worst-case "
                    "page reservation (grow can never fail); optimistic = "
                    "admit on current free pages, recover by recompute "
                    "preemption")
    ap.add_argument("--guard", action="store_true",
                    help="[paged] enable sfu.guard clamp/finite counters and "
                    "non-finite degradation re-runs")
    ap.add_argument("--deadline-ticks", type=int, default=None,
                    help="[paged] per-request decode-step budget; overdue "
                    "requests finish with reason 'timeout'")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="[paged] run the seeded fault-injection session "
                    "(allocator exhaustion + NaN + one deadline expiry) "
                    "against a fault-free reference; rc!=0 on any parity or "
                    "health-summary failure")
    ap.add_argument(
        "--plan", default=None, metavar="PATH",
        help="load an ActivationPlan JSON (repro.sfu); default: the fused "
        "PWL plan compiled from the arch config",
    )
    ap.add_argument(
        "--dump-plan", default=None, metavar="PATH",
        help="write the exact activation plan this run uses as JSON",
    )
    # removed flag, kept one release as a hard error with a pointer
    ap.add_argument("--act-impl", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.act_impl is not None:
        ap.error(
            "--act-impl was removed: pass --plan <plan.json> instead "
            "(dump one with --dump-plan or sfu.dump_plan(sfu.compile_plan("
            "cfg), path); see docs/plans.md)"
        )
    if args.chaos is not None and args.mode != "paged":
        ap.error("--chaos requires --mode paged")

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
        # fused by default: serving is the subsystem the fused kernels were
        # built for, and _serve_paged turns any silent fallback into rc=1
        cfg = getter(args.arch, act_impl="fused")
    plan = sfu.plan_for(cfg)
    print(f"[serve] activation plan {plan.fingerprint}: "
          f"{ {k: s.impl for k, s in plan.items()} }")
    if args.dump_plan:
        print(f"[serve] plan -> {sfu.dump_plan(plan, args.dump_plan)}")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0, cfg.vocab_size
    ), dtype=np.int32)

    if args.mode == "paged":
        if args.chaos is not None:
            return {"rc": _serve_chaos(model, params, prompts, args, cfg),
                    "mode": "chaos"}
        return _serve_paged(model, params, prompts, args)
    return _serve_dense(model, params, prompts, args)


def serve(argv=None) -> int:
    return run(argv)["rc"]


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(serve())
