"""PagedServingEngine: device half of the serving stack.

Owns the page pools, the host page-table / kv_len mirrors, and the jitted
model entry points; drives :class:`~repro.serving.scheduler.
ContinuousBatchingScheduler` through the admit -> prefill -> decode ->
evict loop.  Two shape disciplines keep the whole session on a handful of
compiled programs instead of one per admission:

* **bucketed prefill** — prompts run one-at-a-time (B=1) padded to the
  next power-of-two multiple of the page size, so a mixed workload
  compiles one prefill program per bucket (log2 many), not per length.
  Pad positions write K/V into pages past the prompt's allocation — i.e.
  into the sentinel page — and are never attended (position >= kv_len).
* **bucketed decode columns** — every decode step runs ALL ``max_slots``
  batch slots at a fixed shape; only the page-table *width* varies, and it
  is bucketed to the next power of two over the widest live request.  This
  is what makes decode work scale with the *live* cache: a pool sized for
  500k tokens serving 2k-token requests dispatches a grid over
  ceil(2k/page) columns, and admission/eviction never triggers a
  recompile (it only rewrites one table row).

Inactive slots are encoded entirely in data: an all-sentinel table row and
``kv_len == 0``.  Their decode lane appends into the sentinel page, reads
back one garbage row, and produces logits the scheduler never samples —
dead lanes cost one page of work each, the price of a fixed batch shape.

Pass ``rules`` (a :class:`repro.distributed.sharding.Rules` with a mesh) to
serve sharded: the jitted prefill/decode entry points activate the rules,
so every fused Pallas kernel — prompt/append page writes, flash prefill,
split-KV paged decode — runs per-shard inside shard_map (KV-head / query-
head dims over the model axis, pools replicated over data; see
docs/distributed.md).  Each ``run()`` session resets every warn-once
latch first, so a session that falls back (or degrades) reports it even
when a previous session on the same process already warned.

Resilience (docs/serving.md "Resilience"; ISSUE 10):

* ``policy="optimistic"`` admits on current free pages; a dry pool at
  :meth:`ContinuousBatchingScheduler.grow` raises ``PagePoolExhausted``
  and the engine preempts the *youngest* active request — its pages are
  freed, the request re-enters the queue head with its generated-so-far
  tokens, and re-admission replays prefill over ``prompt + tokens[:-1]``.
  Greedy decoding is deterministic, so the restored request emits exactly
  the tokens the never-preempted run would have (parity pinned in tests).
* ``GenRequest.deadline_ticks`` and the engine-level
  ``wall_clock_budget_s`` expire overdue work with
  ``finish_reason="timeout"`` between steps; a failed decode step retries
  with bounded exponential backoff (``RetryPolicy``) and, if it keeps
  failing, finishes live work as ``"preempted_unrecoverable"`` instead of
  crashing the session.
* ``guard=True`` compiles the prefill/decode programs with ``sfu.guard``
  collectors: per-site clamp / non-finite counters come back with every
  step, and a step whose fused output went non-finite is re-run with the
  offending site degraded to ``impl="jnp"`` (then ``"exact"``) — recorded
  in :meth:`health_summary`, warned once per site.
* ``faults`` (a :class:`repro.serving.faults.FaultInjector`) threads
  deterministic chaos — allocator exhaustion, NaN injection at a plan
  site, simulated kernel failures, dropped ticks — through the exact same
  code paths, so every recovery above is testable and reproducible.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import sfu
from repro.distributed.sharding import use_rules
from repro.models import Model

from .resilience import (
    RETRYABLE_EXCEPTIONS,
    PagePoolExhausted,
    RequestRejected,
    RetryPolicy,
    SimulatedKernelFailure,
    StepRetriesExhausted,
    new_health,
)
from .scheduler import (
    Admission,
    ContinuousBatchingScheduler,
    GenRequest,
    GenResult,
)


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1)).bit_length()


class PagedServingEngine:
    def __init__(
        self,
        model: Model,
        params,
        *,
        max_slots: int = 8,
        page_size: int = 16,
        max_context: int = 512,
        num_pages: Optional[int] = None,
        rules=None,  # repro.distributed.sharding.Rules — serve sharded
        policy: str = "reserved",
        guard: bool = False,
        faults=None,  # repro.serving.faults.FaultInjector
        max_preemptions: int = 8,
        retry: Optional[RetryPolicy] = None,
        wall_clock_budget_s: Optional[float] = None,
    ):
        if page_size & (page_size - 1):
            raise ValueError(f"page_size must be a power of two, got {page_size}")
        self.model = model
        self.params = params
        self.page_size = page_size
        self.max_slots = max_slots
        self.max_cols = -(-max_context // page_size)
        if num_pages is None:
            # worst case: every slot at max_context, plus the sentinel
            num_pages = max_slots * self.max_cols + 1
        with use_rules(rules):
            self.cache = model.make_paged_cache(num_pages, page_size)
        self.sched = ContinuousBatchingScheduler(
            max_slots, page_size, num_pages, policy=policy,
            max_preemptions=max_preemptions, faults=faults,
        )
        # host mirrors: the scheduler mutates these between device steps
        self.page_table = np.zeros((max_slots, self.max_cols), np.int32)
        self.kv_len = np.zeros((max_slots,), np.int32)
        self._cur = np.zeros((max_slots,), np.int32)  # next decode input
        self.rules = rules
        self.guard = bool(guard)
        self.faults = faults
        self.retry = retry if retry is not None else RetryPolicy()
        self.wall_clock_budget_s = wall_clock_budget_s
        self.health = new_health(policy, guard)
        self._fns = self._build_fns()
        self._nan_fns_cache: dict = {}
        self._degraded_cache: dict = {}
        self.decode_steps = 0
        self.generated = 0

    # -- jitted program variants ---------------------------------------------
    def _build_fns(self, plan_override=None, inject_site: Optional[str] = None):
        """Jitted prefill/decode wrappers returning ``(logits, cache, diag)``
        where ``diag`` is the ``sfu.guard`` per-site ``{key: int32[2]}``
        counter dict ({} when the guard is off).  ``plan_override`` swaps the
        activation plan (the degraded re-run path); ``inject_site`` arms the
        trace-time NaN fault for one site."""
        model = self.model
        if plan_override is not None:
            model = Model(dataclasses.replace(self.model.cfg,
                                              act_plan=plan_override))
        rules = self.rules
        guard_on = self.guard

        def wrap(fn):
            def call(params, toks, cache, pt, lens):
                # trace-time contexts: rules activate the sharded dispatch,
                # force_nan arms the fault hook, collecting() the counters
                with contextlib.ExitStack() as stack:
                    if rules is not None:
                        stack.enter_context(use_rules(rules))
                    if inject_site is not None:
                        stack.enter_context(sfu.guard.force_nan(inject_site))
                    col = (stack.enter_context(sfu.guard.collecting())
                           if guard_on else None)
                    logits, new_cache = fn(params, toks, cache, pt, lens)
                diag = col.result() if col is not None else {}
                return logits, new_cache, diag

            return jax.jit(call)

        return {"prefill": wrap(model.prefill_paged),
                "decode": wrap(model.decode_step_paged)}

    def _nan_fns(self, site: str):
        if site not in self._nan_fns_cache:
            self._nan_fns_cache[site] = self._build_fns(inject_site=site)
        return self._nan_fns_cache[site]

    def _degraded_fns(self, sites: tuple, impl: str):
        """Program variant with ``sites`` degraded to ``impl`` ("jnp" keeps
        the same PWL table unfused — near-bitwise with the fused kernels, so
        greedy parity holds; "exact" is the last resort for a genuinely
        poisoned table).  Compiled lazily, cached per (sites, impl)."""
        key = (sites, impl)
        if key not in self._degraded_cache:
            base = sfu.plan_for(self.model.cfg)
            degraded = sfu.ActivationPlan(sites=tuple(
                (k, dataclasses.replace(s, impl=impl) if k in sites else s)
                for k, s in base.items()
            ))
            self._degraded_cache[key] = self._build_fns(plan_override=degraded)
        return self._degraded_cache[key]

    # -- incident / diagnostics ----------------------------------------------
    def _incident(self, kind: str, **info) -> None:
        self.health["incidents"].append({"kind": kind, **info})

    def _scan_diag(self, diag: dict, accumulate: bool) -> list[str]:
        """Read a step's guard counters; returns the sites whose output went
        non-finite.  ``accumulate=False`` on degraded re-runs keeps the
        session counters meaning "observed on the primary path"."""
        bad = []
        for k, rec in diag.items():
            rec = np.asarray(rec)
            clamped, nonfinite = int(rec[0]), int(rec[1])
            if accumulate:
                self.health["clamped"][k] = (
                    self.health["clamped"].get(k, 0) + clamped)
                self.health["nonfinite"][k] = (
                    self.health["nonfinite"].get(k, 0) + nonfinite)
            if nonfinite > 0:
                bad.append(k)
        return sorted(bad)

    # -- device execution -----------------------------------------------------
    def _device_call(self, fn, args, phase: str):
        """One jitted call under the bounded retry policy.  Injected kernel
        failures (and anything in RETRYABLE_EXCEPTIONS) retry with
        exponential backoff; exhausting the budget raises
        :class:`StepRetriesExhausted` for :meth:`decode_step` to contain."""
        attempt = 0
        while True:
            try:
                if (phase == "decode" and self.faults is not None
                        and self.faults.kernel_fail_due()):
                    raise SimulatedKernelFailure(
                        f"injected kernel failure at decode step "
                        f"{self.decode_steps}")
                return fn(self.params, *args)
            except RETRYABLE_EXCEPTIONS as e:
                if attempt >= self.retry.max_retries:
                    raise StepRetriesExhausted(
                        f"{phase} step failed after {attempt + 1} attempts: "
                        f"{e}") from e
                self.health["step_retries"] += 1
                self._incident("step_retry", phase=phase, attempt=attempt,
                               step=self.decode_steps, error=str(e))
                time.sleep(self.retry.backoff(attempt))
                attempt += 1

    def _exec(self, phase: str, args):
        """Run one prefill/decode step with fault injection and guard
        degradation.  jax.jit does not donate inputs, so ``self.cache`` (an
        element of ``args``) stays valid across re-runs — a degraded re-run
        replays the exact same step."""
        nan_site = None
        if phase == "decode" and self.faults is not None:
            nan_site = self.faults.nan_site_due()
        fns = self._fns if nan_site is None else self._nan_fns(nan_site)
        if nan_site is not None:
            self._incident("nan_injected", site=nan_site,
                           step=self.decode_steps)
        logits, cache2, diag = self._device_call(fns[phase], args, phase)
        bad = self._scan_diag(diag, accumulate=True)
        for impl in ("jnp", "exact"):
            if not bad:
                break
            for k in bad:
                sfu.guard.warn_nonfinite(k, impl)
            self._incident("nonfinite_output", phase=phase,
                           sites=list(bad), degraded_to=impl,
                           step=self.decode_steps)
            dfns = self._degraded_fns(tuple(bad), impl)
            logits, cache2, diag = self._device_call(dfns[phase], args, phase)
            still = self._scan_diag(diag, accumulate=False)
            rec = self.health["nonfinite_recoveries"]
            for k in bad:
                if k not in still:
                    rec[k] = rec.get(k, 0) + 1
            bad = still
        if bad:
            self._incident("nonfinite_unrecovered", phase=phase,
                           sites=list(bad), step=self.decode_steps)
        return logits, cache2

    # -- internals ----------------------------------------------------------
    def _prefill(self, adm: Admission) -> bool:
        """Write the page-table row, run bucketed prefill, sample the first
        token (fresh requests) or resume the pre-preemption token (restores).
        Returns True when the request finished AT prefill."""
        slot = adm.slot
        toks_list = adm.prefill_tokens
        n = len(toks_list)
        bucket = max(self.page_size, _next_pow2(n))
        npg = bucket // self.page_size
        row = np.zeros((self.max_cols,), np.int32)
        row[: len(adm.pages)] = adm.pages
        self.page_table[slot] = row
        self.kv_len[slot] = n
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = toks_list
        args = (jnp.asarray(toks), self.cache,
                jnp.asarray(row[None, :npg]), jnp.asarray([n], jnp.int32))
        logits, self.cache = self._exec("prefill", args)
        if adm.resume_tokens:
            # restore after preemption: the "next token" was sampled before
            # the preemption and is already in the scheduler's token list —
            # the replayed prefill only rebuilds the K/V pages, its sampled
            # token is discarded (greedy parity: it IS resume_tokens[-1])
            self._cur[slot] = adm.resume_tokens[-1]
            return False
        tok = int(np.asarray(jnp.argmax(logits[0, 0])))
        self._cur[slot] = tok
        self.generated += 1
        if self.sched.record_prefill_token(slot, tok):
            self._evict(slot)
            return True
        return False

    def _evict(self, slot: int, reason: Optional[str] = None) -> GenResult:
        res = self.sched.evict(slot, reason=reason)
        self.page_table[slot] = 0
        self.kv_len[slot] = 0
        self._cur[slot] = 0
        return res

    def _preempt(self, i: int) -> None:
        """Preempt slot ``i`` (scheduler requeues it or finishes it as
        unrecoverable) and clear its device-facing mirrors."""
        rid = self.sched.slot(i).request.request_id
        res = self.sched.preempt(i)
        self.page_table[i] = 0
        self.kv_len[i] = 0
        self._cur[i] = 0
        self._incident("preemption", slot=i, request_id=rid,
                       step=self.decode_steps,
                       unrecoverable=res is not None)

    def _grow_with_preemption(self, active: list[int]) -> list[int]:
        """Allocate boundary pages for this step; under pressure, preempt the
        youngest active request until the allocation succeeds (or the slot
        being grown is itself the victim).  Returns the surviving slots."""
        for i in active:
            while self.sched.slots[i] is not None:
                try:
                    page = self.sched.grow(i)
                except PagePoolExhausted:
                    victim = self.sched.youngest_active()
                    self._preempt(victim)
                    continue  # retry the grow (unless i was the victim)
                if page is not None:
                    self.page_table[i, len(self.sched.slot(i).pages) - 1] = page
                break
        return [i for i in active if self.sched.slots[i] is not None]

    def decode_step(self) -> list[int]:
        """One batched decode step over every slot (active or not).  Appends
        each active slot's pending token, samples the next, advances the
        scheduler.  Returns the slots that finished this step."""
        if self.faults is not None:
            self.faults.set_step(self.decode_steps)
        active = self.sched.active_slots()
        active = self._grow_with_preemption(active)
        if not active:
            return []
        width = max((len(self.sched.slot(i).pages) for i in active), default=1)
        n_cols = min(_next_pow2(width), self.max_cols)
        args = (jnp.asarray(self._cur[:, None]), self.cache,
                jnp.asarray(self.page_table[:, :n_cols]),
                jnp.asarray(self.kv_len))
        try:
            logits, cache2 = self._exec("decode", args)
        except StepRetriesExhausted as e:
            # the device step is persistently failing: degrade the session
            # instead of dying — finish everything as unrecoverable
            self._incident("step_failed", step=self.decode_steps,
                           error=str(e))
            for i in list(self.sched.active_slots()):
                self._evict(i, reason="preempted_unrecoverable")
            self.sched.drain_queue("preempted_unrecoverable")
            return []
        if self.faults is not None and self.faults.drop_tick_due():
            # simulated lost completion: discard the step's outputs without
            # advancing any bookkeeping.  append_kv wrote the same token KV
            # it will write again on the re-run (same kv_len → same page
            # slot), so the replay is idempotent — but the write landed in
            # `cache2`, which we are dropping, so even that is moot.
            self.health["dropped_ticks"] += 1
            self._incident("dropped_tick", step=self.decode_steps)
            return []
        self.cache = cache2
        nxt = np.asarray(jnp.argmax(logits[:, 0], axis=-1)).astype(np.int32)
        self.sched.tick()
        self.decode_steps += 1
        finished = []
        for i in active:
            done = self.sched.append_token(i, int(nxt[i]))
            self.kv_len[i] += 1
            self._cur[i] = nxt[i]
            self.generated += 1
            if done:
                self._evict(i)
                finished.append(i)
        return finished

    # -- deadlines ------------------------------------------------------------
    def _expire_deadlines(self) -> None:
        for i in self.sched.expired_active():
            rid = self.sched.slot(i).request.request_id
            self._evict(i, reason="timeout")
            self._incident("deadline_expired", request_id=rid, where="active",
                           step=self.decode_steps)
        for res in self.sched.expire_queued():
            self._incident("deadline_expired", request_id=res.request_id,
                           where="queued", step=self.decode_steps)

    # -- public loop ---------------------------------------------------------
    def run(
        self,
        requests: list[GenRequest],
        on_result: Optional[Callable[[GenResult], None]] = None,
    ) -> list[GenResult]:
        """Serve ``requests`` to completion under continuous batching and
        return their results in finish order.  Invalid requests are rejected
        up front (recorded in the health summary, no GenResult) without
        killing the session."""
        # per-session warn lifecycle: a fused fallback (or sharding sanitize
        # warning, or a guard degradation) must be reported once per SESSION,
        # not once per process — a monitoring loop that spins up a second
        # engine would otherwise never see its regression
        sfu.reset_all_warnings()
        t0 = time.monotonic()
        for r in requests:
            try:
                self.sched.submit(r)
            except RequestRejected as e:
                rec = {"request_id": e.request_id, "reason": e.reason,
                       "message": str(e)}
                self.health["rejected"].append(rec)
                self._incident("request_rejected", **rec)
        n_before = len(self.sched.results())
        while self.sched.has_work():
            if (self.wall_clock_budget_s is not None
                    and time.monotonic() - t0 > self.wall_clock_budget_s):
                self._incident("wall_clock_budget_exhausted",
                               budget_s=self.wall_clock_budget_s,
                               step=self.decode_steps)
                for i in list(self.sched.active_slots()):
                    self._evict(i, reason="timeout")
                self.sched.drain_queue("timeout")
            else:
                self._expire_deadlines()
                for adm in self.sched.admit():
                    self._prefill(adm)
                if self.sched.active_slots():
                    self.decode_step()
            if on_result is not None:
                for res in self.sched.results()[n_before:]:
                    on_result(res)
                n_before = len(self.sched.results())
        return self.sched.results()

    # -- health ---------------------------------------------------------------
    def health_summary(self) -> dict:
        """Session health (docs/serving.md "Resilience" documents every
        field).  Scheduler-owned counters are read live, so this is valid
        both mid-session and after :meth:`run` returns."""
        h = dict(self.health)
        h["preemptions"] = self.sched.preemption_count
        h["replayed_prefill_tokens"] = self.sched.replayed_prefill_tokens
        h["timeouts"] = self.sched.timeout_count
        h["rejected"] = list(self.health["rejected"])
        h["clamped"] = dict(self.health["clamped"])
        h["nonfinite"] = dict(self.health["nonfinite"])
        h["nonfinite_recoveries"] = dict(self.health["nonfinite_recoveries"])
        h["incidents"] = list(self.health["incidents"])
        h["faults_fired"] = (list(self.faults.fired)
                             if self.faults is not None else [])
        return h
