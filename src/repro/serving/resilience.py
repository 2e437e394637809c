"""Typed errors, finish reasons, and retry/health plumbing for resilient serving.

This module is the dependency *leaf* of the resilience subsystem: it imports
nothing from the rest of ``repro.serving`` so that ``kv_cache``, ``scheduler``
and ``engine`` (and even ``models/transformer.py``) can all raise the same
typed errors without cycles.

Design notes
------------
- ``PagePoolExhausted`` subclasses ``RuntimeError`` and keeps "exhausted" in
  its message so pre-existing callers (`pytest.raises(RuntimeError,
  match="exhausted")`) keep working.
- ``UnsupportedCacheError`` subclasses ``ValueError`` for the same reason
  (the old `make_paged_cache` rejection was a bare ValueError matched on
  "global-attention").
- ``RequestRejected`` carries a machine-readable ``reason`` from
  ``REJECTION_REASONS`` so front-ends (``launch/serve.py``) can surface the
  failure per-request without killing the session.
"""
from __future__ import annotations

import dataclasses

# Every GenResult.finish_reason is one of these.
FINISH_REASONS = ("length", "eos", "timeout", "preempted_unrecoverable")

# Scheduler admission policies.
POLICY_RESERVED = "reserved"
POLICY_OPTIMISTIC = "optimistic"
POLICIES = (POLICY_RESERVED, POLICY_OPTIMISTIC)

REJECTION_REASONS = (
    "empty_prompt",
    "nonpositive_max_new_tokens",
    "nonpositive_deadline",
    "exceeds_page_capacity",
)


class ServingError(Exception):
    """Base class for all typed serving errors."""


class RequestRejected(ServingError):
    """A request failed admission-time validation.

    Attributes:
      request_id: the id of the rejected request.
      reason: one of ``REJECTION_REASONS``.
    """

    def __init__(self, request_id: str, reason: str, message: str):
        assert reason in REJECTION_REASONS, reason
        super().__init__(f"request {request_id!r} rejected ({reason}): {message}")
        self.request_id = request_id
        self.reason = reason


class UnsupportedCacheError(ServingError, ValueError):
    """The model's layer stack cannot back a paged KV cache.

    Raised by ``Model.make_paged_cache`` for sliding-window /
    SSM / encoder-decoder stacks.  Front-ends should catch this and fall
    back to dense-mode decoding.
    """


class PagePoolExhausted(ServingError, RuntimeError):
    """The page allocator cannot satisfy a request for free pages.

    Under ``policy="reserved"`` this only fires for genuinely invalid asks
    (or injected faults); under ``policy="optimistic"`` it is the normal
    back-pressure signal the engine answers with recompute preemption.
    """


class SimulatedKernelFailure(ServingError, RuntimeError):
    """A fault-injected device-step failure (see ``serving.faults``)."""


class StepRetriesExhausted(ServingError, RuntimeError):
    """A decode step kept failing after the bounded retry budget."""


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Bounded exponential backoff around a failed decode step."""

    max_retries: int = 2
    backoff_s: float = 0.02

    def backoff(self, attempt: int) -> float:
        return self.backoff_s * (2.0 ** attempt)


# Exceptions the engine treats as transient and retries with backoff.
# Real device-runtime errors (jaxlib XlaRuntimeError subclasses RuntimeError
# but so do many programming errors) are deliberately NOT auto-retried —
# extend this tuple in an engine subclass if your deployment wants that.
RETRYABLE_EXCEPTIONS = (SimulatedKernelFailure,)


def new_health(policy: str, guard: bool) -> dict:
    """The engine health-summary skeleton (documented in docs/serving.md)."""
    return {
        "policy": policy,
        "guard": bool(guard),
        "preemptions": 0,
        "replayed_prefill_tokens": 0,
        "timeouts": 0,
        "rejected": [],            # [{request_id, reason, message}]
        "step_retries": 0,
        "dropped_ticks": 0,
        "clamped": {},             # site key -> inputs outside fitted range
        "nonfinite": {},           # site key -> non-finite outputs observed
        "nonfinite_recoveries": {},  # site key -> degraded re-runs that healed it
        "incidents": [],           # [{kind, step, ...}] chronological
        "faults_fired": [],        # injector log, [] when no injector
    }
