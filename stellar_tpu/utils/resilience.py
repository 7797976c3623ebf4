"""Generic resilience primitives: circuit breaker + deadlines.

The verify boundary must stay as dependable as the reference's
``PubKeyUtils::verifySig`` even when the accelerator dies
mid-flight — a node that hangs in ledger close is worse than a slow
node. These are the domain-free building blocks; the verify-specific
policy (what counts as a failure, what the fallback is) lives in
:mod:`stellar_tpu.crypto.batch_verifier`.

* :class:`CircuitBreaker` — closed → open on a consecutive-failure
  threshold → half-open re-probe after an exponential backoff window
  (with jitter so a fleet of nodes doesn't re-probe in lockstep).
* :class:`Deadline` / :func:`call_with_deadline` — watchdogged
  execution budgets for calls whose observed failure mode is a HANG,
  not an exception (``jax.devices()`` / device-array fetches on a
  wedged device can block forever).
* :class:`WatchdogPool` — the persistent worker pool behind
  :func:`call_with_deadline`: one short-lived thread per guarded call
  (the PR 2 shape) cost a spawn per chunk fetch; the pool reuses a
  small set of daemon workers and only spawns when every idle worker
  is busy, so the steady-state guarded fetch is a queue hand-off, not
  a thread start.
"""

from __future__ import annotations

import random
import threading
import time
from collections import deque
from typing import Callable, Optional

from stellar_tpu.utils import tracing

__all__ = [
    "CLOSED", "OPEN", "HALF_OPEN",
    "CircuitBreaker", "Deadline", "DeadlineExceeded", "Overloaded",
    "WatchdogPool", "call_with_deadline", "watchdog_stats",
]

CLOSED = "closed"
OPEN = "open"
HALF_OPEN = "half-open"


class DeadlineExceeded(Exception):
    """A guarded call did not finish within its budget."""


class Overloaded(RuntimeError):
    """Typed admission-control verdict: the system chose to REFUSE or
    DROP work rather than buffer unboundedly (docs/robustness.md,
    "Overload and load-shed"). Two kinds:

    * ``kind="rejected"`` — refused at INGRESS (queue depth or byte
      budget exceeded, or the service is stopping): the work never
      entered a queue;
    * ``kind="shed"`` — admitted, then dropped by the deterministic
      load-shed ladder under overload pressure: the caller learns via
      this exception from its ticket, never by silence.

    ``lane`` names the priority lane (or ``"trickle"`` for the
    micro-batch window), ``reason`` the specific budget that tripped
    (``"queue-depth"``, ``"bytes"``, ``"tenant-depth"``,
    ``"tenant-bytes"``, ``"backlog"``, ...); ``tenant`` names the
    submitting tenant when the verdict was tenant-scoped (ISSUE 14) —
    a per-tenant quota refusal or a tenant-keyed shed is attributable
    to its principal from the exception alone; ``replica`` names the
    refusing fleet replica (ISSUE 17) — in an N-replica deployment a
    refusal attributes to the replica that issued it (``None`` for a
    single-service deployment or a router-level refusal); ``trace_ids``
    carries the refused/shed items' trace IDs (ISSUE 8) — an item's
    trace survives even when the answer is "no", so the ``trace``
    admin route can show WHERE a submission died."""

    def __init__(self, message: str, *, kind: str = "rejected",
                 lane: Optional[str] = None, reason: str = "",
                 tenant: Optional[str] = None, trace_ids=None,
                 replica: Optional[int] = None):
        super().__init__(message)
        self.kind = kind
        self.lane = lane
        self.reason = reason
        self.tenant = tenant
        self.trace_ids = trace_ids if trace_ids is not None else ()
        self.replica = replica


class Deadline:
    """A monotonic time budget threaded through a multi-step operation
    so each step races against what is LEFT, not a fresh allowance."""

    def __init__(self, budget_s: float, clock=time.monotonic):
        self._clock = clock
        self._t0 = clock()
        self.budget_s = float(budget_s)

    @classmethod
    def from_ms(cls, budget_ms: float, clock=time.monotonic) -> "Deadline":
        return cls(budget_ms / 1000.0, clock=clock)

    def elapsed(self) -> float:
        return self._clock() - self._t0

    def remaining(self) -> float:
        return self.budget_s - self.elapsed()

    def expired(self) -> bool:
        return self.remaining() <= 0.0

    def check(self, what: str = "operation") -> None:
        if self.expired():
            raise DeadlineExceeded(
                f"{what}: {self.budget_s:.3f}s budget exhausted")


class WatchdogPool:
    """Persistent daemon-worker pool for deadline-guarded calls.

    Invariants:

    * a submitted job is picked up immediately — ``submit`` spawns a
      fresh worker whenever the queue outnumbers idle workers, so a
      guarded call never waits behind another caller's work;
    * a worker whose job HANGS is simply absent from the idle set (it
      is parked inside ``fn()``); capacity self-heals because the next
      submit spawns, and if the hung call ever returns the worker
      rejoins the pool on its own;
    * at most ``max_idle`` workers linger between bursts — extras exit
      once the queue drains, so a resolve storm doesn't leave a
      thread-per-chunk residue (the pre-pool behavior).

    All shared state (queue, idle/worker counts) mutates under the
    pool's condition variable — the lock-discipline lint covers this
    module.
    """

    def __init__(self, name: str = "watchdog", max_idle: int = 4):
        self.name = name
        self._max_idle = max_idle
        self._cv = threading.Condition()
        self._jobs: deque = deque()
        self._idle = 0
        self._workers = 0
        self._spawned_total = 0

    def _loop(self) -> None:
        while True:
            with self._cv:
                if not self._jobs:
                    if self._idle >= self._max_idle:
                        self._workers -= 1
                        return
                    self._idle += 1
                    while not self._jobs:
                        self._cv.wait()
                    self._idle -= 1
                job = self._jobs.popleft()
            try:
                # trace-context propagation (ISSUE 5): spans opened
                # inside the guarded call parent under the submitter's
                # live span — a HUNG fetch shows up in a flight-recorder
                # dump linked to the resolve that dispatched it
                with tracing.span_context(job["ctx"]):
                    job["box"]["out"] = job["fn"]()
            except BaseException as e:  # re-raised on the caller's thread
                job["box"]["err"] = e
            finally:
                job["done"].set()

    def submit(self, fn: Callable) -> dict:
        """Queue ``fn`` for a pool worker; returns the job record
        (``done`` event + ``box`` result slot). Never blocks."""
        job = {"fn": fn, "box": {}, "done": threading.Event(),
               "ctx": tracing.current_context()}
        with self._cv:
            self._jobs.append(job)
            if self._idle >= len(self._jobs):
                self._cv.notify()
            else:
                # every queued job beyond the idle set gets a fresh
                # worker NOW — hung workers (absent from _idle) can
                # never make a guarded call wait behind their hang
                self._workers += 1
                self._spawned_total += 1
                threading.Thread(target=self._loop, daemon=True,
                                 name=f"{self.name}-worker").start()
        return job

    def stats(self) -> dict:
        with self._cv:
            return {"workers": self._workers, "idle": self._idle,
                    "queued": len(self._jobs),
                    "spawned_total": self._spawned_total}


# process-wide pool behind call_with_deadline (ROADMAP "pool the
# resolve watchdog"): the verify resolve path guards one fetch per
# chunk, so reuse beats a thread spawn per chunk
_pool = WatchdogPool(name="resilience-watchdog")


def watchdog_stats() -> dict:
    """Observability: worker/idle/spawn accounting of the shared pool
    (surfaced by ``batch_verifier.dispatch_health``)."""
    return _pool.stats()


def call_with_deadline(fn: Callable, budget_s: Optional[float],
                       name: str = "guarded-call"):
    """Run ``fn()`` on a pooled watchdog worker; raise
    :class:`DeadlineExceeded` if it doesn't finish within ``budget_s``
    (None = no guard, direct call). Python cannot kill the worker: on
    timeout the job is ABANDONED — its worker stays parked on whatever
    hung (and rejoins the pool by itself if the hang ever resolves) —
    so callers must treat the underlying resource as suspect afterwards
    (that is the circuit breaker's job). An exception from ``fn`` is
    re-raised verbatim."""
    if budget_s is None:
        return fn()
    if budget_s <= 0:
        raise DeadlineExceeded(f"{name}: no budget left")
    job = _pool.submit(fn)
    if not job["done"].wait(budget_s):
        raise DeadlineExceeded(
            f"{name} exceeded {budget_s:.3f}s budget")
    box = job["box"]
    if "err" in box:
        raise box["err"]
    return box.get("out")


class CircuitBreaker:
    """Thread-safe consecutive-failure circuit breaker.

    States: ``closed`` (healthy — every call allowed; failures counted),
    ``open`` (tripped — calls refused until the backoff window expires),
    ``half-open`` (window expired — ONE probe call allowed; its outcome
    decides: success re-closes, failure re-opens with doubled backoff).

    A half-open probe grant expires after the current backoff interval,
    so a probe that itself hangs and never reports can't wedge the
    breaker half-open forever.

    ``on_transition(old, new)`` fires OUTSIDE the internal lock (it may
    log or update metrics; it must not need the breaker's lock-step
    consistency).
    """

    def __init__(self, name: str = "breaker", failure_threshold: int = 3,
                 backoff_min_s: float = 1.0, backoff_max_s: float = 120.0,
                 backoff_factor: float = 2.0, jitter_frac: float = 0.1,
                 clock=time.monotonic, rng=random.random,
                 on_transition: Optional[Callable[[str, str], None]] = None):
        self.name = name
        self._lock = threading.Lock()
        self._clock = clock
        self._rng = rng
        self._on_transition = on_transition
        self._state = CLOSED
        self._failures = 0
        self._opened_total = 0
        self._open_until = 0.0
        self._grant_expires = 0.0
        self.configure(failure_threshold=failure_threshold,
                       backoff_min_s=backoff_min_s,
                       backoff_max_s=backoff_max_s,
                       backoff_factor=backoff_factor,
                       jitter_frac=jitter_frac)

    def configure(self, failure_threshold: Optional[int] = None,
                  backoff_min_s: Optional[float] = None,
                  backoff_max_s: Optional[float] = None,
                  backoff_factor: Optional[float] = None,
                  jitter_frac: Optional[float] = None) -> None:
        """Update policy knobs in place (config push); None keeps the
        current value. Does not change the current state."""
        with self._lock:
            if failure_threshold is not None:
                self._threshold = max(1, int(failure_threshold))
            if backoff_min_s is not None:
                self._backoff_min = max(0.001, float(backoff_min_s))
            if backoff_max_s is not None:
                self._backoff_max = float(backoff_max_s)
            if backoff_factor is not None:
                self._factor = max(1.0, float(backoff_factor))
            if jitter_frac is not None:
                self._jitter = max(0.0, float(jitter_frac))
            self._backoff_max = max(self._backoff_max, self._backoff_min)
            cur = getattr(self, "_backoff_cur", None)
            self._backoff_cur = self._backoff_min if cur is None else \
                min(max(cur, self._backoff_min), self._backoff_max)

    # ---------------- state machine ----------------

    def _transition_locked(self, new: str) -> Optional[tuple]:
        old = self._state
        if old == new:
            return None
        self._state = new
        if new == OPEN:
            self._opened_total += 1
        return (old, new)

    def _fire(self, change: Optional[tuple]) -> None:
        if change is not None and self._on_transition is not None:
            try:
                self._on_transition(*change)
            except Exception:
                pass  # observability must never break the guarded path

    def allow(self) -> bool:
        """May a call proceed right now? In ``open``, flips to
        ``half-open`` once the backoff window has expired and grants
        exactly one probe per grant window."""
        change = None
        with self._lock:
            if self._state == CLOSED:
                return True
            now = self._clock()
            if self._state == OPEN:
                if now < self._open_until:
                    return False
                change = self._transition_locked(HALF_OPEN)
                self._grant_expires = now + self._backoff_cur
                ok = True
            else:  # HALF_OPEN: one outstanding probe per grant window
                ok = now >= self._grant_expires
                if ok:
                    self._grant_expires = now + self._backoff_cur
        self._fire(change)
        return ok

    def record_success(self) -> None:
        with self._lock:
            self._failures = 0
            self._backoff_cur = self._backoff_min
            change = self._transition_locked(CLOSED)
        self._fire(change)

    def record_failure(self) -> bool:
        """Returns True when THIS call transitioned the breaker to
        ``open`` (computed under the lock, so concurrent failure
        reports can't both claim the same onset — callers use it to
        count quarantine onsets exactly once)."""
        change = None
        with self._lock:
            self._failures += 1
            now = self._clock()
            if self._state == CLOSED:
                if self._failures >= self._threshold:
                    change = self._transition_locked(OPEN)
                    self._arm_locked(now)
            elif self._state == HALF_OPEN:
                # the probe failed: back off harder
                self._backoff_cur = min(self._backoff_cur * self._factor,
                                        self._backoff_max)
                change = self._transition_locked(OPEN)
                self._arm_locked(now)
            # already OPEN: a straggler failure report; don't extend
        self._fire(change)
        return change is not None and change[1] == OPEN

    def trip(self) -> None:
        """Force the breaker OPEN immediately, regardless of the
        failure streak — the hard-quarantine primitive. A
        result-INTEGRITY violation (a device returning wrong bits, not
        hanging) must not get ``threshold - 1`` more chances to decide
        signature validity; from half-open the backoff doubles exactly
        as a failed probe would."""
        with self._lock:
            now = self._clock()
            self._failures = max(self._failures, self._threshold)
            if self._state == HALF_OPEN:
                self._backoff_cur = min(self._backoff_cur * self._factor,
                                        self._backoff_max)
            change = self._transition_locked(OPEN)
            self._arm_locked(now)
        self._fire(change)

    def _arm_locked(self, now: float) -> None:
        jittered = self._backoff_cur * (1.0 + self._jitter * self._rng())
        self._open_until = now + jittered

    # ---------------- introspection ----------------

    @property
    def state(self) -> str:
        with self._lock:
            return self._state

    def seconds_until_retry(self) -> float:
        """0 when calls are (or may be) allowed; else time left in the
        open backoff window."""
        with self._lock:
            if self._state != OPEN:
                return 0.0
            return max(0.0, self._open_until - self._clock())

    def snapshot(self) -> dict:
        """Observability payload (info endpoint / metrics push)."""
        with self._lock:
            return {
                "state": self._state,
                "consecutive_failures": self._failures,
                "failure_threshold": self._threshold,
                "backoff_s": round(self._backoff_cur, 3),
                "retry_in_s": round(
                    max(0.0, self._open_until - self._clock()), 3)
                if self._state == OPEN else 0.0,
                "opened_total": self._opened_total,
            }
