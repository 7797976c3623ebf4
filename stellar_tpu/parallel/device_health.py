"""Per-device fault domains for the batch-verify mesh.

PR 2's dispatch resilience treated the whole mesh as ONE fault domain:
a single global breaker meant one sick chip benched every healthy
device behind it. This registry narrows the domain to a single device —
one :class:`~stellar_tpu.utils.resilience.CircuitBreaker` per mesh
device index, so a dispatch/fetch failure attributable to device ``i``
opens only device ``i``'s breaker and the batch re-shards over the
survivors (``docs/robustness.md`` "Per-device fault domains").

Lifecycle of one device:

* **healthy** (breaker closed) — in the dispatch rotation;
* **quarantined** (open) — excluded from sub-chunk assignment; its
  share of the batch rides the surviving devices (same sub-chunk
  shapes, so no fresh XLA compile — see ``BatchVerifier``);
* **probation** (half-open) — after the backoff window ONE sub-chunk
  of real traffic is routed back to it; success re-closes (the device
  regrows into the rotation), failure re-opens with doubled backoff.

:meth:`DeviceHealth.quarantine` is the HARD open
(``CircuitBreaker.trip``) used by the result-integrity audit: a device
caught returning wrong bits must not get ``threshold - 1`` more
chances to decide signature validity.

Every state transition is recorded in a bounded in-memory history ring
(``seq``-ordered; consumers stamp wall-clock time themselves) and mirrored into per-device metrics
gauges (``crypto.verify.device.<idx>.breaker.state``).

Determinism: this module never reads clocks or RNGs itself (it is in
the nondet-lint scope — the quarantine decisions it feeds gate which
backend serves a CONSENSUS verdict); the breakers it owns carry their
own monotonic clocks for backoff pacing, which affects only latency,
never decisions.
"""

from __future__ import annotations

import os
import threading
from collections import deque
from typing import Dict, List, Optional

from stellar_tpu.utils import resilience
from stellar_tpu.utils.metrics import registry as _metrics

__all__ = ["DeviceHealth", "get",
           "DEFAULT_FAILURE_THRESHOLD", "DEFAULT_BACKOFF_MIN_S",
           "DEFAULT_BACKOFF_MAX_S"]

# Env defaults let tools/bench run without a Config; a node pushes its
# Config knobs through batch_verifier.configure_dispatch at setup.
# The per-device threshold defaults LOWER than the global breaker's
# (2 vs 3): benching one chip of n costs 1/n of throughput, so the
# evidence bar for doing it is lower than for benching the whole mesh.
DEFAULT_FAILURE_THRESHOLD = int(os.environ.get(
    "VERIFY_DEVICE_FAILURE_THRESHOLD", "2"))
DEFAULT_BACKOFF_MIN_S = float(os.environ.get(
    "VERIFY_DEVICE_BACKOFF_MIN_S", "1"))
DEFAULT_BACKOFF_MAX_S = float(os.environ.get(
    "VERIFY_DEVICE_BACKOFF_MAX_S", "300"))

HISTORY_LIMIT = 256


class DeviceHealth:
    """Registry of one circuit breaker per mesh-device index."""

    def __init__(self,
                 failure_threshold: Optional[int] = None,
                 backoff_min_s: Optional[float] = None,
                 backoff_max_s: Optional[float] = None,
                 history_limit: int = HISTORY_LIMIT):
        self._lock = threading.Lock()
        self._breakers: Dict[int, resilience.CircuitBreaker] = {}
        self._history: deque = deque(maxlen=history_limit)
        self._seq = 0
        # per-device audit-verdict tallies (key "-1" = unattributable
        # single-device dispatch) — the fault-domain evidence
        # multi-chip capture runs carry (docs/observability.md)
        self._audits: Dict[str, Dict[str, int]] = {}
        self._threshold = int(failure_threshold
                              if failure_threshold is not None
                              else DEFAULT_FAILURE_THRESHOLD)
        self._backoff_min = float(backoff_min_s
                                  if backoff_min_s is not None
                                  else DEFAULT_BACKOFF_MIN_S)
        self._backoff_max = float(backoff_max_s
                                  if backoff_max_s is not None
                                  else DEFAULT_BACKOFF_MAX_S)

    # ---------------- breaker access ----------------

    def breaker(self, idx: int) -> resilience.CircuitBreaker:
        """Get-or-create the breaker for device ``idx``."""
        with self._lock:
            br = self._breakers.get(idx)
            if br is None:
                br = resilience.CircuitBreaker(
                    name=f"verify-device-{idx}",
                    failure_threshold=self._threshold,
                    backoff_min_s=self._backoff_min,
                    backoff_max_s=self._backoff_max,
                    on_transition=lambda old, new, i=idx:
                        self._note_transition(i, old, new))
                self._breakers[idx] = br
            return br

    def configure(self,
                  failure_threshold: Optional[int] = None,
                  backoff_min_s: Optional[float] = None,
                  backoff_max_s: Optional[float] = None) -> None:
        """Config push (Application / tests); None keeps the current
        value. Applies to existing breakers and future ones."""
        with self._lock:
            if failure_threshold is not None:
                self._threshold = max(1, int(failure_threshold))
            if backoff_min_s is not None:
                self._backoff_min = float(backoff_min_s)
            if backoff_max_s is not None:
                self._backoff_max = float(backoff_max_s)
            breakers = list(self._breakers.values())
        for br in breakers:
            br.configure(failure_threshold=failure_threshold,
                         backoff_min_s=backoff_min_s,
                         backoff_max_s=backoff_max_s)

    # ---------------- accounting ----------------

    def allow(self, idx: int) -> bool:
        """May traffic be routed to device ``idx`` right now? Closed:
        yes. Open: no, until the backoff expires. Half-open: one probe
        grant per backoff window — the regrow path."""
        return self.breaker(idx).allow()

    def record_failure(self, idx: int) -> bool:
        """Account one failure to device ``idx``. Returns True when
        THIS failure opened the device's breaker (quarantine onset) —
        the caller escalates correlated failures to the global breaker
        so a whole-host device outage doesn't pay n_devices independent
        quarantines of serialized deadline waits."""
        _metrics.counter(f"crypto.verify.device.{idx}.failures").inc()
        # the breaker reports the OPEN transition atomically (under its
        # own lock), so two threads failing the same device can never
        # both claim the onset and double-count it globally
        return self.breaker(idx).record_failure()

    def record_success(self, idx: int) -> None:
        self.breaker(idx).record_success()

    def quarantine(self, idx: int, reason: str = "integrity") -> None:
        """HARD quarantine: force the breaker open immediately (audit
        mismatch — wrong bits, not a failure streak)."""
        self._note_event(idx, "quarantine", reason)
        _metrics.counter(
            f"crypto.verify.device.{idx}.quarantines").inc()
        self.breaker(idx).trip()

    def note_audit(self, idx: Optional[int], ok: bool,
                   sampled: int) -> None:
        """Record one result-integrity audit verdict against device
        ``idx`` (None = unattributable single-device dispatch):
        per-device ok/mismatch tallies in the snapshot, a history
        event on a mismatch, and counters — so a multi-chip
        capture carries the audit evidence alongside breaker states.
        Clock/RNG-free (this module is in the nondet-lint scope)."""
        key = "-1" if idx is None else str(int(idx))
        with self._lock:
            tally = self._audits.setdefault(key,
                                            {"ok": 0, "mismatch": 0})
            tally["ok" if ok else "mismatch"] += 1
        if not ok:
            self._note_event(-1 if idx is None else idx,
                             "audit-mismatch", f"sampled={sampled}")
        _metrics.counter(
            f"crypto.verify.device.{key}.audit."
            + ("ok" if ok else "mismatch")).inc()

    def available_devices(self, n: int) -> List[int]:
        """Indices (of mesh devices ``0..n-1``) that may serve traffic
        for ONE chunk: every closed breaker, plus any half-open breaker
        whose probe grant is free. NOTE: consulting a half-open breaker
        CONSUMES its single per-window grant — callers that may not
        route traffic to every returned device should use
        :meth:`assign_parts`, which only consults grants it will
        honor."""
        return [i for i in range(n) if self.allow(i)]

    def assign_parts(self, n_devices: int,
                     n_parts: int) -> List[Optional[int]]:
        """Serving device per sub-chunk part (None = host fallback) —
        the degraded-mesh re-shard assignment, with probation-grant
        discipline:

        * closed (healthy) devices share the parts round-robin;
        * a non-closed device is consulted (``allow()`` — which
          consumes its single half-open grant) ONLY when it will
          actually receive a part, and then receives exactly ONE —
          probation traffic is the re-probe, and one grant must never
          cover several sub-chunks nor be burned on a batch too short
          to reach the device;
        * with zero healthy devices and no grants, parts fall back to
          the host (None).
        """
        closed = [i for i in range(n_devices)
                  if self.breaker(i).state == resilience.CLOSED]
        probation: List[int] = []
        for i in range(n_devices):
            if i in closed:
                continue
            if len(probation) >= n_parts:
                break  # later devices keep their grants for next time
            if self.breaker(i).allow():
                probation.append(i)
        out: List[Optional[int]] = []
        ci = 0
        for j in range(n_parts):
            if j < len(probation):
                out.append(probation[j])
            elif closed:
                out.append(closed[ci % len(closed)])
                ci += 1
            else:
                out.append(None)
        return out

    def quarantined(self, n: int) -> List[int]:
        """Currently-open device indices among ``0..n-1`` (answers the
        snapshot question without consuming half-open grants)."""
        with self._lock:
            items = list(self._breakers.items())
        return sorted(i for i, br in items
                      if i < n and br.state == resilience.OPEN)

    # ---------------- history / observability ----------------

    def _note_transition(self, idx: int, old: str, new: str) -> None:
        with self._lock:
            self._seq += 1
            self._history.append({"seq": self._seq, "device": idx,
                                  "from": old, "to": new})
        _metrics.gauge(
            f"crypto.verify.device.{idx}.breaker.state").set(new)
        _metrics.counter("crypto.verify.device.breaker.transitions").inc()

    def _note_event(self, idx: int, event: str, reason: str) -> None:
        with self._lock:
            self._seq += 1
            self._history.append({"seq": self._seq, "device": idx,
                                  "event": event, "reason": reason})

    def history(self, limit: Optional[int] = None) -> List[dict]:
        """Transition/event records, oldest first (bounded ring).
        ``seq`` orders them; consumers stamp wall time themselves."""
        with self._lock:
            out = list(self._history)
        return out if limit is None else out[-limit:]

    def snapshot(self) -> dict:
        """Observability payload (dispatch admin route / bench)."""
        with self._lock:
            items = sorted(self._breakers.items())
            seq = self._seq
            audits = {k: dict(v) for k, v in self._audits.items()}
        return {
            "devices": {str(i): br.snapshot() for i, br in items},
            "quarantined": [i for i, br in items
                            if br.state == resilience.OPEN],
            "transitions_total": seq,
            "audits": audits,
        }

    def _reset_for_testing(self) -> None:
        """Fresh registry state (chaos tests): drop every breaker and
        the history ring — equivalent to process start."""
        with self._lock:
            self._breakers.clear()
            self._history.clear()
            self._seq = 0
            self._audits.clear()


# process-wide registry: device health is a property of the PHYSICAL
# device, shared by every BatchVerifier instance in the process (the
# default verifier, the coalescing bench verifier, test instances)
_registry = DeviceHealth()


def get() -> DeviceHealth:
    return _registry
