"""Transfer ledger: per-resolve host↔device byte accounting (ISSUE 8).

The ROADMAP's #1 perf lever — dispatch-floor demolition — indicts three
quantities nothing measured until now: host-device ROUND TRIPS per resolve,
host↔device BYTES moved, and CONSTANT-TABLE RE-UPLOADS per bucket (the
base/A-table claim: identical bytes shipped again and again because
nothing keeps them resident on device). This module is the instrument:
the batch engine (:mod:`stellar_tpu.parallel.batch_engine`) records
every ``device_put``/dispatch upload and every blocking fetch here, so
each resolve yields

* ``round_trips`` — blocking device fetches (one kernel call whose
  result the host waited on = one round trip);
* ``bytes_h2d`` / ``bytes_d2h`` — payload bytes each direction;
* ``redundant_constant_bytes`` — bytes whose CONTENT FINGERPRINT
  (SHA-256 of the uploaded bytes) was already uploaded before: the
  smoking gun for re-shipped constants (``tools/perf_sentinel.py``
  pins it to a near-zero ceiling in bench records).

Totals surface in ``dispatch_health()["transfer"]``, the Prometheus
export (``crypto.transfer.*`` counters), and every ``bench.py`` record
next to ``dispatch_attribution``; the tier-1 ``TRANSFER_LEDGER_OK``
gate (``tools/transfer_selfcheck.py``) reconciles the ledger's byte
totals against the engine's own independent accounting of what it
shipped, so a new transfer path can never go unrecorded silently
(``docs/observability.md`` "Transfer ledger").

Determinism: this module is in the nondet-lint scope — fingerprints
are content-derived (SHA-256, no salts), no clocks, no RNG. Per-event
mutation happens under the instance lock (lock-lint scope); per-resolve
tokens are handed out by :meth:`TransferLedger.begin` and accumulate
under the same lock, so concurrent resolves never tear each other's
records.
"""

from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict, deque
from typing import Optional

from stellar_tpu.utils.metrics import registry

__all__ = ["TransferLedger", "ResolveLog", "transfer_ledger"]

# defaults; Config pushes TRANSFER_LEDGER_RESOLVES /
# TRANSFER_LEDGER_FINGERPRINTS / TRANSFER_LEDGER_FP_MAX_BYTES
# through configure()
DEFAULT_RESOLVES = 256
DEFAULT_FINGERPRINTS = 4096
# content-fingerprint size cap: hashing runs on the dispatch hot path
# (inside the resolve the instrument is measuring), so uploads larger
# than this are counted bytes-only — never falsely redundant, never
# paying an unbounded SHA-256 — and surfaced in
# ``unfingerprinted_uploads`` so the detector's blind spot is visible
# rather than silent. Today's largest real operand tuple (2048-sig
# batch) is well under this; raise the knob to widen coverage.
DEFAULT_FP_MAX_BYTES = 1 << 20

_NS = "crypto.transfer"


class ResolveLog:
    """Accumulator for ONE resolve's transfers (opaque token: the
    engine threads it through dispatch and fetch closures; all fields
    mutate under the owning ledger's lock)."""

    __slots__ = ("ns", "round_trips", "bytes_h2d", "bytes_d2h",
                 "device_puts", "fetches", "redundant_constant_bytes",
                 "redundant_uploads", "finished")

    def __init__(self, ns: str):
        self.ns = ns
        self.round_trips = 0
        self.bytes_h2d = 0
        self.bytes_d2h = 0
        self.device_puts = 0
        self.fetches = 0
        self.redundant_constant_bytes = 0
        self.redundant_uploads = 0
        self.finished = False

    def snapshot_locked(self) -> dict:
        return {"ns": self.ns,
                "round_trips": self.round_trips,
                "bytes_h2d": self.bytes_h2d,
                "bytes_d2h": self.bytes_d2h,
                "device_puts": self.device_puts,
                "fetches": self.fetches,
                "redundant_constant_bytes":
                    self.redundant_constant_bytes,
                "redundant_uploads": self.redundant_uploads}


class TransferLedger:
    """Process-wide transfer accounting: running totals, a bounded
    ring of per-resolve records, and a bounded LRU of upload content
    fingerprints for redundancy detection."""

    def __init__(self, resolves: int = DEFAULT_RESOLVES,
                 fingerprints: int = DEFAULT_FINGERPRINTS,
                 fp_max_bytes: int = DEFAULT_FP_MAX_BYTES):
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=max(4, int(resolves)))
        self._fp_cap = max(16, int(fingerprints))
        self._fp_max_bytes = max(0, int(fp_max_bytes))
        self._unfingerprinted_uploads = 0
        self._unfingerprinted_bytes = 0
        # fingerprint -> times uploaded (bounded LRU: eviction only
        # forgets OLD constants, so a long-lived table re-shipped every
        # bucket keeps counting as redundant)
        self._fingerprints: OrderedDict = OrderedDict()
        self._round_trips = 0
        self._bytes_h2d = 0
        self._bytes_d2h = 0
        self._device_puts = 0
        self._fetches = 0
        self._redundant_constant_bytes = 0
        self._redundant_uploads = 0
        self._resolves_finished = 0

    def configure(self, resolves: Optional[int] = None,
                  fingerprints: Optional[int] = None,
                  fp_max_bytes: Optional[int] = None) -> None:
        """Config push (TRANSFER_LEDGER_*); None keeps current."""
        with self._lock:
            if resolves is not None:
                cap = max(4, int(resolves))
                if cap != self._ring.maxlen:
                    self._ring = deque(self._ring, maxlen=cap)
            if fingerprints is not None:
                self._fp_cap = max(16, int(fingerprints))
                while len(self._fingerprints) > self._fp_cap:
                    self._fingerprints.popitem(last=False)
            if fp_max_bytes is not None:
                self._fp_max_bytes = max(0, int(fp_max_bytes))

    # ---------------- per-resolve recording ----------------

    def begin(self, ns: str) -> ResolveLog:
        """Open a per-resolve token (not registered anywhere until
        :meth:`finish` — a resolver the caller drops just gets
        garbage-collected; its event-level totals were already
        counted)."""
        return ResolveLog(ns)

    def record_h2d(self, tok: Optional[ResolveLog], arr,
                   device: Optional[int] = None) -> int:
        """One host→device upload (``device_put`` or a committed
        dispatch operand). Fingerprints the CONTENT: a fingerprint
        seen before means these exact bytes were already shipped —
        redundant re-upload. Uploads larger than the fingerprint cap
        (``TRANSFER_LEDGER_FP_MAX_BYTES``) are counted bytes-only:
        the hash runs on the dispatch hot path, so its cost must stay
        bounded, and a sampled/partial hash could convict different
        content as redundant — the skipped uploads are tallied in
        ``unfingerprinted_uploads`` instead. Returns the byte count."""
        nbytes = int(arr.nbytes)
        fp = None
        if nbytes <= self._fp_max_bytes:
            # zero-copy for the engine's C-contiguous operands (axis-0
            # slices / concatenate results); tobytes() only as the
            # fallback for exotic layouts
            try:
                buf = memoryview(arr)
                if not buf.c_contiguous:
                    buf = arr.tobytes()
            except TypeError:
                buf = arr.tobytes()
            fp = hashlib.sha256(buf).digest()[:16]
        with self._lock:
            if fp is not None:
                seen = self._fingerprints.pop(fp, 0)
                self._fingerprints[fp] = seen + 1
                while len(self._fingerprints) > self._fp_cap:
                    self._fingerprints.popitem(last=False)
            else:
                seen = 0
                self._unfingerprinted_uploads += 1
                self._unfingerprinted_bytes += nbytes
            self._bytes_h2d += nbytes
            self._device_puts += 1
            redundant = seen > 0
            if redundant:
                self._redundant_constant_bytes += nbytes
                self._redundant_uploads += 1
            if tok is not None:
                tok.bytes_h2d += nbytes
                tok.device_puts += 1
                if redundant:
                    tok.redundant_constant_bytes += nbytes
                    tok.redundant_uploads += 1
        registry.counter(f"{_NS}.bytes_h2d").inc(nbytes)
        registry.counter(f"{_NS}.device_puts").inc()
        if redundant:
            registry.counter(
                f"{_NS}.redundant_constant_bytes").inc(nbytes)
            registry.counter(f"{_NS}.redundant_uploads").inc()
        return nbytes

    def record_d2h(self, tok: Optional[ResolveLog], arr,
                   device: Optional[int] = None) -> int:
        """One blocking device→host fetch — BY DEFINITION one
        round trip (the host parked on this result). Returns bytes."""
        nbytes = int(arr.nbytes)
        with self._lock:
            self._bytes_d2h += nbytes
            self._fetches += 1
            self._round_trips += 1
            if tok is not None:
                tok.bytes_d2h += nbytes
                tok.fetches += 1
                tok.round_trips += 1
        registry.counter(f"{_NS}.bytes_d2h").inc(nbytes)
        registry.counter(f"{_NS}.fetches").inc()
        registry.counter(f"{_NS}.round_trips").inc()
        return nbytes

    def finish(self, tok: Optional[ResolveLog]) -> Optional[dict]:
        """Close a resolve's token into the per-resolve ring
        (idempotent — a resolver resolved twice records once)."""
        if tok is None:
            return None
        with self._lock:
            rec = tok.snapshot_locked()
            if not tok.finished:
                tok.finished = True
                self._ring.append(rec)
                self._resolves_finished += 1
        return rec

    # ---------------- introspection ----------------

    def totals(self) -> dict:
        """Running process totals — the ``dispatch_health()``
        ``transfer`` block and the bench-record embed."""
        with self._lock:
            return {
                "round_trips": self._round_trips,
                "bytes_h2d": self._bytes_h2d,
                "bytes_d2h": self._bytes_d2h,
                "device_puts": self._device_puts,
                "fetches": self._fetches,
                "redundant_constant_bytes":
                    self._redundant_constant_bytes,
                "redundant_uploads": self._redundant_uploads,
                # benchmark/harness.py reads this key from
                # dispatch_health()["transfer"]; nothing else sets it
                "resident_hits": 0,
                "resolves_recorded": self._resolves_finished,
                "fingerprints_tracked": len(self._fingerprints),
                "unfingerprinted_uploads":
                    self._unfingerprinted_uploads,
                "unfingerprinted_bytes": self._unfingerprinted_bytes,
            }

    def recent(self, limit: int = 32) -> list:
        """The most recent per-resolve records (admin/bench drill-in);
        ``limit=0`` means none."""
        limit = max(0, int(limit))
        with self._lock:
            return [dict(r) for r in
                    (list(self._ring)[-limit:] if limit else [])]

    def _reset_for_testing(self) -> None:
        """Fresh ledger state (per-resolve ring, fingerprints, totals).
        Cumulative registry counters are untouched — same policy as
        the dispatch layer's reset."""
        with self._lock:
            self._ring.clear()
            self._fingerprints.clear()
            self._unfingerprinted_uploads = 0
            self._unfingerprinted_bytes = 0
            self._round_trips = 0
            self._bytes_h2d = 0
            self._bytes_d2h = 0
            self._device_puts = 0
            self._fetches = 0
            self._redundant_constant_bytes = 0
            self._redundant_uploads = 0
            self._resolves_finished = 0


# process-wide ledger (one node per process, like the registry and the
# flight recorder)
transfer_ledger = TransferLedger()
