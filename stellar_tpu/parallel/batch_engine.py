"""Workload-agnostic batch-dispatch engine for device-accelerated work.

PRs 2-6 built a substantial dispatch substrate around ed25519 verify —
jit bucket management, per-device fault domains with degraded re-shard
(:mod:`stellar_tpu.parallel.device_health`), circuit breakers, watchdog
fetches, a sampled result-integrity audit, bit-identical host-oracle
failover, and span/attribution instrumentation — but all of it was
welded to one kernel inside ``crypto/batch_verifier.py``. The machinery
was never signature-specific: it is the generic shape of "ship a padded
batch to an accelerator you cannot fully trust, attribute every failure
to one chip, and never let degraded hardware change a decision".

This module is that machinery, factored behind a **workload plugin
interface**:

* :class:`Workload` — what a workload must provide: host-side
  ``encode`` (byte rows -> fixed-shape arrays + an eligibility gate),
  ``kernel_fn`` (the traced device function, batch axis LEADING on
  inputs and output), ``host_result`` (the bit-identical host oracle,
  also the audit's source of truth), ``finalize`` (compose gate +
  device rows into the caller-visible result), and pad rows for bucket
  padding. Namespaces (``metrics_ns``/``span_ns``) keep each
  workload's serve/audit accounting and resolve spans separable while
  host-level device state stays shared.
* :class:`BatchEngine` — the dispatch/resolve loop itself, factored
  out of ``BatchVerifier`` (same bucket/padding scheme, same
  per-device sub-chunk split, same breaker and probation-grant
  discipline, same audit composition and host-only escalation, same
  spans and counters), generic over the plugin's array tuple and
  result rows.

**Dispatch floor.** Every dispatched operand takes one path: a
``device_put`` to its destination, recorded once by the transfer
ledger and by the engine's byte tally; the engine keeps no device
buffer beyond the call that uses it and compiles one executable per
(variant, dispatch shape). Two levers bound the cost of that path:

* **coalesced per-mesh dispatch**: a fully healthy mesh serving a
  full bucket ships ONE sharded h2d upload whose per-device shards
  feed the SAME per-device sub-chunk executables — n_devices×n_arrays
  ``device_put`` round trips collapse to n_arrays while per-device
  fault attribution, breakers, degraded re-shard, probation grants
  and the sampled audit keep their existing shape;
* **async pipelined submit**: batches wider than the top bucket
  encode/pad chunk ``k+1`` while chunk ``k`` is in flight and fetch
  only verdict bits — host prep hides behind device work
  (``overlap_frac`` up from 0.0, regression-gated by
  ``tools/perf_sentinel.py``).

Workload #1 is ed25519 verify
(:class:`stellar_tpu.crypto.batch_verifier.BatchVerifier` — a thin
subclass, bit-identical to the pre-refactor module: every chaos /
device-domain / soak gate runs against this engine). Workload #2 is
batched SHA-256 (:class:`stellar_tpu.crypto.batch_hasher.BatchHasher`
over :mod:`stellar_tpu.ops.sha256`).

**Shared vs per-workload state.** The host's devices are process
properties, so everything that models THEM is shared across workloads:
the global dispatch breaker, the device probe and its verdict, the
per-device :mod:`~stellar_tpu.parallel.device_health` registry (the
same physical chip serves both workloads — a quarantine earned under
one applies to the other), the sticky HOST-ONLY integrity posture (a
machine caught corrupting any workload's bits has forfeited trust for
all of them), and the host-level dispatch counters
(``crypto.verify.dispatch.*`` — names kept for continuity). Everything
that models the WORK is per-plugin: serve/audit meters under the
plugin's ``metrics_ns``, resolve-phase spans under its ``span_ns``,
the differential oracle, and the audit comparison
(``docs/robustness.md`` "Engine and workload plugins").

Fault tolerance (``docs/robustness.md``): a device's worst failure
mode is a HANG, not an exception — a mid-flight death would park
``resolve`` in ``np.asarray`` forever. Every device interaction is
therefore (a) deadline-guarded (``VERIFY_DEVICE_DEADLINE_MS``), (b)
accounted to a circuit breaker — the PER-DEVICE one when the failure is
attributable to a mesh device, the process-wide one otherwise — and
(c) backed by host re-computation of the affected rows through the
plugin's oracle — degraded mode changes latency, never results. A chip
that returns WRONG BITS instead of hanging defeats all of the above,
so every resolve additionally re-computes a deterministic
content-seeded sample of device rows through the host oracle
(:mod:`stellar_tpu.crypto.audit`); a mismatch hard-quarantines the
device, flips the process HOST-ONLY, and re-computes the affected rows
— a corrupting accelerator never decides a result.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Callable, Optional, Sequence, Tuple

import numpy as np

from stellar_tpu.crypto import audit as audit_mod
from stellar_tpu.parallel import device_health, signer_tables
from stellar_tpu.utils import faults, resilience, tracing
from stellar_tpu.utils.metrics import registry
from stellar_tpu.utils.timeline import pipeline_timeline
from stellar_tpu.utils.transfer_ledger import transfer_ledger

__all__ = [
    "Workload", "BatchEngine",
    "device_available", "start_device_probe",
    "dispatch_health", "configure_dispatch",
    "dispatch_attribution", "phase_attribution", "dispatch_degraded",
    "host_only_mode", "note_shed_onset", "register_service_health",
    "service_health_snapshot", "register_fleet_health",
    "fleet_health_snapshot", "served_counts",
    "trace_ranges", "note_trace_event",
    "RESOLVE_PHASES", "RESOLVE_ROOT", "PHASE_SUFFIXES",
    "DEFAULT_BUCKET_SIZES",
]

_log = logging.getLogger("stellar_tpu.crypto")


# ---------------- dispatch resilience policy ----------------
# Env defaults let tools/bench set these without a Config; a node pushes
# its Config knobs through configure_dispatch() at setup. The knobs are
# properties of the host's devices, shared by every workload on the
# substrate.

DEADLINE_MS = float(os.environ.get("VERIFY_DEVICE_DEADLINE_MS", "8000"))
DISPATCH_RETRIES = int(os.environ.get("VERIFY_DISPATCH_RETRIES", "1"))
# Result-integrity audit: fraction of each device-served part
# re-checked through the host oracle (min 1 row per part; <= 0
# disables). The sample is derived from the batch CONTENT
# (crypto/audit.py) so consensus replicas audit identical rows.
AUDIT_RATE = float(os.environ.get("VERIFY_AUDIT_RATE", "0.02"))

# The production jit bucket ladder (the verify workload's
# default_verifier). Also the shape set the static overflow prover must
# cover — stellar_tpu.analysis.overflow proves the verify kernel at
# exactly these sizes (tools/analyze.py).
DEFAULT_BUCKET_SIZES = (128, 512, 2048, 4096, 8192, 16384)


# ---------------- resolve flight-recorder phases (ISSUE 5) ----------------
# Every phase of a blocking resolve is a span; the phases are DISJOINT
# wall-time intervals under the workload's root span, so summing their
# timer deltas attributes the blocking headline ("relay = X ms, device
# compute = Y ms, fetch = Z ms" — docs/observability.md). Phase names
# are ``<span_ns>.<suffix>`` — "verify.*" for the ed25519 workload
# (the pinned RESOLVE_PHASES contract), "hash.*" for SHA-256.
PHASE_SUFFIXES = ("prep", "bucket", "dispatch", "fetch", "audit",
                  "host_fallback")
RESOLVE_PHASES = tuple(f"verify.{s}" for s in PHASE_SUFFIXES)
RESOLVE_ROOT = "verify.blocking"


def phase_names(span_ns: str) -> Tuple[str, ...]:
    return tuple(f"{span_ns}.{s}" for s in PHASE_SUFFIXES)


def trace_ranges(ids) -> list:
    """Compress a per-row trace-ID list into ``[lo, hi)`` pairs — the
    exemplar form span/event records carry (``attrs["traces"]``), so a
    2048-row batch costs a handful of ints per record and matching
    (:func:`stellar_tpu.utils.tracing.trace_matches`) stays EXACT,
    never truncated. Contiguous runs (a submission's block of IDs)
    collapse to one pair; interleaved coalesced tickets produce one
    pair per run."""
    out: list = []
    for t in ids:
        t = int(t)
        if out and t == out[-1][1]:
            out[-1][1] = t + 1
        else:
            out.append([t, t + 1])
    return out


def note_trace_event(name: str, **attrs) -> None:
    """Flight-recorder instant event on behalf of the verify service
    (trace milestones: enqueue, coalesce, verdict, shed/reject). The
    service sits inside the consensus nondet-lint scope and may not
    import the clock-bearing tracing module — its recorder writes
    route through here, same policy as :func:`note_shed_onset`."""
    tracing.flight_recorder.note(name, **attrs)


def phase_attribution(before: dict, after: dict, reps: int = 1,
                      span_ns: str = "verify") -> dict:
    """Per-phase dispatch attribution from span-timer deltas, for any
    workload namespace.

    ``before``/``after`` are :func:`stellar_tpu.utils.tracing.
    span_totals` snapshots taken around the measured resolves. EVERY
    phase is reported (zero-count phases included), so a host-only
    record still carries the complete breakdown; ``coverage`` is the
    phase-sum over the blocking root span's time — the reconciliation
    the bench record asserts (>= 0.95 means the breakdown explains the
    headline, not a fraction of it).

    Phase deltas read the ROOT-ATTRIBUTED ``span.attr.<phase>`` timers
    (flushed only when a blocking root span completes — ISSUE 8), not
    the per-exit phase histograms: a snapshot taken mid-resolve, or
    concurrent service-path resolves with no blocking root, can
    therefore never inflate ``coverage`` with phase time whose root
    never finished (the re-shard/retry re-entry double-count)."""
    def delta(name, prefix="span.attr."):
        key = f"{prefix}{name}"
        b = before.get(key, {"count": 0, "sum_ms": 0.0})
        a = after.get(key, {"count": 0, "sum_ms": 0.0})
        return a["count"] - b["count"], a["sum_ms"] - b["sum_ms"]

    reps = max(1, int(reps))
    phases = {}
    phase_sum = 0.0
    for name in phase_names(span_ns):
        c, s = delta(name)
        phases[name] = {"count": c, "total_ms": round(s, 3),
                        "per_rep_ms": round(s / reps, 4)}
        phase_sum += s
    root_count, root_sum = delta(f"{span_ns}.blocking", prefix="span.")
    coverage = (phase_sum / root_sum) if root_sum > 0 else None
    return {
        "phases": phases,
        "span_sum_per_rep_ms": round(phase_sum / reps, 4),
        "blocking_span_per_rep_ms": round(root_sum / reps, 4),
        "blocking_span_count": root_count,
        "coverage": round(coverage, 4) if coverage is not None else None,
        "reps": reps,
    }


def dispatch_attribution(before: dict, after: dict, reps: int = 1) -> dict:
    """The verify workload's attribution (the pinned bench contract —
    exact shape of PR 5's ``batch_verifier.dispatch_attribution``)."""
    return phase_attribution(before, after, reps, span_ns="verify")


def _on_breaker_transition(old: str, new: str) -> None:
    registry.counter("crypto.verify.breaker.transitions").inc()
    registry.gauge("crypto.verify.breaker.state").set(new)
    _log.warning("verify-device breaker %s -> %s", old, new)
    if new == resilience.OPEN:
        # flight-recorder trigger: the spans leading into the trip
        # must survive to be read (docs/observability.md)
        tracing.flight_recorder.dump("breaker-open:verify-device")


_breaker = resilience.CircuitBreaker(
    name="verify-device",
    failure_threshold=int(os.environ.get(
        "VERIFY_BREAKER_FAILURE_THRESHOLD", "3")),
    backoff_min_s=float(os.environ.get(
        "VERIFY_BREAKER_BACKOFF_MIN_S", "1")),
    backoff_max_s=float(os.environ.get(
        "VERIFY_BREAKER_BACKOFF_MAX_S", "120")),
    on_transition=_on_breaker_transition)


def configure_dispatch(deadline_ms: Optional[float] = None,
                       dispatch_retries: Optional[int] = None,
                       failure_threshold: Optional[int] = None,
                       backoff_min_s: Optional[float] = None,
                       backoff_max_s: Optional[float] = None,
                       audit_rate: Optional[float] = None,
                       device_failure_threshold: Optional[int] = None,
                       device_backoff_min_s: Optional[float] = None,
                       device_backoff_max_s: Optional[float] = None,
                       signer_table_bytes: Optional[int] = None,
                       signer_table_enabled: Optional[bool] = None
                       ) -> None:
    """Push dispatch-resilience knobs (Config / tests); None keeps the
    current value. ``deadline_ms <= 0`` disables the resolve watchdog;
    ``audit_rate <= 0`` disables the result-integrity audit; the
    ``device_*`` knobs shape the per-device quarantine breakers; the
    ``signer_table_*`` knobs shape the hot-signer per-pubkey A-table
    cache (``stellar_tpu.parallel.signer_tables``). The knobs
    govern EVERY workload on the substrate (verify and hash dispatches
    share the devices whose health they model)."""
    global DEADLINE_MS, DISPATCH_RETRIES, AUDIT_RATE
    if deadline_ms is not None:
        DEADLINE_MS = float(deadline_ms)
    if dispatch_retries is not None:
        DISPATCH_RETRIES = max(0, int(dispatch_retries))
    if audit_rate is not None:
        AUDIT_RATE = float(audit_rate)
    _breaker.configure(failure_threshold=failure_threshold,
                       backoff_min_s=backoff_min_s,
                       backoff_max_s=backoff_max_s)
    device_health.get().configure(
        failure_threshold=device_failure_threshold,
        backoff_min_s=device_backoff_min_s,
        backoff_max_s=device_backoff_max_s)
    signer_tables.signer_table_cache.configure(
        max_bytes=signer_table_bytes,
        enabled=signer_table_enabled)


# ---------------- host-only mode (result-integrity posture) ----------------
# Once ANY device is caught returning wrong bits — for ANY workload —
# the process stops trusting the accelerator path entirely:
# quarantining the one chip bounds the blast radius, but a machine that
# corrupted once has forfeited the benefit of the doubt for consensus
# decisions. Sticky for the process lifetime (operators restart after
# replacing the part); tests reset via
# _reset_dispatch_state_for_testing.

_host_only = False
_host_only_lock = threading.Lock()


def _enter_host_only(reason: str) -> None:
    global _host_only
    with _host_only_lock:
        already = _host_only
        _host_only = True
    if not already:
        registry.gauge("crypto.verify.host_only").set(True)
        _log.error(
            "batch dispatch entering HOST-ONLY mode (%s): device "
            "results are no longer trusted for consensus decisions",
            reason)


def host_only_mode() -> bool:
    return _host_only


def dispatch_degraded() -> bool:
    """True when the accelerator path is unavailable to new work — the
    global breaker is OPEN or the process flipped host-only. This is
    the verify service's shed-ladder pressure input
    (:mod:`stellar_tpu.crypto.verify_service`): with effective
    capacity collapsed to the host oracle, the service sheds
    lowest-priority backlog instead of queueing to death."""
    return _host_only or _breaker.state == resilience.OPEN


# ---------------- resident verify service hooks ----------------
# verify_service.py sits ON TOP of this substrate and is inside the
# consensus nondet-lint scope, so it may not import the clock-bearing
# tracing layer directly; its flight-recorder trigger and health
# surface route through here instead.

_service_lock = threading.Lock()
_service_health_provider: Optional[Callable[[], dict]] = None


def register_service_health(provider: Optional[Callable[[], dict]]
                            ) -> None:
    """Install the resident verify service's snapshot callable so
    ``dispatch_health()`` (and the ``dispatch`` admin route) carries
    queue depths and shed/reject accounting next to the breaker state.
    ``None`` unregisters (tests)."""
    global _service_health_provider
    with _service_lock:
        _service_health_provider = provider


def service_health_snapshot() -> dict:
    """The registered service's snapshot, or ``{"running": False}``
    when no service ever started — shared by ``dispatch_health()``
    and the ``service`` admin route."""
    provider = _service_health_provider
    return provider() if provider is not None else {"running": False}


_fleet_health_provider: Optional[Callable[[], dict]] = None


def register_fleet_health(provider: Optional[Callable[[], dict]]
                          ) -> None:
    """Install the replicated fleet's snapshot callable (ISSUE 17) so
    ``dispatch_health()`` (and the ``fleet`` admin route) carries
    per-replica states and the fleet conservation law next to the
    single-service surface. ``None`` unregisters (tests)."""
    global _fleet_health_provider
    with _service_lock:
        _fleet_health_provider = provider


def fleet_health_snapshot() -> dict:
    """The registered fleet's snapshot, or ``{"enabled": False}``
    when no fleet ever started — shared by ``dispatch_health()`` and
    the ``fleet`` admin route."""
    provider = _fleet_health_provider
    return provider() if provider is not None else {"enabled": False}


def note_shed_onset(reason: str) -> None:
    """First-onset load-shed trigger: dump the flight recorder so the
    spans and queue events leading INTO the overload survive to be
    read (same policy as breaker trips and audit mismatches —
    docs/observability.md)."""
    registry.counter("crypto.verify.service.shed_onsets").inc()
    tracing.flight_recorder.dump(f"service-shed:{reason}")


def served_counts() -> dict:
    """Process-wide items-served tally by backend for the VERIFY
    workload — the attribution bench.py records so a silent fallback
    can never be reported as a device number. (Other workloads tally
    under their own ``metrics_ns``, e.g. ``crypto.hash.serve.*``.)"""
    return {
        "device": registry.meter("crypto.verify.serve.device").count,
        "host_fallback": registry.meter(
            "crypto.verify.serve.host_fallback").count,
    }


def dispatch_health() -> dict:
    """Degradation observability (info endpoint / `dispatch` admin
    route): breaker state, backend attribution, fallback/retry/deadline
    counters, active knobs."""
    return {
        "device_state": _device_state or "unprobed",
        "breaker": _breaker.snapshot(),
        "deadline_ms": DEADLINE_MS,
        "dispatch_retries": DISPATCH_RETRIES,
        "served": served_counts(),
        "fallback_chunks": registry.meter(
            "crypto.verify.dispatch.fallback").count,
        "deadline_misses": registry.counter(
            "crypto.verify.dispatch.deadline_miss").count,
        "retries": registry.counter("crypto.verify.dispatch.retry").count,
        "short_circuits": registry.counter(
            "crypto.verify.dispatch.short_circuit").count,
        "host_only": _host_only,
        "audit": {
            "rate": AUDIT_RATE,
            "sampled": registry.counter(
                "crypto.verify.audit.sampled").count,
            "mismatches": registry.counter(
                "crypto.verify.audit.mismatch").count,
        },
        "device_health": device_health.get().snapshot(),
        "watchdog": resilience.watchdog_stats(),
        "flight_recorder": tracing.flight_recorder.stats(),
        "transfer": transfer_ledger.totals(),
        "signer_tables": signer_tables.signer_table_cache.snapshot(),
        "service": service_health_snapshot(),
        "fleet": fleet_health_snapshot(),
    }


_failure_traceback_logged = threading.Event()


def _note_device_failure(stage: str, exc: BaseException,
                         dev_idx: Optional[int] = None) -> None:
    """One failing device interaction: breaker accounting + metrics.
    ``dev_idx`` attributes the failure to ONE mesh device (only its
    breaker opens — the fault-domain boundary); None means the failure
    is not attributable (single-device dispatch) and feeds the
    process-wide breaker. The caller re-computes the affected rows on
    the host."""
    registry.meter("crypto.verify.dispatch.fallback").mark()
    if dev_idx is None:
        _breaker.record_failure()
    elif device_health.get().record_failure(dev_idx):
        # correlated-outage escalation: each quarantine ONSET counts
        # one failure against the global breaker. A single sick chip
        # (one quarantine, then healthy traffic resets the streak)
        # leaves the mesh serving; a whole-host outage quarantines
        # device after device with no intervening success, reaches the
        # global threshold, and short-circuits the remaining chunks —
        # bounding the outage at global_threshold quarantines instead
        # of n_devices independent ones
        tracing.flight_recorder.dump(f"quarantine:device{dev_idx}")
        _breaker.record_failure()
    # the first failure carries its traceback: a chip compiler error
    # otherwise shows only its first line, and every later failure
    # repeats the same cause
    first = not _failure_traceback_logged.is_set()
    _failure_traceback_logged.set()
    _log.warning(
        "device%s %s failed (%s: %s) — affected rows re-computed on "
        "the host oracle",
        "" if dev_idx is None else f" {dev_idx}",
        stage, type(exc).__name__, exc, exc_info=exc if first else None)


def _resolve_budget_s() -> Optional[float]:
    """Watchdog budget for one device-array fetch, or None (unguarded).
    Guarded whenever a real accelerator answered the probe (hangs are
    its observed failure mode) or a chaos fault is armed; UNGUARDED on
    jax-CPU/unprobed processes — XLA-on-CPU test executions are slow
    but cannot hang, and a false deadline trip there would
    silently reroute differential tests to the host oracle."""
    if DEADLINE_MS <= 0:
        return None
    if faults.is_active(faults.RESOLVE) or \
            faults.is_active(faults.DISPATCH) or \
            faults.is_active(faults.TRANSFER):
        return DEADLINE_MS / 1000.0
    if _device_state in (None, "cpu"):
        return None
    return DEADLINE_MS / 1000.0


def _fetch(dev, dev_idx: Optional[int] = None,
           span_ns: str = "verify",
           traces=None) -> np.ndarray:
    """The blocking half of a dispatch (runs under the watchdog).
    ``dev_idx`` attributes the fetch to one mesh device for per-device
    chaos faults — including result corruption, applied here so the
    wrong bits flow through exactly the path real corruption would.
    The span opens on the POOL WORKER with the submitter's propagated
    context, so a fetch that hangs appears OPEN in a flight-recorder
    dump, parent-linked to the resolve that dispatched it; ``traces``
    carries the part's trace-ID exemplar ranges into the worker-side
    span. The transfer ledger is NOT written here: a fetch that misses
    its watchdog deadline keeps running on the abandoned pool worker,
    and a late completion would inflate the ledger against the
    engine's own delivered-bytes tally (and mutate a resolve token
    whose ring snapshot was already taken) — the caller records d2h at
    the moment it actually accepts the result."""
    attrs = {"device": dev_idx}
    if traces:
        attrs["traces"] = traces
    with tracing.span(f"{span_ns}.fetch.device", **attrs):
        faults.inject(faults.RESOLVE, device=dev_idx)
        arr = np.asarray(dev)
        return faults.corrupt_verdicts(faults.RESOLVE, dev_idx, arr)


# ---------------- the workload plugin interface ----------------


class Workload:
    """What a batch workload must provide to ride the engine.

    The engine owns dispatch, fault domains, audit SAMPLING, failover,
    and instrumentation; the plugin owns everything the work MEANS:
    encoding, the kernel, the host oracle, and result composition.
    Subclasses override every method from ``encode`` on (the base
    raises); the two hooks before it have defaults.

    Contracts:

    * every array of ``encode``'s tuple (and of ``pad_rows``) carries
      the batch on its LEADING axis — the engine pads, splits into
      per-device sub-chunks, and slices along axis 0;
    * ``kernel_fn``'s callable takes the encoded arrays (padded to a
      bucket) and returns ONE array, batch axis leading — the engine
      jit-caches it per dispatch shape and slices rows back out;
    * ``host_result`` must be bit-identical to the composed device
      decision for gate-passing rows: it is both the failover path and
      the result-integrity audit's source of truth.
    """

    #: dotted namespace for serve/audit meters, e.g. "crypto.verify"
    metrics_ns = "workload"
    #: span-name prefix for the resolve phases, e.g. "verify"
    span_ns = "workload"
    #: kernel-variant key; None marks an engine's PRIMARY plugin. A
    #: variant plugin (a different kernel over the same result rows,
    #: submitted via ``submit(..., variant=...)`` — e.g. the hot-signer
    #: cached-table kernel, ISSUE 16) must set a unique name: its jit
    #: wrappers are cached under ``variant_name`` so
    #: ``sorted(engine._kernels)`` stays exactly the primary shape set
    #: the compile-reuse invariant pins.
    variant_name: Optional[str] = None

    def on_audit_conviction(self, items: Sequence) -> None:
        """Hook: the result-integrity audit just CONVICTED the serving
        device over a part these items rode (the engine has already
        quarantined the chip and flipped host-only; the rows are being
        host re-computed). Plugins holding derived state about the
        items — e.g. the hot-signer table cache, whose resident tables
        must never outlive the audit that caught the batch they
        served — evict it here. Default: nothing to evict."""

    def audit_material(self, encoded: tuple, lo: int, hi: int) -> bytes:
        """Hook: the bytes that seed the audit sample of rows
        ``lo:hi`` of ``encode``'s arrays. They must be a function of
        the batch content alone, so replicas fed the same batch audit
        the same rows. Default: every encoded array's rows, joined. A
        plugin whose arrays carry data derived from what another array
        already commits to may leave it out: it adds hashing time, not
        determinism."""
        return b"".join(x[lo:hi].tobytes() for x in encoded)

    def encode(self, items: Sequence) -> Tuple[np.ndarray, tuple]:
        """Host prep: ``items`` -> ``(gate, arrays)``. ``gate`` is a
        bool row mask — True where the device result DECIDES the row's
        outcome (False rows are filled by :meth:`finalize` without
        trusting device bits, and are excluded from audit sampling —
        auditing a row the gate already decided would be vacuous)."""
        raise NotImplementedError

    def pad_rows(self) -> tuple:
        """One syntactically-valid padding row per encoded array
        (shape ``(1, ...)``), repeated to fill a bucket. Padded lanes'
        results are sliced off, never read."""
        raise NotImplementedError

    def kernel_fn(self):
        """The traceable device function (imported lazily so a module
        import never touches jax)."""
        raise NotImplementedError

    def empty_result(self, n: int) -> np.ndarray:
        """Zero-filled result rows (the engine scatters into this)."""
        raise NotImplementedError

    def host_result(self, items: Sequence) -> np.ndarray:
        """Bit-identical host computation of result rows — the
        failover path AND the audit oracle."""
        raise NotImplementedError

    def finalize(self, gate: np.ndarray, out: np.ndarray,
                 items: Sequence) -> np.ndarray:
        """Compose the caller-visible result from the gate and the
        resolved rows (device- or host-served)."""
        raise NotImplementedError


class BatchEngine:
    """Generic batched device dispatcher with a jit bucket cache.

    Args:
      plugin: the :class:`Workload`.
      mesh: optional 1-D ``jax.sharding.Mesh``; if given (and it spans
        >= 2 devices), buckets divisible by the device count are split
        into per-device SUB-CHUNKS of the plain kernel — one
        attributable dispatch per device, quarantine/re-shard per
        ``stellar_tpu.parallel.device_health`` — instead of one
        whole-bucket call. Non-divisible buckets (and mesh=None) use
        a single whole-bucket dispatch under the global breaker.
      bucket_sizes: padded batch sizes, ascending; each dispatch shape
        compiles once (per serving device on the mesh path).
    """

    def __init__(self, plugin: Workload, mesh=None,
                 bucket_sizes=(128, 512, 2048)):
        self._plugin = plugin
        self._ns = plugin.metrics_ns
        self._span_ns = plugin.span_ns
        self._mesh = mesh
        self._devices = None
        if mesh is not None:
            from stellar_tpu.parallel.mesh import mesh_devices
            devs = mesh_devices(mesh)
            if len(devs) >= 2:
                self._devices = devs
        self._buckets = tuple(sorted(bucket_sizes))
        # jit-wrapper cache keyed by DISPATCH SHAPE (rows per kernel
        # call: the bucket on single-device hosts, bucket // n_devices
        # on a mesh): written from any thread that dispatches (trickle
        # leaders, chaos tests, the close path) — guarded, the wrapper
        # itself is built outside the lock (cheap; the compile happens
        # lazily at first call).
        self._kernels = {}
        # variant-kernel caches keyed variant_name -> {shape: jit
        # wrapper}: kernel VARIANTS (the hot-signer path) never
        # leak into the primary dict above, so the pinned
        # `sorted(self._kernels)` shape sets survive variant traffic
        self._kernels_variants = {}
        self._kernels_lock = threading.Lock()
        # per-instance backend attribution (items served), mirrored into
        # the process-wide meters: bench and the chaos tests read these
        self._stats_lock = threading.Lock()
        self.served = {"device": 0, "host-fallback": 0}
        self.device_served = {}  # mesh device index -> items served
        self.deadline_misses = 0
        self.retries = 0
        self.audit_mismatches = 0
        # how many buckets rode the single coalesced per-mesh upload
        # — the engine's own view of that lever, next to the ledger's
        # byte view
        self.coalesced_dispatches = 0
        # engine-side byte accounting, derived INDEPENDENTLY from the
        # dispatch shapes (prod(shape) * itemsize at the placement
        # sites) — the reconciliation oracle the transfer ledger's
        # tier-1 self-check compares against, so a new transfer path
        # that forgets its ledger hook shows up as a byte gap
        self.shipped_bytes = 0
        self.fetched_bytes = 0

    def _mark_served(self, kind: str, n: int,
                     dev_idx: Optional[int] = None) -> None:
        with self._stats_lock:
            self.served[kind] += n
            if dev_idx is not None:
                self.device_served[dev_idx] = \
                    self.device_served.get(dev_idx, 0) + n
        registry.meter(self._ns + ".serve." +
                       ("device" if kind == "device" else
                        "host_fallback")).mark(n)

    # ---------------- device dispatch ----------------

    def _kernel_for(self, n: int, *, plugin=None):
        # keyword-only `plugin` keeps the positional signature stable
        # (harnesses call `_kernel_for(shape)` directly to pre-warm)
        if plugin is None or plugin is self._plugin:
            plugin = self._plugin
            cache = self._kernels
        else:
            with self._kernels_lock:
                cache = self._kernels_variants.setdefault(
                    plugin.variant_name, {})
        with self._kernels_lock:
            kernel = cache.get(n)
        if kernel is None:
            import jax
            # one plain jit wrapper per dispatch shape; on the mesh
            # path placement follows the committed inputs, so the SAME
            # wrapper serves every device (jax caches one executable
            # per (shape, device) underneath)
            built = jax.jit(plugin.kernel_fn())
            with self._kernels_lock:
                # setdefault: a racing builder's wrapper wins once —
                # both wrappers trace identically, so the loser is
                # just garbage, never a different kernel
                kernel = cache.setdefault(n, built)
        return kernel

    def _bucket(self, n: int) -> int:
        for b in self._buckets:
            if n <= b:
                return b
        return self._buckets[-1]

    def _dispatch_one(self, arrays: tuple, bsize: int,
                      dev_idx: Optional[int], *, plugin=None):
        """One kernel call (whole padded bucket, or one per-device
        sub-chunk): inject-point + retry + failure attribution. Returns
        the in-flight device array, or None (host fallback)."""
        attempts = 1 + DISPATCH_RETRIES
        for attempt in range(attempts):
            try:
                faults.inject(faults.DISPATCH, device=dev_idx)
                return self._kernel_for(bsize, plugin=plugin)(*arrays)
            except Exception as e:
                if attempt + 1 < attempts:
                    registry.counter(
                        "crypto.verify.dispatch.retry").inc()
                    with self._stats_lock:
                        self.retries += 1
                else:
                    _note_device_failure("dispatch", e, dev_idx)
        return None

    def _ship_accounting(self, a) -> int:
        """Engine-side independent byte count of one upload (shape ×
        itemsize — NOT the ledger's ``nbytes`` read, so the two tallies
        reconcile only when both paths saw the same arrays)."""
        total = int(np.prod(a.shape)) * a.dtype.itemsize
        with self._stats_lock:
            self.shipped_bytes += total
        return total

    def _place_operands(self, tok, arrays: tuple, dest,
                        dev_idx: Optional[int] = None) -> tuple:
        """Commit one operand tuple to ``dest`` (a device, a per-mesh
        Sharding, or None = the default device) and return the placed
        tuple. Each operand is one ``device_put``, recorded by the
        transfer ledger and by the engine's own shape-derived tally —
        the reconciliation oracle of ``tools/transfer_selfcheck.py``."""
        import jax
        placed = []
        shipped = 0
        with tracing.span(f"{self._span_ns}.place") as sp:
            for a in arrays:
                placed.append(jax.device_put(a, dest))
                transfer_ledger.record_h2d(tok, a, device=dev_idx)
                shipped += self._ship_accounting(a)
            sp.set_attrs(h2d_bytes=shipped)
        return tuple(placed)

    def _coalesced_upload(self, arrays: tuple, tok):
        """ONE sharded h2d upload of the whole padded bucket — the
        coalesced per-mesh transfer replacing n_devices separate
        ``device_put`` round trips. Each operand is committed once
        under a batch-axis NamedSharding; its per-device shards then
        feed the SAME per-device sub-chunk executables the legacy path
        compiles, so fault attribution and the compile-reuse invariant
        are untouched.

        Returns ``{dev_idx: operand_tuple}`` of committed shard
        arrays, or ``None`` when the upload failed (the caller falls
        back to the attributable per-device upload path, which
        re-encounters and properly accounts the failure)."""
        from jax.sharding import NamedSharding, PartitionSpec
        n_dev = len(self._devices)
        try:
            # the upload carries every device's shard: a per-device
            # transfer fault (stall-transfer:<idx>, fail-device) armed
            # for ANY device of the mesh sees the coalesced put
            for di in range(n_dev):
                faults.inject(faults.TRANSFER, device=di)
            sharding = NamedSharding(
                self._mesh, PartitionSpec(self._mesh.axis_names[0]))
            placed = self._place_operands(
                tok, arrays, dest=sharding, dev_idx=None)
        except Exception as e:
            registry.counter(
                "crypto.verify.dispatch.coalesce_fallback").inc()
            _log.warning(
                "coalesced per-mesh upload failed (%s: %s) — "
                "falling back to per-device uploads",
                type(e).__name__, e)
            return None
        by_dev = {di: [] for di in range(n_dev)}
        for op in placed:
            shard_by_device = {s.device: s.data
                               for s in op.addressable_shards}
            for di, dev in enumerate(self._devices):
                by_dev[di].append(shard_by_device[dev])
        with self._stats_lock:
            self.coalesced_dispatches += 1
        return {di: tuple(ops) for di, ops in by_dev.items()}

    def _dispatch_parts(self, arrays: tuple, b: int, chunk: int,
                        tok=None, traces=None, ptok=None,
                        plugin=None):
        """Split one padded bucket into per-device sub-chunks over the
        CURRENTLY HEALTHY devices — the degraded-mesh re-shard.

        The sub-chunk shape is fixed at ``b // n_devices`` for the FULL
        mesh size, independent of how many devices survive: quarantine
        only changes which healthy device serves how many sub-chunks
        (round-robin over the survivors), never the shapes — and every
        survivor already compiled its sub-chunk executable when it
        served its own share, so degradation and regrowth never pay a
        fresh XLA compile (the invariant `docs/robustness.md` pins).

        On a fully healthy mesh serving a full bucket (identity
        assignment) the operands ride ONE coalesced sharded upload
        (:meth:`_coalesced_upload`) and each device's kernel call
        consumes its shard in place — same executables, same
        per-device injection points, same per-part output arrays, so
        ``DeviceHealth`` attribution, breakers, the sampled audit and
        degraded re-shard all keep working. Any degradation (or a
        short chunk, or a failed coalesced upload) takes the legacy
        per-device upload loop below.

        A half-open device's breaker grants exactly one sub-chunk per
        backoff window — probation traffic IS the re-probe; success
        regrows the device into the rotation.

        Returns part records ``[lo, hi, dev_idx, arr]``: valid rows
        ``lo:hi`` of the chunk, serving device, in-flight array (None =
        host fallback). All-padding tail sub-chunks are skipped."""
        n_dev = len(self._devices)
        sub = b // n_dev
        # sub-chunks that carry real rows (pure-padding tails are
        # never dispatched)
        n_parts = min(n_dev, -(-chunk // sub))
        assignment = device_health.get().assign_parts(n_dev, n_parts)
        if assignment != list(range(n_parts)):
            # degraded-mesh re-shard decision: record WHO serves WHAT
            # (or None = host fallback) so a dump of a degraded window
            # shows the assignment that produced its latencies
            reshard_attrs = {"assignment": list(assignment),
                             "parts": n_parts, "devices": n_dev}
            if traces:
                reshard_attrs["traces"] = traces
            tracing.flight_recorder.note(
                f"{self._span_ns}.reshard", **reshard_attrs)
        parts = []
        if n_parts == n_dev and assignment == list(range(n_dev)):
            per_device = self._coalesced_upload(arrays, tok)
            if per_device is not None:
                for j, di in enumerate(assignment):
                    lo = j * sub
                    hi = min(lo + sub, chunk)
                    arr = self._dispatch_one(
                        per_device[di], bsize=sub, dev_idx=di,
                        plugin=plugin)
                    if arr is not None:
                        # pipeline timeline: a COMMITTED kernel call
                        # opens this device's busy interval (ISSUE 10)
                        pipeline_timeline.note_dispatch(ptok, di)
                    parts.append([lo, hi, di, arr])
                return parts
        for j, di in enumerate(assignment):
            lo = j * sub
            hi = min(lo + sub, chunk)
            if di is None:
                # zero survivors and no probation grants: the whole
                # mesh is quarantined — only now does the engine
                # fall back to the host oracle
                registry.counter(
                    "crypto.verify.dispatch.short_circuit").inc()
                parts.append([lo, hi, None, None])
                continue
            subs = tuple(x[lo:lo + sub] for x in arrays)
            try:
                faults.inject(faults.TRANSFER, device=di)
                placed = self._place_operands(
                    tok, subs, dest=self._devices[di], dev_idx=di)
            except Exception as e:
                _note_device_failure("transfer", e, di)
                parts.append([lo, hi, di, None])
                continue
            arr = self._dispatch_one(placed, bsize=sub, dev_idx=di,
                                     plugin=plugin)
            if arr is not None:
                # pipeline timeline: a COMMITTED kernel call opens
                # this device's busy interval (ISSUE 10)
                pipeline_timeline.note_dispatch(ptok, di)
            parts.append([lo, hi, di, arr])
        return parts

    def _dispatch_device(self, *encoded: np.ndarray, tok=None,
                         trace_ids=None, ptok=None, plugin=None):
        """Dispatch padded/chunked batches to the jitted kernel without
        blocking; returns a list of (slice, chunk_len, parts) where
        parts are per-device sub-chunk records (single-device hosts get
        one whole-bucket part). A part whose dispatch raises (or that
        an open breaker refuses, or host-only mode) carries ``None``
        and is re-computed on the host at resolve time; transient
        dispatch exceptions get ``DISPATCH_RETRIES`` fresh attempts
        first. ``tok`` threads the resolve's transfer-ledger token;
        ``trace_ids`` the per-item trace IDs (exemplar ranges land on
        every dispatch span)."""
        n = encoded[0].shape[0]
        top = self._buckets[-1]
        if plugin is None:
            plugin = self._plugin
        pads = plugin.pad_rows()
        pending = []
        start = 0
        host_only = _host_only
        while start < n:
            chunk = min(top, n - start)
            b = self._bucket(chunk)
            pad = b - chunk
            sl = slice(start, start + chunk)
            tr = trace_ranges(trace_ids[sl]) if trace_ids else None

            def _padded_inputs():
                # built ONLY for chunks that will actually dispatch:
                # a host-only or breaker-refused chunk must not pay
                # bucket-sized copies it never reads (nor charge
                # them to the bucket phase of the attribution).
                # Pipeline-wise the padding build is host PREP: a
                # device idle while it runs is a prep bubble.
                with tracing.span(f"{self._span_ns}.bucket"), \
                        pipeline_timeline.host_phase(ptok, "prep"):
                    return tuple(
                        np.concatenate([x[sl], np.repeat(p, pad, 0)])
                        for x, p in zip(encoded, pads))

            def _span_attrs(**extra):
                at = dict(extra)
                if tr:
                    at["traces"] = tr
                return at

            if host_only:
                # integrity posture: no device dispatch at all
                parts = [[0, chunk, None, None]]
            elif self._devices is not None and \
                    b % len(self._devices) == 0:
                # the global breaker gates the mesh path too: a
                # correlated outage (escalated quarantines) opens it
                # and short-circuits whole chunks; its half-open grant
                # admits one chunk as the recovery probe
                if _breaker.allow():
                    arrays = _padded_inputs()
                    with tracing.span(f"{self._span_ns}.dispatch",
                                      **_span_attrs(devices=True)):
                        parts = self._dispatch_parts(
                            arrays, b, chunk, tok=tok, traces=tr,
                            ptok=ptok, plugin=plugin)
                else:
                    registry.counter(
                        "crypto.verify.dispatch.short_circuit").inc()
                    parts = [[0, chunk, None, None]]
            elif _breaker.allow():
                arrays = _padded_inputs()
                with tracing.span(f"{self._span_ns}.dispatch",
                                  **_span_attrs()):
                    # whole-bucket operands commit to the default
                    # device before the kernel call
                    try:
                        faults.inject(faults.TRANSFER, device=None)
                        placed = self._place_operands(
                            tok, arrays, dest=None, dev_idx=None)
                        arr = self._dispatch_one(placed, b, None,
                                                 plugin=plugin)
                    except Exception as e:
                        _note_device_failure("transfer", e, None)
                        arr = None
                    if arr is not None:
                        pipeline_timeline.note_dispatch(ptok, None)
                parts = [[0, chunk, None, arr]]
            else:
                registry.counter(
                    "crypto.verify.dispatch.short_circuit").inc()
                parts = [[0, chunk, None, None]]
            pending.append((sl, chunk, parts))
            start += chunk
        return pending

    # ---------------- public API ----------------

    def _prep(self, items: Sequence, plugin=None):
        # host-side prep phase: byte recode into the on-wire arrays
        # plus the plugin's eligibility gate
        with tracing.span(f"{self._span_ns}.prep"):
            return (plugin or self._plugin).encode(items)

    def submit(self, items: Sequence, trace_ids=None,
               variant=None) -> Callable[[], np.ndarray]:
        """Asynchronous batch: host prep + non-blocking device
        dispatch, PIPELINED per bucket chunk (ISSUE 12).

        Batches wider than the top bucket are encoded and dispatched
        chunk by chunk: while chunk ``k``'s kernels are in flight on
        device, the host encodes and pads chunk ``k+1`` — the prep of
        every chunk after the first hides behind in-flight device
        work, which is exactly the ``overlap_frac`` the
        pipeline-bubble profiler measures (0.0 under the old
        encode-everything-then-dispatch loop). The resolver then
        fetches only the result rows (verdict bits / digest words),
        never the operands.

        Returns a zero-arg resolver; calling it blocks on the device
        results and returns the per-item result rows. Multiple
        submitted batches additionally pipeline on device (jax async
        dispatch), overlapping transfer and compute across batches.

        ``trace_ids`` (ISSUE 8): optional per-item trace IDs, aligned
        with ``items``. They survive sub-chunking, re-shard, audit and
        host failover — every dispatch/fetch/audit/fallback span and
        recorder event for a part carries the part's exemplar ranges
        (``trace_ranges``), so one item's path through the engine
        reconstructs from the flight recorder (the ``trace`` admin
        route).

        ``variant`` (ISSUE 16): optional :class:`Workload` replacing
        the primary plugin for THIS submit only — a different kernel
        over the same result rows (the hot-signer cached-table path).
        Its jit wrappers live in the per-variant cache, so the pinned
        primary bucket shapes never grow; dispatch, fault domains,
        breakers, audit and failover are untouched.
        """
        plugin = variant if variant is not None else self._plugin
        n = len(items)
        if n == 0:
            return lambda: plugin.empty_result(0)
        items = list(items)  # pinned for possible host re-computation
        trace_ids = list(trace_ids) if trace_ids is not None else None
        top = self._buckets[-1]
        # pipeline timeline (ISSUE 10): the token's lifetime IS the
        # resolve wall; a gate-empty early return simply drops it
        # (begin registers nothing — same policy as the transfer
        # ledger's tokens)
        ptok = pipeline_timeline.begin(self._ns)
        tok = transfer_ledger.begin(self._ns)
        # pending: (global slice, chunk, parts, gate_c, encoded_c) —
        # the per-chunk gate and encoded arrays stay with their chunk
        # (the audit samples against the bytes that actually
        # dispatched)
        pending = []
        gates = []
        start = 0
        while start < n:
            chunk = min(top, n - start)
            sl = slice(start, start + chunk)
            with pipeline_timeline.host_phase(ptok, "prep"):
                gate_c, encoded_c = self._prep(items[sl], plugin)
            gates.append(gate_c)
            if gate_c.any():
                (_psl, _pchunk, parts), = self._dispatch_device(
                    *encoded_c, tok=tok,
                    trace_ids=(trace_ids[sl] if trace_ids else None),
                    ptok=ptok, plugin=plugin)
            else:
                # no row of this chunk reads device bits: the plugin
                # finalizes (gate-fail fill / host hashing) without a
                # dispatch
                parts = []
            pending.append((sl, chunk, parts, gate_c, encoded_c))
            start += chunk
        gate = gates[0] if len(gates) == 1 else np.concatenate(gates)
        if not any(p for _sl, _c, p, _g, _e in pending):
            # nothing dispatched at all — the dropped tokens were
            # never registered, and the ring stays clean
            out0 = plugin.empty_result(n)
            return lambda: plugin.finalize(gate, out0, items)

        def _part_traces(gl: int, gh: int):
            return trace_ranges(trace_ids[gl:gh]) if trace_ids \
                else None

        def _audit_part(vals: np.ndarray, sl: slice, lo: int, hi: int,
                        di: Optional[int], gate_c: np.ndarray,
                        encoded_c: tuple) -> bool:
            """Sampled result-integrity audit of one device-served
            part (chunk-local rows ``lo:hi`` of the chunk at ``sl``):
            re-compute a content-seeded sample through the host oracle
            and compare against the COMPOSED result (the quantity
            pinned bit-identical to the plugin's oracle). The sample
            is seeded from the part's encoded rows as the plugin's
            ``audit_material`` selects them (a function of the batch
            content; the span's ``material_bytes`` attr is its size).
            Only rows that PASSED the gate are sampled: a gate-failed
            row's outcome never reads device bits, so auditing it
            would be vacuous (and a device-predictable blind spot).
            True = clean (or nothing to audit)."""
            gl, gh = sl.start + lo, sl.start + hi
            audit_attrs = {"device": di}
            atr = _part_traces(gl, gh)
            if atr:
                audit_attrs["traces"] = atr
            with tracing.span(f"{self._span_ns}.audit", **audit_attrs), \
                    pipeline_timeline.host_phase(ptok, "audit"):
                with tracing.span(
                        f"{self._span_ns}.audit.sample") as sp:
                    material = plugin.audit_material(encoded_c, lo, hi)
                    sp.set_attrs(material_bytes=len(material))
                    eligible = np.flatnonzero(gate_c[lo:hi]).tolist()
                    idxs = audit_mod.sample_rows(material, eligible,
                                                 AUDIT_RATE)
                if not idxs:
                    return True
                registry.counter(self._ns + ".audit.sampled").inc(
                    len(idxs))
                with tracing.span(f"{self._span_ns}.audit.oracle"):
                    want = plugin.host_result(
                        [items[gl + i] for i in idxs])
                    got_comp = np.stack([np.asarray(vals[i])
                                         for i in idxs])
                    clean = bool((np.asarray(want) == got_comp).all())
            # verdict lands in both evidence streams: the per-device
            # health registry (MULTICHIP fault-domain evidence) and
            # the flight recorder (visible in dumps near the spans)
            device_health.get().note_audit(di, ok=clean,
                                           sampled=len(idxs))
            verdict_attrs = audit_mod.verdict_record(
                di, gl, gh, len(idxs), clean)
            ptr = _part_traces(gl, gh)
            if ptr:
                verdict_attrs["traces"] = ptr
            tracing.flight_recorder.note(
                f"{self._span_ns}.audit.verdict", **verdict_attrs)
            return clean

        def _resolve_impl() -> np.ndarray:
            out = plugin.empty_result(n)
            for sl, chunk, parts, gate_c, encoded_c in pending:
                for lo, hi, di, arr in parts:
                    got = None
                    accepted = False
                    ptr = _part_traces(sl.start + lo, sl.start + hi)
                    # _host_only is re-read PER PART: once any part's
                    # audit proves corruption, the remaining
                    # already-dispatched parts of this very batch are
                    # host re-computed too — the batch that convicted
                    # the machine must not let device bits decide its
                    # other rows
                    if arr is not None and not _host_only:
                        # an OPEN breaker short-circuits this fault
                        # domain's remaining parts so one outage costs
                        # threshold x deadline, not parts x deadline;
                        # state (not allow()) is checked because a
                        # half-open part already holds its grant from
                        # dispatch time and must be fetched, not
                        # refused
                        gate_br = _breaker if di is None else \
                            device_health.get().breaker(di)
                        if gate_br.state != resilience.OPEN:
                            # the fetch span covers the whole
                            # fetch/deadline race; a trip dumps while
                            # it (and the worker-side device span) are
                            # still open, so the dump shows exactly
                            # where the hang is parked
                            fetch_attrs = {"device": di}
                            if ptr:
                                fetch_attrs["traces"] = ptr
                            with tracing.span(f"{self._span_ns}.fetch",
                                              **fetch_attrs), \
                                    pipeline_timeline.host_phase(
                                        ptok, "fetch"):
                                try:
                                    got = resilience.call_with_deadline(
                                        lambda d=arr, i=di:
                                        _fetch(d, i, self._span_ns,
                                               ptr),
                                        _resolve_budget_s(),
                                        name=f"{self._span_ns}-resolve")
                                except resilience.DeadlineExceeded as e:
                                    registry.counter(
                                        "crypto.verify.dispatch."
                                        "deadline_miss").inc()
                                    with self._stats_lock:
                                        self.deadline_misses += 1
                                    _note_device_failure(
                                        "resolve-deadline", e, di)
                                    tracing.flight_recorder.dump(
                                        "watchdog-timeout:device"
                                        f"{'-global' if di is None else di}")
                                except Exception as e:
                                    _note_device_failure(
                                        "resolve", e, di)
                        else:
                            registry.counter(
                                "crypto.verify.dispatch."
                                "short_circuit").inc()
                    gl, gh = sl.start + lo, sl.start + hi
                    if got is not None:
                        full = np.asarray(got)
                        vals = full[:hi - lo]
                        # all three accountings record DELIVERED
                        # results at this one point, so a
                        # deadline-missed fetch that later completes
                        # on the abandoned pool worker can never skew
                        # ledger-vs-engine reconciliation (nor close
                        # a busy interval the engine already gave up
                        # on)
                        transfer_ledger.record_d2h(tok, full,
                                                   device=di)
                        pipeline_timeline.note_delivery(ptok, di)
                        accepted = True
                        fetched = int(np.prod(full.shape)) * \
                            full.dtype.itemsize
                        with self._stats_lock:
                            self.fetched_bytes += fetched
                        if not _audit_part(vals, sl, lo, hi, di,
                                           gate_c, encoded_c):
                            # wrong bits: hard-quarantine the chip,
                            # stop trusting the accelerator path, and
                            # re-compute the whole part on the host —
                            # the corrupted rows never surface
                            registry.counter(
                                self._ns + ".audit.mismatch").inc()
                            with self._stats_lock:
                                self.audit_mismatches += 1
                            if di is not None:
                                device_health.get().quarantine(
                                    di, reason="audit-mismatch")
                            else:
                                _breaker.trip()
                            tracing.flight_recorder.dump(
                                f"audit-mismatch:device{di}")
                            _enter_host_only(
                                "result-integrity audit mismatch on "
                                f"device {di}")
                            # conviction hook: derived per-item state
                            # (the hot-signer table cache) must not
                            # outlive the audit that caught the part
                            # it served
                            plugin.on_audit_conviction(items[gl:gh])
                            _log.error(
                                "audit mismatch: device %s returned "
                                "wrong %s bits for rows %d:%d",
                                di, self._span_ns, gl, gh)
                            got = None
                        else:
                            out[gl:gh] = vals
                            if di is None:
                                _breaker.record_success()
                            else:
                                device_health.get().record_success(di)
                                # healthy traffic also resets the
                                # global breaker's quarantine streak,
                                # so isolated quarantines accumulated
                                # over hours never masquerade as a
                                # correlated outage (and a real one —
                                # zero successes — still escalates)
                                _breaker.record_success()
                            self._mark_served("device", hi - lo, di)
                    if got is None:
                        if arr is not None and not accepted:
                            # a dispatched part the engine gave up on
                            # (deadline miss, fetch exception, breaker
                            # short-circuit, host-only flip): its busy
                            # interval closes HERE, never by the
                            # abandoned pool worker — an audit
                            # mismatch, by contrast, was genuinely
                            # delivered and already closed above
                            pipeline_timeline.note_delivery(
                                ptok, di, delivered=False)
                        # failover: bit-identical host re-computation
                        # of the affected rows (latency changes,
                        # results never do)
                        fb_attrs = {"device": di}
                        if ptr:
                            fb_attrs["traces"] = ptr
                        with tracing.span(
                                f"{self._span_ns}.host_fallback",
                                **fb_attrs), \
                                pipeline_timeline.host_phase(
                                    ptok, "host_fallback"):
                            out[gl:gh] = plugin.host_result(
                                items[gl:gh])
                        self._mark_served("host-fallback", hi - lo)
            return plugin.finalize(gate, out, items)

        def resolve() -> np.ndarray:
            with tracing.span(f"{self._span_ns}.resolve"):
                try:
                    return _resolve_impl()
                finally:
                    # close the per-resolve transfer + pipeline
                    # records (both idempotent); the transfer record
                    # rides the pipeline ring entry so one record
                    # carries bytes AND utilization
                    pipeline_timeline.finish(
                        ptok, transfer=transfer_ledger.finish(tok))

        return resolve

    def compute_batch(self, items: Sequence,
                      trace_ids=None) -> np.ndarray:
        """Blocking batch: per-item result rows, bit-identical to the
        plugin's host oracle. The root span covers the whole blocking
        call, so the per-phase spans under it attribute the blocking
        headline (:func:`phase_attribution`) — the root COLLECTS its
        phases (``_collect``) and flushes them into the
        root-attributed ``span.attr.*`` timers only on completion, the
        idempotency guarantee mid-resolve snapshots rely on."""
        with tracing.span(f"{self._span_ns}.blocking",
                          _collect=phase_names(self._span_ns)):
            return self.submit(items, trace_ids=trace_ids)()


# ---------------- device probe / availability ----------------

_device_state: Optional[str] = None  # None=unprobed, else platform|"dead"
_device_probe_lock = threading.Lock()
# current probe attempt: {"thread", "box", "started", "accounted"}.
# Unlike the pre-breaker design this is RE-ARMABLE: a "dead" verdict is
# re-probed when the breaker's backoff window expires, so a recovered
# device is picked up instead of being ignored for the process lifetime.
_probe: Optional[dict] = None


def _launch_probe_locked() -> dict:
    """Spawn a fresh probe attempt (call with _device_probe_lock held).
    A probe on a wedged device hangs; its daemon thread is abandoned
    when accounted — backoff growth bounds the leak to one thread per
    half-open window."""
    global _probe

    box: dict = {}

    def probe():
        try:
            faults.inject(faults.PROBE)
            import jax
            platform = jax.devices()[0].platform
            if platform != "cpu":
                # jax.devices() answers from the in-process cache once
                # the backend has initialized, so on an accelerator only
                # a REAL tiny dispatch proves the device: a vacuous
                # success here would re-close a dispatch-opened breaker
                # (and reset its backoff) while the device is still
                # dead. On a wedged device this hangs — exactly what the
                # caller's watchdog + breaker accounting expect.
                np.asarray(jax.jit(lambda x: x + 1)(
                    np.zeros(2, np.int32)))
            box["platform"] = platform
        except Exception as e:  # no backend at all
            box["error"] = str(e)

    t = threading.Thread(target=probe, daemon=True, name="device-probe")
    _probe = {"thread": t, "box": box, "started": time.monotonic(),
              "accounted": False}
    t.start()
    return _probe


def _account_probe_locked(cur: dict, hung: bool, timeout_s: float) -> None:
    """Turn a finished/overdue probe attempt into device state + breaker
    accounting (call with _device_probe_lock held; idempotent)."""
    global _device_state
    if cur["accounted"]:
        return
    cur["accounted"] = True
    box = cur["box"]
    if hung:
        _device_state = "dead"
        _breaker.record_failure()
        _log.warning(
            "device probe hung > %ss — batch dispatch falls "
            "back to the host oracle (breaker: %s)",
            timeout_s, _breaker.state)
    elif "platform" in box:
        _device_state = box["platform"]
        _breaker.record_success()
    else:
        _device_state = "dead"
        _breaker.record_failure()
        _log.warning(
            "device probe failed (%s) — batch dispatch falls "
            "back to the host oracle (breaker: %s)",
            box.get("error", "no backend"), _breaker.state)


def start_device_probe() -> None:
    """Fire the device probe WITHOUT waiting for it (idempotent).
    Called from LedgerManager/Application construction so the jax
    import + ``jax.devices()`` cost (seconds, or a hang on a wedged
    device) is paid during startup, never inside the first ledger
    close (the reference initializes its crypto stack at app start,
    not in ``closeLedger``)."""
    with _device_probe_lock:
        if _probe is None and _device_state is None:
            _launch_probe_locked()


def device_available(timeout_s: float = 30.0,
                     block: bool = True) -> bool:
    """True when a REAL accelerator is reachable AND the dispatch
    breaker is closed. Probes run in watchdogged threads: a wedged device
    can make ``jax.devices()`` or a dispatch hang rather than raise,
    and a node must fall back to the host oracle instead of hanging the
    close path (failure detection, not configuration). jax-CPU reports
    False permanently: batching bignum kernels through XLA-on-CPU is
    strictly slower than the host oracle, so auto mode only engages the
    device path on tpu-class hardware — that is configuration, and is
    never re-probed.

    A "dead" verdict, by contrast, is a FAILURE and heals: the circuit
    breaker re-probes (half-open) once its exponential-backoff window
    expires, so a device that comes back is picked up without hammering
    one that stays down.

    ``block=False`` never waits: a still-pending probe answers False
    for now WITHOUT caching a verdict, so latency-critical callers
    (the close path) fall back to the host oracle this round and pick
    up the device once the probe resolves. A pending probe older than
    ``timeout_s`` is accounted hung even for non-blocking callers, so
    breaker-paced recovery works on a node that only ever asks
    non-blockingly."""
    start_device_probe()
    with _device_probe_lock:
        cur = _probe
        if cur is None or cur["accounted"]:
            if _device_state == "cpu":
                return False  # configuration, not a fault
            if _device_state not in (None, "dead") and \
                    _breaker.state == resilience.CLOSED:
                return True
            # dead (or breaker tripped by dispatch failures): re-probe
            # only when the backoff window has expired
            if _breaker.allow():
                cur = _launch_probe_locked()
            else:
                return False
    t = cur["thread"]
    if block:
        # join OUTSIDE the lock: a blocking waiter must never make a
        # concurrent block=False caller (the close path) wait on the
        # lock for up to timeout_s
        t.join(timeout_s)
    with _device_probe_lock:
        if not cur["accounted"]:
            if not t.is_alive():
                _account_probe_locked(cur, hung=False, timeout_s=timeout_s)
            elif block or \
                    time.monotonic() - cur["started"] > timeout_s:
                _account_probe_locked(cur, hung=True, timeout_s=timeout_s)
            else:
                return False  # pending — ask again later, don't cache
        return _device_state not in (None, "dead", "cpu") and \
            _breaker.state == resilience.CLOSED


def _reset_dispatch_state_for_testing() -> None:
    """Fresh probe/breaker state (chaos tests): equivalent to process
    start for the dispatch layer. Cumulative metrics are untouched."""
    global _device_state, _probe, _host_only
    with _device_probe_lock:
        _device_state = None
        _probe = None
    with _host_only_lock:
        _host_only = False
    _failure_traceback_logged.clear()
    _breaker.record_success()  # closed, zero failures, backoff reset
    device_health.get()._reset_for_testing()
    transfer_ledger._reset_for_testing()
    pipeline_timeline._reset_for_testing()
    signer_tables.signer_table_cache._reset_for_testing()


def _auto_mesh():
    """1-D mesh over every local device, or None when single-device.
    Buckets not divisible by the mesh size fall back to the unsharded
    kernel, so odd device counts degrade gracefully."""
    try:
        import jax
        devs = jax.devices()
    except Exception:
        return None
    if len(devs) < 2:
        return None
    from jax.sharding import Mesh
    return Mesh(np.array(devs), ("batch",))
