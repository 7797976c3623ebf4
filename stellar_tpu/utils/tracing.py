"""Structured tracing: spans + zones + the resolve flight recorder
(reference: Tracy ``ZoneScoped`` annotations — 672 across ``src/`` —
plus ``util/LogSlowExecution.h`` wall-time watchdogs; ISSUE 5 grows
the zone layer into structured spans with IDs, parent links and
cross-thread context propagation).

Model:

* a :class:`span` is a context manager that times one phase of work.
  On entry it draws a process-unique ``span_id``, links to the
  innermost live span of the current thread as ``parent_id``, and
  registers an OPEN record with the :class:`FlightRecorder`; on exit
  it feeds the inclusive duration into the registry timer
  ``span.<name>`` (a reservoir histogram — p50/p90/p99 export) and
  moves the record into the recorder's bounded ring.
* :class:`zone` is the historical spelling (timer prefix ``zone.``);
  it is a span, so every existing ``with zone(...)`` call site gained
  span IDs and recorder coverage for free.
* **cross-thread propagation**: :func:`current_context` captures the
  caller's innermost span id; :class:`span_context` installs it as the
  parent on another thread. ``resilience.WatchdogPool`` does this for
  every guarded call, so a span opened inside a pooled device fetch
  parents correctly under the resolve that submitted it — which is
  exactly what makes a HUNG fetch attributable in a dump.
* the :class:`FlightRecorder` keeps the last N completed spans plus
  every still-open span in memory; ``dump(reason)`` snapshots both on
  breaker trips, audit mismatches, watchdog timeouts and the verify
  service's first load-shed onset (``service-shed:<why>`` —
  ``crypto/batch_verifier.py`` wires all the triggers) so the spans
  leading into a failure survive to be read from the ``spans`` admin
  route. See ``docs/observability.md``.
* **profiler capture**: while a JAX profiler session collects, each
  span also opens a profiler TraceMe (``jax.profiler.TraceAnnotation``)
  named exactly as its recorder record (``span.<name>``), so an
  xprof/Perfetto capture holds the program phases on the device
  trace's own clock. With no session collecting, the cost is one
  enabled-check per span; jax is never imported from here.
* **deferred spans** (:meth:`span.deferred`): a span that is recorded
  only if its owner calls ``keep()`` — a cache probe whose hits must
  stay span-free times its misses this way.
* **span phase families**: ``verify.*`` phases attribute one blocking
  resolve (``batch_verifier.RESOLVE_PHASES``); ``service.dispatch`` /
  ``service.resolve`` wrap the resident verify service's continuous-
  batching cycle (``crypto/verify_service.py``), so a recorder dump
  taken under overload shows which lane's batch each in-flight
  dispatch is serving.

Determinism: this module is clock-bearing BY DESIGN (``perf_counter``
pairs). Its timings feed metrics and the recorder, never decisions —
and the nondet lint (``stellar_tpu/analysis/nondet.py``) fences
everything except the duration-blind context managers out of the
consensus modules.

Zone times are inclusive, like Tracy; the thread-local stack exists
for parent links and the ``current_zones`` introspection.
"""

from __future__ import annotations

import itertools
import logging
import sys
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from stellar_tpu.utils.metrics import registry

__all__ = ["span", "zone", "LogSlowExecution", "current_zones",
           "current_context", "span_context", "frame_mark",
           "FlightRecorder", "flight_recorder", "span_totals",
           "trace_matches"]

_log = logging.getLogger("stellar_tpu.perf")

_tls = threading.local()

# span ids: process-unique, monotone. itertools.count.__next__ is a
# single C call (atomic under the GIL).
_ids = itertools.count(1)

# time origin for span start stamps: milliseconds since tracing
# import, monotonic — no wall clock enters the records
_EPOCH = time.perf_counter()


def _now_ms() -> float:
    return (time.perf_counter() - _EPOCH) * 1000.0


# The profiler's TraceMe class (jax.profiler.TraceAnnotation), found the
# first time a span opens after jax is loaded: no profiler session can
# collect before that, and a jax-free process never imports it.
_trace_me = None


def _load_trace_me():
    global _trace_me
    prof = sys.modules.get("jax.profiler")
    if prof is not None:
        _trace_me = prof.TraceAnnotation
    return _trace_me


def _stack() -> list:
    s = getattr(_tls, "zones", None)
    if s is None:
        s = _tls.zones = []
    return s


def current_zones() -> List[str]:
    """The live zone/span names of this thread (innermost last);
    context anchors are invisible."""
    return [e.name for e in _stack() if e.name is not None]


def current_context() -> Optional[int]:
    """The innermost live span id of this thread (None outside any
    span) — hand it to another thread via :class:`span_context` so
    spans opened there parent under this one."""
    s = _stack()
    return s[-1].span_id if s else None


def trace_matches(rec: dict, trace_id: int) -> bool:
    """True when a span/event record carries ``trace_id`` in its
    ``traces`` exemplar ranges (ISSUE 8). Trace exemplars are stored
    COMPRESSED as ``[lo, hi)`` pairs (``batch_engine.trace_ranges``)
    so a 2048-item batch costs a handful of ints in the record, not a
    2048-element list — and matching stays exact, never truncated."""
    attrs = rec.get("attrs")
    if not attrs:
        return False
    for pair in attrs.get("traces") or ():
        try:
            lo, hi = pair
        except (TypeError, ValueError):
            continue
        if lo <= trace_id < hi:
            return True
    return False


class FlightRecorder:
    """Bounded in-memory ring of span records + the set of still-open
    spans, dumped on failure triggers (breaker trips, audit
    mismatches, watchdog timeouts).

    Records are plain dicts: ``{"id", "parent", "name", "thread",
    "start_ms", "dur_ms"}`` (+ optional ``attrs`` / ``event`` /
    ``open`` / ``abandoned`` flags). ``dur_ms`` is None while a span
    is open — a dump therefore shows exactly where each in-flight
    thread is parked, with parent links back to the resolve that got
    it there. All shared state mutates under the instance lock (the
    lock-discipline lint covers this module)."""

    DEFAULT_CAPACITY = 4096

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=max(16, int(capacity)))
        self._active: Dict[int, dict] = {}
        self._dumps: deque = deque(maxlen=8)
        self._dumps_total = 0
        self._recorded_total = 0

    def configure(self, capacity: Optional[int] = None) -> None:
        """Config push (FLIGHT_RECORDER_SPANS); None keeps current."""
        if capacity is None:
            return
        cap = max(16, int(capacity))
        with self._lock:
            if cap != self._ring.maxlen:
                self._ring = deque(self._ring, maxlen=cap)

    # ---------------- span lifecycle ----------------

    def start_span(self, rec: dict) -> None:
        with self._lock:
            self._active[rec["id"]] = rec

    def finish_span(self, rec: dict) -> None:
        with self._lock:
            self._active.pop(rec["id"], None)
            self._ring.append(rec)
            self._recorded_total += 1

    def abandon_span(self, rec: dict) -> None:
        """A span whose ``__exit__`` never ran (orphan found by an
        outer span's defensive pop): closed into the ring with an
        ``abandoned`` flag and no duration."""
        rec["abandoned"] = True
        self.finish_span(rec)

    def note(self, name: str, **attrs) -> None:
        """Instant event record (duration 0) — audit verdicts,
        re-shard decisions — parented under the caller's live span."""
        rec = {"id": next(_ids), "parent": current_context(),
               "name": name,
               "thread": threading.current_thread().name,
               "start_ms": round(_now_ms(), 3), "dur_ms": 0.0,
               "event": True}
        if attrs:
            rec["attrs"] = attrs
        with self._lock:
            self._ring.append(rec)
            self._recorded_total += 1

    # ---------------- failure dumps / introspection ----------------

    def dump(self, reason: str, limit: int = 256) -> dict:
        """Snapshot the open spans + the ring tail under ``reason``;
        kept in a bounded dump list (``spans`` admin route) and
        counted in ``tracing.recorder.dumps``."""
        limit = max(0, int(limit))
        with self._lock:
            open_spans = [dict(r, open=True)
                          for r in self._active.values()]
            tail = list(self._ring)[-limit:] if limit else []
            d = {"reason": reason, "seq": self._dumps_total + 1,
                 "open_spans": open_spans,
                 "spans": [dict(r) for r in tail]}
            self._dumps.append(d)
            self._dumps_total += 1
        registry.counter("tracing.recorder.dumps").inc()
        _log.warning("flight recorder dump (%s): %d open spans, "
                     "%d recent records", reason, len(open_spans),
                     len(d["spans"]))
        return d

    def dumps(self) -> List[dict]:
        with self._lock:
            return list(self._dumps)

    def stats(self) -> dict:
        """Accounting only (the ``dispatch_health`` embed): no record
        copies, minimal time under the recorder lock."""
        with self._lock:
            return {"capacity": self._ring.maxlen,
                    "recorded_total": self._recorded_total,
                    "dumps_total": self._dumps_total,
                    "dump_reasons": [d["reason"]
                                     for d in self._dumps]}

    def snapshot(self, limit: int = 128) -> dict:
        """The ``spans`` admin-route payload: open spans, the most
        recent completed records, and dump accounting. ``limit=0``
        means NO recent records (accounting only — what
        ``dispatch_health`` wants), never the whole ring."""
        limit = max(0, int(limit))
        with self._lock:
            tail = list(self._ring)[-limit:] if limit else []
            return {
                "active": [dict(r) for r in self._active.values()],
                "recent": [dict(r) for r in tail],
                "capacity": self._ring.maxlen,
                "recorded_total": self._recorded_total,
                "dumps_total": self._dumps_total,
                "dump_reasons": [d["reason"] for d in self._dumps],
            }

    def trace_timeline(self, trace_id: int) -> dict:
        """Reconstruct one trace's end-to-end timeline (ISSUE 8): every
        record in the ring, the open-span set, and the failure dumps
        whose ``traces`` exemplar ranges contain ``trace_id``, sorted
        by start time, plus derived milestones (queue wait, coalesce,
        dispatch-to-verdict) when the service notes are present. The
        ring is bounded, so a trace older than the retention window
        reconstructs partially (``found`` stays True if anything
        matched) — the ``trace`` admin route serves this payload."""
        tid = int(trace_id)
        with self._lock:
            recs = {r["id"]: dict(r) for r in self._ring
                    if trace_matches(r, tid)}
            for r in self._active.values():
                if trace_matches(r, tid):
                    recs.setdefault(r["id"], dict(r, open=True))
            for d in self._dumps:
                for r in d["spans"] + d["open_spans"]:
                    if trace_matches(r, tid):
                        recs.setdefault(r["id"], dict(r))
        records = sorted(recs.values(),
                         key=lambda r: (r["start_ms"], r["id"]))

        def first(name):
            for r in records:
                if r["name"] == name:
                    return r
            return None

        phases: Dict[str, dict] = {}
        for r in records:
            if r.get("event") or r.get("dur_ms") is None:
                continue
            p = phases.setdefault(r["name"],
                                  {"count": 0, "total_ms": 0.0})
            p["count"] += 1
            p["total_ms"] = round(p["total_ms"] + r["dur_ms"], 3)
        summary = {}
        enq = first("service.enqueue")
        coal = first("service.coalesce")
        verdict = first("service.verdict")
        disp = first("span.service.dispatch")
        # tenant attribution (ISSUE 14): the enqueue milestone carries
        # the submitting tenant, so one item's queue wait is
        # attributable to its principal from the trace route alone
        # (shed/reject milestones carry it too — the fallback covers
        # items refused before any enqueue was recorded)
        for rec in (enq, first("service.shed"),
                    first("service.reject")):
            tenant = (rec or {}).get("attrs", {}).get("tenant")
            if tenant is not None:
                summary["tenant"] = tenant
                break
        if enq and coal:
            summary["queue_wait_ms"] = round(
                coal["start_ms"] - enq["start_ms"], 3)
        if disp and verdict:
            summary["dispatch_to_verdict_ms"] = round(
                verdict["start_ms"] - disp["start_ms"], 3)
        if enq and verdict:
            summary["enqueue_to_verdict_ms"] = round(
                verdict["start_ms"] - enq["start_ms"], 3)
        shed = first("service.shed") or first("service.reject")
        if shed is not None:
            summary["dropped"] = shed["name"]

        # cross-replica stitch summary (ISSUE 20): which segments of
        # the wire -> route -> replica -> verdict path are present,
        # the replica hop sequence (one entry per fleet.route, with
        # the rendezvous score; handoff re-routes flagged), and the
        # seam check — every service.handoff must be followed by a
        # re-admission on a survivor, so a re-homed trace's timeline
        # reads handoff -> route -> enqueue -> verdict with no gap.
        def every(name):
            return [r for r in records if r["name"] == name]

        routes = every("fleet.route")
        handoffs = every("service.handoff")
        enqueues = every("service.enqueue")
        terminal = (first("service.verdict") or first("service.shed")
                    or first("service.reject")
                    or first("fleet.refuse"))
        hops = [{"replica": r.get("attrs", {}).get("replica"),
                 "score": r.get("attrs", {}).get("score"),
                 "handoff": bool(r.get("attrs", {}).get("handoff"))}
                for r in routes]
        order = {r["id"]: i for i, r in enumerate(records)}
        seamless = all(
            any(order[e["id"]] > order[h["id"]] for e in enqueues)
            for h in handoffs)
        stitch = {
            "wire": bool(first("ingress.frame")),
            "route": bool(routes),
            "enqueue": bool(enqueues),
            "terminal": terminal["name"] if terminal else None,
            "hops": hops,
            "handoffs": len(handoffs),
            "seamless": seamless,
            "end_to_end": (bool(first("ingress.frame"))
                           and bool(routes) and terminal is not None
                           and seamless),
        }
        return {"trace": tid, "found": bool(records),
                "records": records, "phases": phases,
                "summary": summary, "stitch": stitch}

    def to_chrome_trace(self, by_replica: bool = False) -> dict:
        """Render the recorder as Chrome ``trace_event`` JSON (the
        ``chrome://tracing`` / Perfetto import format): thread-named
        tracks (metadata ``M`` events), completed spans as properly
        nested ``B``/``E`` pairs, instant events and still-open /
        abandoned spans as ``i`` instants (an open span has no duration
        yet — an instant marks where it is parked). Nesting is derived
        from the records' PARENT LINKS (same-thread), not from interval
        arithmetic, and child intervals are clamped inside their
        parent's, so float rounding can never emit a crossing
        begin/end pair. Counter tracks (``C`` events) from the
        pipeline-bubble profiler ride alongside — per-device in-flight
        state, busy fractions and cumulative transfer bytes share the
        span clock, so one chrome://tracing load shows spans, bytes
        AND utilization (ISSUE 10). Served by ``spans?format=chrome``
        and the ``tools/trace_export.py`` CLI
        (docs/observability.md).

        ``by_replica=True`` (ISSUE 20, ``spans?format=chrome&
        fleet=true``): the whole-fleet window. Records attributable
        to a fleet replica — a ``replica`` attribute, or a
        ``verify-service/<i>`` dispatcher thread — move to per-replica
        process tracks (pid ``2 + i``, named by ``process_name``
        metadata) while everything else stays on the host track (pid
        1). All tracks share the ONE recorder clock, so cross-replica
        ordering in the merged view is real, not cosmetic."""
        with self._lock:
            done = [dict(r) for r in self._ring]
            open_ = [dict(r, open=True)
                     for r in self._active.values()]
        spans = [r for r in done
                 if not r.get("event") and r.get("dur_ms") is not None]
        instants = [r for r in done
                    if r.get("event") or r.get("dur_ms") is None]
        instants += open_
        tids: Dict[str, int] = {}
        seen_tracks: Dict[tuple, str] = {}

        def tid_of(thread: str) -> int:
            if thread not in tids:
                tids[thread] = len(tids) + 1
            return tids[thread]

        def pid_of(r) -> int:
            if not by_replica:
                return 1
            rep = (r.get("attrs") or {}).get("replica")
            if rep is None:
                th = r.get("thread", "")
                if th.startswith("verify-service/"):
                    tail = th.rsplit("/", 1)[1]
                    if tail.isdigit():
                        rep = int(tail)
            try:
                return 1 if rep is None else 2 + int(rep)
            except (TypeError, ValueError):
                return 1

        def track(pid: int, r) -> int:
            tid = tid_of(r["thread"])
            seen_tracks.setdefault((pid, tid), r["thread"])
            return tid

        by_id = {r["id"]: r for r in spans}
        children: Dict[int, list] = {}
        roots: Dict[str, list] = {}
        for r in spans:
            p = r.get("parent")
            if p in by_id and by_id[p]["thread"] == r["thread"]:
                children.setdefault(p, []).append(r)
            else:
                roots.setdefault(r["thread"], []).append(r)
        events: List[dict] = []

        def emit(r, lo_ms: float, hi_ms: float, pid: int) -> float:
            """Emit one span's B/E pair (and its subtree), clamped to
            the parent interval [lo_ms, hi_ms]; returns this span's
            end so siblings can't overlap. The subtree inherits the
            root's pid — nesting must stay within one track."""
            t0 = min(max(r["start_ms"], lo_ms), hi_ms)
            t1 = min(max(t0, r["start_ms"] + r["dur_ms"]), hi_ms)
            tid = track(pid, r)
            args = {"id": r["id"]}
            if r.get("attrs"):
                args.update(r["attrs"])
            events.append({"name": r["name"], "ph": "B", "pid": pid,
                           "tid": tid, "ts": round(t0 * 1000.0, 1),
                           "args": args})
            cursor = t0
            for c in sorted(children.get(r["id"], []),
                            key=lambda x: (x["start_ms"], x["id"])):
                cursor = emit(c, max(cursor, t0), t1, pid)
            events.append({"name": r["name"], "ph": "E", "pid": pid,
                           "tid": tid, "ts": round(t1 * 1000.0, 1)})
            return t1

        for thread, rs in sorted(roots.items()):
            cursor = 0.0
            for r in sorted(rs, key=lambda x: (x["start_ms"], x["id"])):
                cursor = emit(r, max(cursor, r["start_ms"]),
                              float("inf"), pid_of(r))
        for r in instants:
            args = {"id": r["id"]}
            if r.get("attrs"):
                args.update(r["attrs"])
            if r.get("open"):
                args["open"] = True
            if r.get("abandoned"):
                args["abandoned"] = True
            pid = pid_of(r)
            events.append({"name": r["name"], "ph": "i", "pid": pid,
                           "tid": track(pid, r), "s": "t",
                           "ts": round(r["start_ms"] * 1000.0, 1),
                           "args": args})
        # pipeline utilization + transfer-byte counter tracks
        # (ISSUE 10): lazy import — timeline imports this module at
        # load time, and the export path only ever runs long after
        # both are imported
        try:
            from stellar_tpu.utils.timeline import pipeline_timeline
            events += pipeline_timeline.chrome_counter_events()
        except ImportError:  # pragma: no cover — import-order edge
            pass
        meta = [{"name": "thread_name", "ph": "M", "pid": pid,
                 "tid": tid, "args": {"name": thread}}
                for (pid, tid), thread in sorted(seen_tracks.items())]
        if by_replica:
            pids = sorted({pid for pid, _tid in seen_tracks})
            meta += [{"name": "process_name", "ph": "M", "pid": pid,
                      "tid": 0,
                      "args": {"name": ("host" if pid == 1 else
                                        f"replica {pid - 2}")}}
                     for pid in pids]
        return {"traceEvents": meta + events, "displayTimeUnit": "ms"}

    def clear(self) -> None:
        """Tests: drop every record, open span, dump and the
        accounting counters — a fresh recorder."""
        with self._lock:
            self._ring.clear()
            self._active.clear()
            self._dumps.clear()
            self._dumps_total = 0
            self._recorded_total = 0


# process-wide recorder (one node per process, like the registry)
flight_recorder = FlightRecorder()


class span:
    """``with span("verify.fetch", device=3): ...`` — inclusive wall
    time into the registry histogram ``span.<name>``, plus a recorder
    record carrying span id, parent link, thread and attrs.

    ``_collect`` (ISSUE 8) makes a span a ROOT-ATTRIBUTED collector:
    same-thread descendant spans whose names are in the set fold their
    inclusive durations into the collector, and the collector flushes
    the totals into ``span.attr.<name>`` timers only when IT exits.
    That is what makes ``phase_attribution`` idempotent under
    re-shard/retry re-entry: a phase re-entered inside a resolve that
    has not completed contributes nothing to the attribution timers,
    so a ``span_totals()`` snapshot taken mid-resolve can never count
    a phase whose blocking root is still open (the phases' own
    ``span.<name>`` timers update per-exit as before — the recorder
    and per-phase histograms are unchanged)."""

    _PREFIX = "span"
    __slots__ = ("name", "attrs", "span_id", "parent_id", "_t0",
                 "_rec", "_collect", "_collected", "_tm")

    def __init__(self, name: str, _collect=None, **attrs):
        self.name = name
        self.attrs = attrs
        self._collect = None if _collect is None else frozenset(_collect)
        self._collected = None if _collect is None else {}

    def __enter__(self):
        st = _stack()
        self.parent_id = st[-1].span_id if st else None
        self.span_id = next(_ids)
        self._rec = {"id": self.span_id, "parent": self.parent_id,
                     "name": f"{self._PREFIX}.{self.name}",
                     "thread": threading.current_thread().name,
                     "start_ms": round(_now_ms(), 3), "dur_ms": None}
        if self.attrs:
            self._rec["attrs"] = dict(self.attrs)
        st.append(self)
        flight_recorder.start_span(self._rec)
        tm = _trace_me or _load_trace_me()
        if tm is not None and tm.is_enabled():
            self._tm = tm(self._rec["name"])
            self._tm.__enter__()
        else:
            self._tm = None
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._tm is not None:
            self._tm.__exit__(None, None, None)
        if self._rec.get("abandoned"):
            # already swept into the ring by an outer span's (or
            # anchor's) defensive pop — a late __exit__ (closed
            # generator, GC) must not fabricate a duration spanning
            # the gap nor re-append the record
            return False
        dt_ms = (time.perf_counter() - self._t0) * 1000.0
        registry.timer(f"{self._PREFIX}.{self.name}").update_ms(dt_ms)
        self._rec["dur_ms"] = round(dt_ms, 3)
        flight_recorder.finish_span(self._rec)
        # Root-attributed phase accounting (ISSUE 8): fold this span's
        # inclusive time into the nearest enclosing collector on THIS
        # thread that registered its name. The collector's dict is
        # touched only from its own thread (the stack is thread-local),
        # so no lock is needed.
        st = _stack()
        for e in reversed(st):
            if e is self:
                continue
            coll = getattr(e, "_collect", None)
            if coll is not None and self.name in coll:
                tot = e._collected.get(self.name)
                if tot is None:
                    e._collected[self.name] = [1, dt_ms]
                else:
                    tot[0] += 1
                    tot[1] += dt_ms
                break
        if self._collect is not None and self._collected:
            # flush AFTER this root's own timer updated: a snapshot
            # racing the flush sees the root without its phases
            # (coverage dips toward under-attribution, never inflates
            # past 1 by a phantom in-flight resolve)
            for name, (cnt, sum_ms) in self._collected.items():
                registry.timer(f"span.attr.{name}").record_total(
                    cnt, sum_ms)
        # Defensive pop back to SELF: an inner span abandoned mid-flight
        # (entered by hand, a generator that never resumed, an exit
        # skipped by interpreter shutdown) must not leave orphan stack
        # entries poisoning parent links for the rest of the thread's
        # life. Entries above this span are closed into the recorder as
        # abandoned; if this span is not on the stack at all (its own
        # entry was already swept by an outer pop), the stack is left
        # untouched.
        st = _stack()
        if any(e is self for e in st):
            while st:
                top = st.pop()
                if top is self:
                    break
                top._abandon()
        return False

    def _abandon(self):
        rec = getattr(self, "_rec", None)
        if rec is not None and rec.get("dur_ms") is None:
            flight_recorder.abandon_span(rec)

    def set_attrs(self, **attrs) -> None:
        """Add attributes known only inside the span (bytes moved,
        cache hits) to its record."""
        self._rec.setdefault("attrs", {}).update(attrs)

    @staticmethod
    def deferred(name: str) -> "_DeferredSpan":
        """``sp = span.deferred("keys.probe"); ...; sp.keep()`` — a
        span that starts when made and is recorded only if ``keep()``
        runs, ending it there. Dropped unkept, it cost one clock read:
        a cache probe whose hits must stay span-free records only its
        misses. A kept span lands in the ``span.<name>`` timer and the
        recorder, parented under the innermost live span, but it was
        never on the live stack: it parents nothing, folds into no
        collector and reaches no profiler capture."""
        return _DeferredSpan(name)


class _DeferredSpan:
    __slots__ = ("name", "_t0")

    def __init__(self, name: str):
        self.name = name
        self._t0 = time.perf_counter()

    def keep(self) -> None:
        dt_ms = (time.perf_counter() - self._t0) * 1000.0
        name = f"span.{self.name}"
        registry.timer(name).update_ms(dt_ms)
        st = _stack()
        flight_recorder.finish_span({
            "id": next(_ids), "parent": st[-1].span_id if st else None,
            "name": name, "thread": threading.current_thread().name,
            "start_ms": round((self._t0 - _EPOCH) * 1000.0, 3),
            "dur_ms": round(dt_ms, 3)})


class zone(span):
    """Historical spelling (timer prefix ``zone.``): the ZoneScoped
    analog. A full span — IDs, parent links, recorder coverage."""

    _PREFIX = "zone"
    __slots__ = ()


class _Anchor:
    """Stack entry carrying a borrowed parent span id (cross-thread
    context): invisible to ``current_zones``, never timed."""

    __slots__ = ("span_id", "name")

    def __init__(self, span_id: int):
        self.span_id = span_id
        self.name = None

    def _abandon(self):
        pass


class span_context:
    """Install ``parent_id`` as this thread's innermost span, so spans
    opened here link under a span living on another thread:

        ctx = tracing.current_context()      # caller thread
        with tracing.span_context(ctx): ...  # worker thread

    ``parent_id=None`` is a no-op (callers need no outside-any-span
    special case)."""

    __slots__ = ("_anchor",)

    def __init__(self, parent_id: Optional[int]):
        self._anchor = _Anchor(parent_id) if parent_id is not None \
            else None

    def __enter__(self):
        if self._anchor is not None:
            _stack().append(self._anchor)
        return self

    def __exit__(self, *exc):
        if self._anchor is not None:
            st = _stack()
            if any(e is self._anchor for e in st):
                while st:
                    top = st.pop()
                    if top is self._anchor:
                        break
                    # orphans above the anchor (a span abandoned
                    # inside the pooled fn) get the same treatment as
                    # span.__exit__'s defensive sweep — closed into
                    # the ring as abandoned, never stuck in _active
                    top._abandon()
        return False


def span_totals() -> Dict[str, dict]:
    """``{timer_name: {"count", "sum_ms"}}`` snapshot of every
    registry timer — the delta input of
    ``batch_verifier.dispatch_attribution`` (bench takes one before
    and one after the measured reps). Reads the registry's cheap
    totals accessor, not the full percentile-rendering ``to_dict``."""
    return registry.timer_totals()


class LogSlowExecution:
    """Warn when a scope overruns its budget (reference
    ``LogSlowExecution``: construct at scope entry, log on exit if the
    elapsed wall time exceeds the threshold)."""

    __slots__ = ("name", "threshold_ms", "_t0")

    def __init__(self, name: str, threshold_ms: float = 1000.0):
        self.name = name
        self.threshold_ms = threshold_ms

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt_ms = (time.perf_counter() - self._t0) * 1000.0
        if dt_ms > self.threshold_ms:
            registry.counter(f"slow.{self.name}").inc()
            _log.warning("'%s' hung for %.0f ms (threshold %.0f ms)",
                         self.name, dt_ms, self.threshold_ms)
        return False


def frame_mark() -> None:
    """Per-ledger frame boundary (reference ``FrameMark`` at the end of
    closeLedger, ``LedgerManagerImpl.cpp:1121``)."""
    registry.meter("frame.ledger_close").mark()
