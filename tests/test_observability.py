"""Resolve flight recorder + histogram metrics + export surface
(ISSUE 5): structured spans with parent links, cross-thread context
propagation, reservoir percentiles, dispatch attribution completeness,
and the spans / Prometheus admin routes. See docs/observability.md."""

import json
import threading
import urllib.request

import numpy as np
import pytest

from stellar_tpu.crypto import batch_verifier as bv
from stellar_tpu.crypto import ed25519_ref as ref
from stellar_tpu.utils import resilience, tracing
from stellar_tpu.utils.metrics import (
    MetricsRegistry, Timer, registry,
)


@pytest.fixture(autouse=True)
def clean_recorder():
    """Every test starts with an empty recorder and leaves the
    process-wide dispatch state (host-only flips!) as it found it."""
    tracing.flight_recorder.clear()
    yield
    tracing.flight_recorder.clear()
    bv._reset_dispatch_state_for_testing()


# ---------------- spans: ids, parents, records ----------------


def test_span_ids_and_parent_links():
    registry.clear()
    with tracing.span("outer") as outer:
        assert outer.parent_id is None
        with tracing.span("inner", device=3) as inner:
            assert inner.parent_id == outer.span_id
            assert inner.span_id != outer.span_id
    snap = tracing.flight_recorder.snapshot()
    recs = {r["name"]: r for r in snap["recent"]}
    assert recs["span.inner"]["parent"] == outer.span_id
    assert recs["span.outer"]["parent"] is None
    assert recs["span.inner"]["attrs"] == {"device": 3}
    assert recs["span.inner"]["dur_ms"] is not None
    assert snap["active"] == []
    # span timers are histograms in the registry, same dotted scheme
    d = registry.to_dict()
    assert d["span.outer"]["count"] == 1
    assert "p50_ms" in d["span.outer"]


def test_zone_is_a_span_with_recorder_coverage():
    """The historical zone spelling gained span ids + recorder records
    for free (timer prefix stays ``zone.`` — same dotted names)."""
    registry.clear()
    with tracing.zone("ledgerish") as z:
        assert z.span_id is not None
    assert registry.to_dict()["zone.ledgerish"]["count"] == 1
    names = [r["name"] for r in
             tracing.flight_recorder.snapshot()["recent"]]
    assert "zone.ledgerish" in names


def test_zone_exit_pops_stale_inner_zones():
    """ISSUE 5 satellite: an inner zone abandoned mid-flight (entered
    by hand / generator never resumed) must not leave orphan stack
    entries — the outer exit pops defensively back to itself and the
    orphans land in the recorder flagged abandoned."""
    registry.clear()
    outer = tracing.zone("outer")
    outer.__enter__()
    inner = tracing.zone("inner")
    inner.__enter__()
    inner2 = tracing.zone("inner2")
    inner2.__enter__()
    assert tracing.current_zones() == ["outer", "inner", "inner2"]
    outer.__exit__(None, None, None)      # inner exits never ran
    assert tracing.current_zones() == []
    recs = tracing.flight_recorder.snapshot()["recent"]
    abandoned = {r["name"] for r in recs if r.get("abandoned")}
    assert abandoned == {"zone.inner", "zone.inner2"}
    # the orphans never fed the timers (no fabricated durations)
    d = registry.to_dict()
    assert "zone.inner" not in d and "zone.inner2" not in d
    assert d["zone.outer"]["count"] == 1
    # exiting a zone that is no longer on the stack leaves it alone
    inner.__exit__(None, None, None)
    assert tracing.current_zones() == []


def test_abandoned_span_late_exit_is_inert():
    """A span swept as abandoned whose __exit__ runs LATER (closed
    generator, GC) must not fabricate a duration or duplicate its
    record."""
    registry.clear()
    outer = tracing.zone("outer")
    outer.__enter__()
    inner = tracing.zone("inner")
    inner.__enter__()
    outer.__exit__(None, None, None)      # sweeps inner as abandoned
    before = tracing.flight_recorder.snapshot(limit=100)
    inner.__exit__(None, None, None)      # late exit: must be a no-op
    after = tracing.flight_recorder.snapshot(limit=100)
    assert after["recorded_total"] == before["recorded_total"]
    assert "zone.inner" not in registry.to_dict()
    inner_recs = [r for r in after["recent"]
                  if r["name"] == "zone.inner"]
    assert len(inner_recs) == 1 and inner_recs[0]["dur_ms"] is None


def test_exception_unwind_keeps_stack_clean():
    with pytest.raises(RuntimeError):
        with tracing.zone("a"):
            with tracing.zone("b"):
                raise RuntimeError("boom")
    assert tracing.current_zones() == []


# ---------------- cross-thread context propagation ----------------


def test_watchdog_pool_propagates_span_context():
    """ISSUE 5 satellite: spans opened inside a deadline-guarded call
    (WatchdogPool worker thread) parent under the submitter's live
    span."""
    box = {}

    def job():
        with tracing.span("inside-pool") as s:
            box["parent"] = s.parent_id
            box["thread"] = threading.current_thread().name
        return 42

    with tracing.span("caller") as caller:
        assert resilience.call_with_deadline(job, 5.0) == 42
    assert box["parent"] == caller.span_id
    assert box["thread"] != threading.current_thread().name
    # and without a live span, the worker runs context-free
    box.clear()
    assert resilience.call_with_deadline(job, 5.0) == 42
    assert box["parent"] is None


def test_span_context_manual():
    with tracing.span("root") as root:
        ctx = tracing.current_context()
    assert ctx == root.span_id
    done = threading.Event()
    got = {}

    def worker():
        with tracing.span_context(ctx):
            with tracing.span("child") as c:
                got["parent"] = c.parent_id
        got["zones_after"] = tracing.current_zones()
        done.set()

    threading.Thread(target=worker).start()
    assert done.wait(5.0)
    assert got["parent"] == root.span_id
    assert got["zones_after"] == []       # anchor popped


# ---------------- flight recorder ----------------


def test_flight_recorder_ring_is_bounded():
    rec = tracing.FlightRecorder(capacity=16)
    for i in range(100):
        rec.note("evt", i=i)
    snap = rec.snapshot(limit=1000)
    assert len(snap["recent"]) == 16
    assert snap["recorded_total"] == 100
    assert snap["recent"][-1]["attrs"] == {"i": 99}


def test_flight_recorder_limit_zero_means_none():
    """limit=0 is accounting-only (dispatch_health's call), never the
    whole ring."""
    rec = tracing.FlightRecorder(capacity=64)
    for i in range(10):
        rec.note("evt", i=i)
    assert rec.snapshot(limit=0)["recent"] == []
    assert rec.dump("r", limit=0)["spans"] == []
    assert rec.snapshot(limit=0)["recorded_total"] == 10


def test_span_context_abandons_orphans_above_anchor():
    """A span left open inside a pooled fn must not stay in _active
    forever: the anchor's exit sweeps it into the ring as abandoned,
    same as span.__exit__'s defensive pop."""
    with tracing.span("caller") as caller:
        ctx = caller.span_id
        done = threading.Event()

        def worker():
            with tracing.span_context(ctx):
                tracing.span("leaked").__enter__()   # never exited
            done.set()

        threading.Thread(target=worker).start()
        assert done.wait(5.0)
    snap = tracing.flight_recorder.snapshot(limit=100)
    assert snap["active"] == []
    leaked = [r for r in snap["recent"] if r["name"] == "span.leaked"]
    assert leaked and leaked[0].get("abandoned")


def test_timer_reservoir_shrinks_on_config_push():
    from stellar_tpu.utils import metrics as metrics_mod
    saved = metrics_mod.RESERVOIR_SIZE
    try:
        metrics_mod.RESERVOIR_SIZE = 128
        t = Timer()
        for _ in range(200):
            t.update_ms(1.0)
        assert len(t._reservoir) == 128
        metrics_mod.RESERVOIR_SIZE = 16
        for _ in range(50):
            t.update_ms(9.0)
        assert len(t._reservoir) == 16   # stale tail evicted
    finally:
        metrics_mod.RESERVOIR_SIZE = saved


def test_flight_recorder_dump_snapshots_open_spans():
    rec = tracing.flight_recorder
    with tracing.span("in-flight"):
        d = rec.dump("test-trigger")
    assert d["reason"] == "test-trigger"
    open_names = [r["name"] for r in d["open_spans"]]
    assert "span.in-flight" in open_names
    assert all(r["open"] for r in d["open_spans"])
    assert rec.dumps()[-1]["reason"] == "test-trigger"
    assert rec.snapshot()["dumps_total"] == 1


# ---------------- histogram metrics + Prometheus export ----------------


def test_timer_percentiles_from_reservoir():
    t = Timer()
    for v in range(1, 101):               # 1..100 ms
        t.update_ms(float(v))
    assert abs(t.percentile_ms(50) - 50.5) < 1.0
    assert abs(t.percentile_ms(90) - 90.1) < 1.5
    assert abs(t.percentile_ms(99) - 99.0) < 1.5
    d = t.to_dict()
    assert {"p50_ms", "p90_ms", "p99_ms", "sum_ms"} <= set(d)
    assert d["count"] == 100 and d["sum_ms"] == 5050.0


def test_timer_reservoir_bounded_and_representative():
    from stellar_tpu.utils import metrics as metrics_mod
    t = Timer()
    n = metrics_mod.RESERVOIR_SIZE * 4
    for _ in range(n):
        t.update_ms(7.0)
    assert len(t._reservoir) == metrics_mod.RESERVOIR_SIZE
    assert t.percentile_ms(50) == 7.0
    assert t.count == n


def test_timer_record_total_folds_aggregates():
    """ISSUE 8: the root-attributed phase flush folds (count, sum)
    pairs — exact totals for attribution deltas, batch-mean into the
    reservoir."""
    t = Timer()
    t.record_total(3, 30.0)
    t.record_total(2, 5.0)
    t.record_total(0, 99.0)   # no-op
    assert t.count == 5
    assert t.sum_ms() == 35.0
    assert t.mean_ms() == 7.0
    d = t.to_dict()
    assert d["count"] == 5 and d["sum_ms"] == 35.0


def test_attribution_idempotent_under_mid_resolve_snapshot():
    """ISSUE 8 satellite regression: a ``span_totals()`` snapshot
    taken MID-RESOLVE must not count phases of the unfinished resolve
    — before root-attributed accounting, a phase re-entered by a
    second resolve (the re-shard / failover shape) leaked into the
    window and inflated coverage past the completed roots' time."""
    import time

    import numpy as np

    from stellar_tpu.parallel import batch_engine

    started = threading.Event()
    release = threading.Event()
    blocking = {"on": False}

    class _W(batch_engine.Workload):
        metrics_ns = "test.attr"
        span_ns = "attrx"

        def encode(self, items):
            return (np.ones(len(items), dtype=bool),
                    (np.zeros((len(items), 2), dtype=np.uint8),))

        def pad_rows(self):
            return (np.zeros((1, 2), dtype=np.uint8),)

        def kernel_fn(self):
            raise AssertionError("host-only test must not trace")

        def empty_result(self, n):
            return np.zeros(n, dtype=np.uint8)

        def host_result(self, items):
            if blocking["on"]:
                started.set()
                release.wait(10)
            # a real phase cost: the sub-ms span plumbing around a
            # zero-work stub would otherwise swamp the coverage ratio
            time.sleep(0.05)
            return np.zeros(len(items), dtype=np.uint8)

        def finalize(self, gate, out, items):
            return out

    bv._enter_host_only("test: mid-resolve attribution")
    eng = batch_engine.BatchEngine(_W(), bucket_sizes=(4,))
    before = tracing.span_totals()
    eng.compute_batch([1, 2, 3, 4])          # resolve 1 completes
    blocking["on"] = True
    t = threading.Thread(
        target=lambda: eng.compute_batch([5, 6, 7, 8]))
    t.start()
    assert started.wait(10)
    # resolve 2 re-entered prep AND is parked inside host_fallback;
    # the mid-resolve snapshot must attribute resolve 1 ONLY
    att = batch_engine.phase_attribution(
        before, tracing.span_totals(), reps=1, span_ns="attrx")
    try:
        assert att["blocking_span_count"] == 1
        assert att["phases"]["attrx.prep"]["count"] == 1
        assert att["phases"]["attrx.host_fallback"]["count"] == 1
        assert att["coverage"] is not None
        assert att["coverage"] <= 1.01
    finally:
        release.set()
        t.join(10)
    # ...and once resolve 2 completes, its phases attribute too —
    # nothing is lost, only deferred to root completion
    att2 = batch_engine.phase_attribution(
        before, tracing.span_totals(), reps=2, span_ns="attrx")
    assert att2["blocking_span_count"] == 2
    assert att2["phases"]["attrx.prep"]["count"] == 2
    assert att2["phases"]["attrx.host_fallback"]["count"] == 2
    assert att2["coverage"] >= 0.95
    # idempotent: re-deriving from the same snapshots changes nothing
    assert att2 == batch_engine.phase_attribution(
        before, tracing.span_totals(), reps=2, span_ns="attrx")


def test_prometheus_exposition_parses_and_covers_types():
    import re
    r = MetricsRegistry()
    r.counter("a.b.total").inc(3)
    r.meter("x.y").mark(2)
    r.timer("span.verify.blocking").update_ms(12.5)
    r.gauge("g.num").set(4)
    r.gauge("g.label").set('open"ish')
    text = r.to_prometheus()
    sample = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{.*\})? [-+0-9.eE]+$')
    for ln in text.splitlines():
        if ln and not ln.startswith("#"):
            assert sample.match(ln), ln
    assert "a_b_total 3" in text
    assert "x_y_total 2" in text
    assert 'span_verify_blocking_ms{quantile="0.5"} 12.5' in text
    assert "span_verify_blocking_ms_count 1" in text
    assert "g_num 4" in text
    assert r'g_label{value="open\"ish"} 1' in text


# ---------------- dispatch attribution (acceptance) ----------------


def _pool_items(n):
    pool = []
    for i in range(8):
        seed = bytes([i + 1]) * 32
        pk = ref.secret_to_public(seed)
        msg = b"attr-%d" % i
        pool.append((pk, msg, ref.sign(seed, msg)))
    return [pool[i % len(pool)] for i in range(n)]


def test_dispatch_attribution_complete_and_reconciles():
    """ISSUE 5 acceptance: on a host-only resolve (the unreachable-device
    shape — no jax, no device) the breakdown still lists EVERY phase,
    and the per-phase span sum reconciles to >= 95% of the blocking
    root span."""
    # partition-off: the hot-signer split (PR 16) would turn this
    # repeat-signer pool into TWO submission streams (hot + cold),
    # doubling the per-phase counts this test pins at exactly one
    # resolve each. The attribution semantics are what's under test,
    # not the partition (its own suite covers that); the autouse
    # reset restores the default afterwards.
    from stellar_tpu.parallel import signer_tables
    signer_tables.signer_table_cache.configure(enabled=False)
    bv._enter_host_only("test: host-only attribution")
    v = bv.BatchVerifier(bucket_sizes=(64,))
    items = _pool_items(64)
    before = tracing.span_totals()
    out = v.verify_batch(items)
    assert out.all()
    att = bv.dispatch_attribution(before, tracing.span_totals(),
                                  reps=1)
    assert set(att["phases"]) == set(bv.RESOLVE_PHASES)
    # device phases ran zero times, but are REPORTED — completeness
    assert att["phases"]["verify.dispatch"]["count"] == 0
    assert att["phases"]["verify.fetch"]["count"] == 0
    assert att["phases"]["verify.prep"]["count"] == 1
    assert att["phases"]["verify.host_fallback"]["count"] == 1
    assert att["blocking_span_count"] == 1
    assert att["coverage"] is not None and att["coverage"] >= 0.95
    # phase intervals are disjoint: the sum can't exceed the root by
    # more than rounding noise
    assert att["span_sum_per_rep_ms"] <= \
        att["blocking_span_per_rep_ms"] * 1.01


def test_audit_evidence_lands_in_device_health():
    """Audit verdicts (ok AND mismatch tallies) surface in the
    DeviceHealth snapshot — the fault-domain evidence MULTICHIP
    captures carry."""
    from stellar_tpu.parallel import device_health
    dh = device_health.get()
    dh.note_audit(2, ok=True, sampled=3)
    dh.note_audit(2, ok=False, sampled=1)
    dh.note_audit(None, ok=True, sampled=1)
    snap = dh.snapshot()
    assert snap["audits"]["2"] == {"ok": 1, "mismatch": 1}
    assert snap["audits"]["-1"] == {"ok": 1, "mismatch": 0}
    events = [h for h in dh.history()
              if h.get("event") == "audit-mismatch"]
    assert events and events[-1]["device"] == 2


def test_multichip_fault_domain_evidence_shape():
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "multichip_bench",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools",
            "multichip_bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    ev = mod.fault_domain_evidence()
    assert {"device_health", "quarantine_onsets",
            "audit_mismatch_events", "history_tail",
            "host_only"} <= set(ev)
    v = bv.BatchVerifier(bucket_sizes=(8,))
    ev2 = mod.fault_domain_evidence(v)
    assert "per_device_served" in ev2 and "served" in ev2


# ---------------- admin routes ----------------


class _StubApp:
    """spans / metrics?format=prometheus are served directly — no
    main-thread marshalling, so no clock is needed."""


def _http_get_raw(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/{path}", timeout=10) as r:
        return r.headers.get("Content-Type"), r.read().decode()


def test_spans_route_and_prometheus_export():
    from stellar_tpu.main.command_handler import CommandHandler
    registry.timer("span.verify.blocking").update_ms(5.0)
    handler = CommandHandler(_StubApp(), port=0)
    try:
        with tracing.span("live-span"):
            ctype, body = _http_get_raw(handler.port, "spans")
        assert ctype.startswith("application/json")
        out = json.loads(body)
        assert [r["name"] for r in out["active"]] == ["span.live-span"]
        assert {"recent", "capacity", "recorded_total",
                "dumps_total", "dump_reasons"} <= set(out)
        tracing.flight_recorder.dump("route-test")
        _, body2 = _http_get_raw(handler.port,
                                 "spans?dumps=true&limit=4")
        out2 = json.loads(body2)
        assert out2["dumps"][-1]["reason"] == "route-test"
        assert len(out2["recent"]) <= 4
        ctype3, text = _http_get_raw(handler.port,
                                     "metrics?format=prometheus")
        assert ctype3.startswith("text/plain")
        assert "span_verify_blocking_ms_count" in text
    finally:
        handler.stop()


def test_config_pushes_observability_knobs():
    from stellar_tpu.main.application import Application
    from stellar_tpu.main.config import Config
    from stellar_tpu.utils import metrics as metrics_mod
    saved = metrics_mod.RESERVOIR_SIZE
    try:
        cfg = Config(FLIGHT_RECORDER_SPANS=64,
                     METRICS_RESERVOIR_SIZE=32)
        Application(cfg)
        assert tracing.flight_recorder.snapshot()["capacity"] == 64
        assert metrics_mod.RESERVOIR_SIZE == 32
    finally:
        metrics_mod.RESERVOIR_SIZE = saved
        tracing.flight_recorder.configure(
            capacity=tracing.FlightRecorder.DEFAULT_CAPACITY)
