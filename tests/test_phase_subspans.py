"""Sub-spans inside the resolve phases: where ``verify.prep``,
``verify.dispatch`` and ``verify.audit`` spend their time, the result
cache's ``keys.*`` spans, and the profiler capture of every span.

The kernels are stubbed with a jitted all-accept (every row here is a
valid signature, so the audit agrees with it): the spans under test are
host-side, and a stub keeps the test free of kernel compiles.
"""

import glob
import statistics

import numpy as np
import pytest

from stellar_tpu.crypto import batch_verifier as bv
from stellar_tpu.crypto import ed25519_ref as ref
from stellar_tpu.crypto import keys
from stellar_tpu.crypto.batch_verifier import BatchVerifier
from stellar_tpu.parallel import signer_tables as st
from stellar_tpu.utils import tracing

# child -> the parent it must nest in, for every new verify.* span
PARENTS = {
    "span.verify.prep.partition": "span.verify.prep",
    "span.verify.table_build": "span.verify.prep.partition",
    "span.verify.prep.challenge": "span.verify.prep",
    "span.verify.prep.gate": "span.verify.prep",
    "span.verify.prep.gather": "span.verify.prep",
    "span.verify.place": "span.verify.dispatch",
    "span.verify.audit.sample": "span.verify.audit",
    "span.verify.audit.oracle": "span.verify.audit",
}
ROUNDING_MS = 0.001  # recorder stamps are rounded to the microsecond


@pytest.fixture(autouse=True)
def fresh_dispatch():
    bv._reset_dispatch_state_for_testing()
    st.signer_table_cache.configure(max_bytes=st.DEFAULT_CACHE_BYTES,
                                    enabled=True)
    yield
    bv._reset_dispatch_state_for_testing()
    keys.set_verifier_backend(None)
    keys.flush_verify_cache()


def _accept_all(*operands):
    import jax.numpy as jnp
    return jnp.ones(operands[1].shape[0], dtype=bool)


def _signed(n, tag):
    out = []
    for i in range(n):
        seed = bytes([tag, i]) * 16
        msg = b"sub-span row %d" % i
        out.append((ref.secret_to_public(seed), msg, ref.sign(seed, msg)))
    return out


def _verifier(mesh):
    v = BatchVerifier(mesh=mesh, bucket_sizes=(16,))
    for plugin in (v._plugin, v._hot):
        plugin.kernel_fn = lambda: _accept_all
    return v


def _mesh8():
    import jax
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:8]), ("batch",))


def _warm_then_run(mesh=None):
    """Three signers whose tables are built by a first call, then the
    measured call: their rows ride hot, one first-sight row rides cold
    and builds its table. Returns the measured call's records."""
    v = _verifier(mesh)
    warm = _signed(3, 1)
    assert v.verify_batch(warm).all()
    tracing.flight_recorder.clear()
    items = warm + _signed(1, 2)
    assert v.verify_batch(items).all()
    return tracing.flight_recorder.snapshot(limit=4096)["recent"]


@pytest.mark.parametrize("mesh", [None, "mesh8"])
def test_every_new_verify_span_nests_in_its_parent(mesh):
    recs = _warm_then_run(_mesh8() if mesh else None)
    by_id = {r["id"]: r for r in recs}
    seen = {r["name"] for r in recs}
    assert set(PARENTS) <= seen, set(PARENTS) - seen
    for r in recs:
        want = PARENTS.get(r["name"])
        if want is None:
            continue
        parent = by_id[r["parent"]]
        assert parent["name"] == want, (r["name"], parent["name"])
        assert parent["thread"] == r["thread"]
        assert r["start_ms"] >= parent["start_ms"] - ROUNDING_MS
        assert r["start_ms"] + r["dur_ms"] <= \
            parent["start_ms"] + parent["dur_ms"] + 2 * ROUNDING_MS
    place = [r for r in recs if r["name"] == "span.verify.place"]
    assert sum(r["attrs"]["h2d_bytes"] for r in place) > 0
    # every placement uploads: no operand is served from a device cache
    assert all(r["attrs"]["h2d_bytes"] > 0 for r in place)
    assert not any("resident_hits" in r["attrs"] for r in place)


def test_children_of_prep_and_audit_sum_to_no_more_than_the_parent():
    recs = _warm_then_run()
    kids = {}
    for r in recs:
        kids.setdefault(r["parent"], []).append(r)
    checked = 0
    for r in recs:
        if r["name"] not in ("span.verify.prep", "span.verify.audit"):
            continue
        mine = kids.get(r["id"], [])
        if not mine:
            continue
        checked += 1
        total = sum(c["dur_ms"] for c in mine)
        assert total <= r["dur_ms"] + len(mine) * ROUNDING_MS, r
    assert checked >= 3  # the partition's prep, both encodes, an audit


def test_attribution_still_reads_the_six_phases_alone():
    """The sub-spans join no phase: the root-attributed timers are the
    six phases' and nothing else, and their sum still reconciles to
    the blocking root."""
    assert bv.batch_engine.PHASE_SUFFIXES == (
        "prep", "bucket", "dispatch", "fetch", "audit", "host_fallback")
    v = _verifier(None)
    warm = _signed(3, 3)
    v.verify_batch(warm)
    before = tracing.span_totals()
    tracing.flight_recorder.clear()
    v.verify_batch(warm + _signed(1, 4))
    after = tracing.span_totals()
    att = bv.dispatch_attribution(before, after, reps=1)
    delta = {k for k in after
             if k.startswith("span.attr.") and
             after[k]["count"] != before.get(k, {"count": 0})["count"]}
    assert delta <= {f"span.attr.{p}" for p in bv.RESOLVE_PHASES}
    assert "span.attr.verify.prep" in delta
    # prep is counted once per prep span, at the prep spans' own time
    # (children never fold into it twice)
    preps = [r for r in tracing.flight_recorder.snapshot(
        limit=4096)["recent"] if r["name"] == "span.verify.prep"]
    d_prep = after["span.attr.verify.prep"]["count"] - \
        before["span.attr.verify.prep"]["count"]
    assert d_prep == len(preps) == 3
    assert att["coverage"] is not None and 0.5 < att["coverage"] <= 1.01


def test_a_verify_sig_cache_hit_records_no_span():
    keys.set_verifier_backend(None)
    keys.flush_verify_cache()
    (pk, msg, sig), = _signed(1, 5)
    t0 = tracing.span_totals()
    n0 = tracing.flight_recorder.stats()["recorded_total"]
    assert keys.verify_sig(pk, msg, sig)          # miss: probe + seed
    t1 = tracing.span_totals()
    n1 = tracing.flight_recorder.stats()["recorded_total"]
    assert n1 - n0 == 2
    for name in ("span.keys.probe", "span.keys.seed"):
        assert t1[name]["count"] - t0.get(name, {"count": 0})["count"] == 1
    for _ in range(5):
        assert keys.verify_sig(pk, msg, sig)      # hits
    assert tracing.flight_recorder.stats()["recorded_total"] == n1
    assert tracing.span_totals() == t1


def test_spans_reach_the_profiler_on_the_recorders_clock(tmp_path):
    """Under a profiler session every span is also a TraceMe of the
    same name on the host plane, and the two clocks differ by one
    constant offset (what ``benchmark/trace_reduce.align_spans``
    assumes)."""
    import jax
    from jax.profiler import ProfileData
    v = _verifier(None)
    warm = _signed(3, 6)
    v.verify_batch(warm)
    tracing.flight_recorder.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        v.verify_batch(warm + _signed(1, 7))
        with tracing.span("outer"), tracing.zone("inner"):
            pass
    finally:
        jax.profiler.stop_trace()
    recs = [r for r in tracing.flight_recorder.snapshot(
        limit=4096)["recent"] if r.get("dur_ms") is not None
        and not r.get("event")]
    xplane, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    events = {}
    for plane in ProfileData.from_file(xplane).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("span.", "zone.")):
                    events.setdefault(ev.name, []).append(ev.start_ns)
    names = {r["name"] for r in recs}
    assert {"span.outer", "zone.inner"} | set(PARENTS) <= names
    offsets = []
    for name in names:
        mine = sorted(r["start_ms"] for r in recs if r["name"] == name)
        theirs = sorted(events.get(name, []))
        assert len(theirs) == len(mine), name
        offsets += [t - (tracing._EPOCH + m / 1e3) * 1e9
                    for m, t in zip(mine, theirs)]
    mid = statistics.median(offsets)
    assert max(abs(o - mid) for o in offsets) < 0.1e6  # 0.1 ms
