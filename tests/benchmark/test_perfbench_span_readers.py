"""The per-layer readers of the phases' sub-spans: each returns its
timers' time per call on a synthetic window, and nothing when its timer
or the calls are absent."""

from pathlib import Path

import pytest

from benchmark import harness

ROOT = Path(__file__).resolve().parents[2]

# reader -> the timers it sums
READS = {
    "cache_ms": ("span.keys.probe", "span.keys.seed"),
    "cache_ms.envelope": ("span.keys.probe", "span.keys.seed"),
    "challenge_ms": ("span.verify.prep.challenge",),
    "gather_ms": ("span.verify.prep.gather",),
    "place_ms": ("span.verify.place",),
    "place_ms.envelope": ("span.verify.place",),
    "audit_sample_ms": ("span.verify.audit.sample",),
    "audit_oracle_ms": ("span.verify.audit.oracle",),
    "herder_ms": ("span.herder.payload",),
}


def _ctx(spans, calls=4):
    return {"calls": [{"rows": [b"r"]}] * calls, "window_s": 1.0,
            "setup_s": 1.0, "trace": None, "spans": spans,
            "accounting": {"hot_rows": 0, "cold_rows": 0}}


@pytest.mark.parametrize("name", sorted(READS))
def test_reader_gives_its_timers_per_call(name):
    read = harness.reader(ROOT, name)
    spans = {t: {"count": 8, "sum_ms": 2.0 * (k + 1)}
             for k, t in enumerate(READS[name])}
    spans["span.verify.prep"] = {"count": 8, "sum_ms": 100.0}
    want = sum(s["sum_ms"] for t, s in spans.items() if t in READS[name])
    assert read(_ctx(spans)) == pytest.approx(want / 4)


@pytest.mark.parametrize("name", sorted(READS))
def test_reader_gives_nothing_without_its_timer_or_calls(name):
    read = harness.reader(ROOT, name)
    assert read(_ctx({"span.verify.prep": {"count": 1, "sum_ms": 1.0}})) \
        is None
    spans = {t: {"count": 1, "sum_ms": 1.0} for t in READS[name]}
    assert read(_ctx(spans, calls=0)) is None


def test_cache_ms_reads_either_timer_alone():
    read = harness.reader(ROOT, "cache_ms")
    assert read(_ctx({"span.keys.seed": {"count": 2, "sum_ms": 0.4}})) == \
        pytest.approx(0.1)


def test_each_reader_has_its_entry_with_its_cells():
    plan = harness.load_spec(ROOT)
    entries = {m["name"]: m for m in plan["per_layer"]}
    for name in READS:
        m = entries[name]
        assert m["source"] == "program_span" and m["better"] == "lower"
        cell = "scp-envelopes" if m["moves"].startswith("envelope_") \
            else "txset-multisig"
        assert m["workloads"] == [cell]
