"""Transfer ledger (ISSUE 8): per-resolve host<->device byte
accounting, content-fingerprint redundancy detection, the engine hooks
that feed it, and the reconciliation against the engine's own
shape-derived accounting. See docs/observability.md "Transfer ledger"
and tools/transfer_selfcheck.py (the tier-1 TRANSFER_LEDGER_OK gate)."""

import numpy as np
import pytest

from stellar_tpu.crypto import batch_verifier as bv
from stellar_tpu.parallel import batch_engine
from stellar_tpu.utils import tracing
from stellar_tpu.utils.metrics import registry
from stellar_tpu.utils.transfer_ledger import (
    TransferLedger, transfer_ledger,
)


@pytest.fixture(autouse=True)
def clean_state():
    tracing.flight_recorder.clear()
    yield
    tracing.flight_recorder.clear()
    bv._reset_dispatch_state_for_testing()


# ---------------- unit: the ledger itself ----------------


def test_ledger_counts_and_redundancy():
    led = TransferLedger(resolves=8, fingerprints=64)
    tok = led.begin("test.ns")
    a = np.arange(32, dtype=np.uint8).reshape(4, 8)
    b = np.ones((2, 8), dtype=np.uint8)
    assert led.record_h2d(tok, a) == 32
    assert led.record_h2d(tok, b) == 16
    # same CONTENT again: redundant re-upload, the base/A-table shape
    led.record_h2d(tok, a.copy())
    led.record_d2h(tok, np.zeros(4, dtype=bool))
    rec = led.finish(tok)
    assert rec["bytes_h2d"] == 80
    assert rec["bytes_d2h"] == 4
    assert rec["device_puts"] == 3
    assert rec["round_trips"] == 1
    assert rec["redundant_constant_bytes"] == 32
    assert rec["redundant_uploads"] == 1
    tot = led.totals()
    assert tot["bytes_h2d"] == 80
    assert tot["round_trips"] == 1
    assert tot["resolves_recorded"] == 1
    assert led.recent(8) == [rec]


def test_ledger_finish_is_idempotent_and_ring_bounded():
    led = TransferLedger(resolves=4, fingerprints=64)
    toks = [led.begin("ns") for _ in range(6)]
    for t in toks:
        led.record_d2h(t, np.zeros(1, dtype=bool))
        led.finish(t)
        led.finish(t)  # resolver resolved twice records once
    assert led.totals()["resolves_recorded"] == 6
    assert len(led.recent(100)) == 4  # ring bound


def test_ledger_fingerprint_lru_bounded_and_configure():
    led = TransferLedger(resolves=8, fingerprints=16)
    for i in range(40):
        led.record_h2d(None, np.array([i], dtype=np.int64))
    assert led.totals()["fingerprints_tracked"] <= 16
    led.configure(resolves=4, fingerprints=32)
    assert led.totals()["fingerprints_tracked"] <= 32
    # distinct content is never redundant
    assert led.totals()["redundant_constant_bytes"] == 0


def test_ledger_fp_size_cap_counts_bytes_only():
    # uploads above the fingerprint cap: bytes counted, content NEVER
    # hashed (hot-path cost bound) — and never falsely redundant
    led = TransferLedger(resolves=8, fingerprints=64, fp_max_bytes=64)
    big = np.zeros(128, dtype=np.uint8)
    led.record_h2d(None, big)
    led.record_h2d(None, big.copy())  # same content, above cap
    tot = led.totals()
    assert tot["bytes_h2d"] == 256
    assert tot["redundant_constant_bytes"] == 0
    assert tot["unfingerprinted_uploads"] == 2
    assert tot["unfingerprinted_bytes"] == 256
    assert tot["fingerprints_tracked"] == 0
    # at-or-below the cap still fingerprints
    small = np.zeros(64, dtype=np.uint8)
    led.record_h2d(None, small)
    led.record_h2d(None, small.copy())
    tot = led.totals()
    assert tot["redundant_constant_bytes"] == 64
    assert tot["unfingerprinted_uploads"] == 2
    led.configure(fp_max_bytes=1024)
    led.record_h2d(None, big.copy())
    assert led.totals()["unfingerprinted_uploads"] == 2


def test_config_pushes_transfer_ledger_knobs():
    from stellar_tpu.main.application import Application
    from stellar_tpu.main.config import Config
    try:
        Application(Config(TRANSFER_LEDGER_RESOLVES=8,
                           TRANSFER_LEDGER_FINGERPRINTS=32,
                           TRANSFER_LEDGER_FP_MAX_BYTES=2048))
        assert transfer_ledger._ring.maxlen == 8
        assert transfer_ledger._fp_cap == 32
        assert transfer_ledger._fp_max_bytes == 2048
    finally:
        transfer_ledger.configure(resolves=256, fingerprints=4096,
                                  fp_max_bytes=1 << 20)


# ---------------- engine hooks (jax-CPU, trivial kernel) ----------------


class _XferWorkload(batch_engine.Workload):
    """Tiny stub: one (n, 2) uint8 operand, kernel = first column.
    Compiles in milliseconds on jax-CPU — the hook test's point is the
    LEDGER, not the kernel."""

    metrics_ns = "test.xfer"
    span_ns = "xfer"

    def encode(self, items):
        arr = np.array([[v, v + 1] for v in items], dtype=np.uint8)
        return np.ones(len(items), dtype=bool), (arr,)

    def pad_rows(self):
        return (np.zeros((1, 2), dtype=np.uint8),)

    def kernel_fn(self):
        def k(a):
            return a[:, 0]
        return k

    def empty_result(self, n):
        return np.zeros(n, dtype=np.uint8)

    def host_result(self, items):
        return np.array(list(items), dtype=np.uint8)

    def finalize(self, gate, out, items):
        return out


def test_engine_device_path_records_and_reconciles():
    """A dispatched resolve records h2d at the upload, d2h + a round
    trip at the fetch; a SECOND resolve of identical content uploads
    again (the engine keeps no device buffer past its call) and the
    redundancy detector convicts exactly that re-upload — and the
    ledger's deltas still reconcile EXACTLY with the engine's own
    shape-derived accounting."""
    eng = batch_engine.BatchEngine(_XferWorkload(), bucket_sizes=(4,))
    items = [10, 20, 30, 40]
    before = transfer_ledger.totals()
    out = eng.compute_batch(items)
    assert list(out) == items
    mid = transfer_ledger.totals()
    assert mid["bytes_h2d"] - before["bytes_h2d"] == 8   # (4, 2) uint8
    assert mid["bytes_d2h"] - before["bytes_d2h"] == 4   # (4,) uint8
    assert mid["round_trips"] - before["round_trips"] == 1
    assert mid["redundant_constant_bytes"] == \
        before["redundant_constant_bytes"]
    out = eng.compute_batch(items)  # identical content: a second upload
    assert list(out) == items
    after = transfer_ledger.totals()
    assert after["bytes_h2d"] - mid["bytes_h2d"] == 8
    assert after["redundant_constant_bytes"] - \
        mid["redundant_constant_bytes"] == 8
    assert after["device_puts"] - before["device_puts"] == 2
    # reconciliation: ledger deltas == engine's independent tally
    assert after["bytes_h2d"] - before["bytes_h2d"] == \
        eng.shipped_bytes == 16
    assert after["bytes_d2h"] - before["bytes_d2h"] == \
        eng.fetched_bytes == 8
    # per-resolve records landed in the ring; the second resolve's
    # record carries the redundant re-upload
    last = transfer_ledger.recent(2)
    assert [r["round_trips"] for r in last] == [1, 1]
    assert [r["bytes_h2d"] for r in last] == [8, 8]
    assert [r["redundant_constant_bytes"] for r in last] == [0, 8]
    assert "resident_hits" not in last[-1]


def test_redundancy_detector_convicts_repeats_across_engines():
    """The detector's fingerprints are process-wide: the same bytes
    shipped by a second engine are convicted as a re-upload, while
    different content is not."""
    items = [50, 60, 70, 80]
    before = transfer_ledger.totals()
    eng = batch_engine.BatchEngine(_XferWorkload(), bucket_sizes=(4,))
    assert list(eng.compute_batch(items)) == items
    other = batch_engine.BatchEngine(_XferWorkload(), bucket_sizes=(4,))
    assert list(other.compute_batch(items)) == items
    assert list(other.compute_batch([51, 61, 71, 81])) == \
        [51, 61, 71, 81]
    after = transfer_ledger.totals()
    assert after["bytes_h2d"] - before["bytes_h2d"] == 24
    assert after["redundant_constant_bytes"] - \
        before["redundant_constant_bytes"] == 8
    assert after["redundant_uploads"] - before["redundant_uploads"] == 1
    # every upload really shipped: engine tallies match the ledger
    assert eng.shipped_bytes + other.shipped_bytes == 24


def test_host_only_resolve_moves_zero_bytes():
    """The integrity posture never touches the device — the ledger
    must show it (a host-only record claiming transfers would be
    fiction)."""
    bv._enter_host_only("test: transfer ledger host-only")
    eng = batch_engine.BatchEngine(_XferWorkload(), bucket_sizes=(4,))
    before = transfer_ledger.totals()
    out = eng.compute_batch([1, 2, 3, 4])
    assert list(out) == [1, 2, 3, 4]
    after = transfer_ledger.totals()
    for k in ("bytes_h2d", "bytes_d2h", "round_trips", "device_puts"):
        assert after[k] == before[k], k
    # the resolve still records (all-zero) so the ring stays complete
    assert after["resolves_recorded"] == before["resolves_recorded"] + 1


def test_transfer_surfaces_in_health_and_prometheus():
    eng = batch_engine.BatchEngine(_XferWorkload(), bucket_sizes=(4,))
    eng.compute_batch([7, 8, 9, 10])
    health = bv.dispatch_health()
    assert health["transfer"]["round_trips"] >= 1
    assert set(health["transfer"]) >= {
        "round_trips", "bytes_h2d", "bytes_d2h",
        "redundant_constant_bytes", "resolves_recorded"}
    text = registry.to_prometheus()
    for name in ("crypto_transfer_bytes_h2d", "crypto_transfer_fetches",
                 "crypto_transfer_round_trips"):
        assert name in text, name
