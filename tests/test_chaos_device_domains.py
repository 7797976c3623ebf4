"""Chaos suite for PER-DEVICE fault domains (ISSUE 4 /
``docs/robustness.md`` "Per-device fault domains").

The multi-device scenarios need a multi-device jax backend, and device
count is fixed at backend init — so the quarantine lifecycle runs in
ONE subprocess forced to 4 CPU devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``,
``tests/_device_domain_script.py``) whose phase records the tests here
assert on:

* ``fail-device:1`` benches ONLY device 1 — the three survivors keep
  serving device-path verifies (no global host fallback), with
  decisions bit-identical throughout;
* the degraded re-shard introduces NO new kernel shapes (the
  compile-reuse invariant);
* the healed device regrows via the half-open probe sub-chunk;
* ``corrupt-device:2`` (wrong bits, no failure signal) is caught by
  the sampled result-integrity audit: the device is quarantined, the
  process flips host-only, and the corrupted verdicts never surface.

The unit half of the module (no subprocess) covers the deterministic
audit sampler, the per-device fault modes, the DeviceHealth registry,
and the pooled resolve watchdog.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from stellar_tpu.crypto import audit
from stellar_tpu.parallel.device_health import DeviceHealth
from stellar_tpu.utils import faults, resilience

pytestmark = pytest.mark.chaos

# The subprocess lifecycle tests are ALSO marked slow: they run once
# per tier-1, inside the dedicated `-m chaos` gate (tools/tier1.sh),
# not a second time inside the `-m 'not slow'` sweep — the driver
# subprocess pays jax init + up to 4 per-device kernel compiles, which
# must not ride the sweep's fixed budget twice.
lifecycle = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "tests", "_device_domain_script.py")


# ---------------- the 4-device subprocess lifecycle ----------------


@pytest.fixture(scope="module")
def domain_run():
    """Run the full quarantine lifecycle once (module-scoped: the
    subprocess pays jax init + up to 4 per-device kernel compiles —
    parallel warm-up plus a persistent compilation cache keep reruns
    cheap) and hand every test its phase records."""
    env = {**os.environ,
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    env.pop("STELLAR_TPU_FAULTS", None)
    p = subprocess.run([sys.executable, SCRIPT], capture_output=True,
                       text=True, timeout=560, env=env, cwd=REPO)
    assert p.returncode == 0, \
        f"driver failed rc={p.returncode}\n--- stderr ---\n" \
        f"{p.stderr[-3000:]}\n--- stdout ---\n{p.stdout[-1000:]}"
    return json.loads(p.stdout.strip().splitlines()[-1])


@lifecycle
def test_baseline_all_devices_serve(domain_run):
    ph = domain_run["phases"]["baseline"]
    assert ph["bit_identical"]
    assert ph["served"]["host-fallback"] == 0
    # 16 items over 4 devices, 2 chunks: every device served its share
    assert ph["device_served"] == {"0": 4, "1": 4, "2": 4, "3": 4}
    assert ph["quarantined"] == []


@lifecycle
def test_single_device_failure_is_isolated(domain_run):
    """ISSUE 4 acceptance: fail-device:1 benches ONE device; >= 3
    devices keep serving device-path verifies, and only device 1's
    rows (up to threshold x sub-chunk) ride the host."""
    ph = domain_run["phases"]["fail_device_1"]
    assert ph["bit_identical"]
    # device 1's two sub-chunks (2 rows each) fell back before its
    # breaker opened at threshold 2 — nothing else did
    assert ph["served"]["host-fallback"] == 4
    assert ph["quarantined"] == [1]
    surviving = {d for d, n in ph["device_served"].items()
                 if n > domain_run["phases"]["baseline"]
                 ["device_served"][d]}
    assert surviving >= {"0", "2", "3"}


@lifecycle
def test_degraded_reshard_serves_fully_on_survivors(domain_run):
    """With device 1 quarantined the batch re-shards over the three
    survivors: everything rides the device path except (at most) one
    half-open PROBATION sub-chunk that device 1's breaker may grant —
    whose failure against the still-armed fault re-opens it."""
    ph = domain_run["phases"]["degraded"]
    assert ph["bit_identical"]
    assert ph["host_fallback_delta"] <= 2  # <= one probe sub-chunk
    assert ph["device_delta"] >= 14
    assert ph["quarantined"] == [1]


@lifecycle
def test_degraded_reshard_compiles_no_new_kernels(domain_run):
    """The compile-reuse invariant: quarantine re-assigns sub-chunks,
    it never introduces a new dispatch shape (a fresh bucket would be
    a ~2-minute XLA compile in the middle of degradation)."""
    assert domain_run["phases"]["baseline"]["kernel_shapes"] == \
        domain_run["phases"]["degraded"]["kernel_shapes"] == [2]


@lifecycle
def test_coalesced_dispatch_reuses_subchunk_kernels(domain_run):
    """ISSUE 12: the healthy baseline really rode the COALESCED
    per-mesh upload (one sharded h2d per bucket, per-device shard
    kernel calls), and neither coalescing nor the degraded re-shard
    that follows it compiled any shape beyond the single sub-chunk
    executable — the coalesced path feeds the SAME per-device
    executables the legacy path uses, so degradation under it still
    pays zero fresh XLA compiles."""
    base = domain_run["phases"]["baseline"]
    degraded = domain_run["phases"]["degraded"]
    assert base["coalesced_dispatches"] > 0
    # degradation leaves the coalesced path (assignment != identity)
    # without minting a new dispatch shape
    assert degraded["kernel_shapes"] == base["kernel_shapes"] == [2]


@lifecycle
def test_healed_device_regrows(domain_run):
    """After the fault clears, the half-open probe sub-chunk re-closes
    device 1's breaker and it rejoins the rotation."""
    ph = domain_run["phases"]["healed"]
    assert ph["bit_identical"]
    assert ph["quarantined"] == []
    assert ph["dev1_delta"] > 0


@lifecycle
def test_corrupt_device_caught_quarantined_host_only(domain_run):
    """ISSUE 4 acceptance: corrupt-device:2 (wrong bits, no failure
    signal) is caught by the audit; the device is quarantined, the
    process flips host-only, and decisions stay bit-identical — the
    corrupted verdicts never surface."""
    ph = domain_run["phases"]["corrupt_device_2"]
    assert ph["bit_identical"]
    assert ph["audit_mismatches"] >= 1
    assert 2 in ph["quarantined"]
    assert ph["device2_state"] == "open"
    assert ph["host_only"] is True


@lifecycle
def test_host_only_steady_state(domain_run):
    """Once corruption was seen, no device dispatch happens at all —
    and decisions still match the oracle."""
    ph = domain_run["phases"]["host_only_steady"]
    assert ph["bit_identical"]
    assert ph["device_delta"] == 0
    assert domain_run["dispatch_health"]["host_only"] is True
    assert domain_run["dispatch_health"]["audit"]["mismatches"] >= 1


@lifecycle
def test_hot_signer_table_serves_on_chaos_mesh(domain_run):
    """ISSUE 16: on the forced 4-device mesh, a repeat signer's cached
    A-table actually serves rows through the HOT kernel variant (cache
    hits > 0, one install for one signer), verdicts stay bit-identical
    to the oracle, and the variant introduces no kernel shape beyond
    the single pinned sub-chunk executable."""
    ph = domain_run["phases"]["hot_signer_serve"]
    assert ph["bit_identical"]
    st = ph["signer_tables"]
    assert st["enabled"]
    assert st["entries"] == 1 and st["installs"] == 1
    assert st["hits"] > 0
    assert st["audit_evictions"] == 0
    assert ph["kernel_shapes"] == [2]


@lifecycle
def test_audit_conviction_evicts_served_signer_table(domain_run):
    """ISSUE 16 hardening: corrupt-device:2 convicted WHILE the cached
    table was serving the batch — the conviction must evict that
    signer's entry (nothing a convicted chip served stays trusted; the
    table is re-derived from the pubkey on next sight), with the
    corrupted verdicts never surfacing and the process flipped
    host-only."""
    ph = domain_run["phases"]["hot_signer_audit_evict"]
    assert ph["bit_identical"]
    st = ph["signer_tables"]
    assert st["audit_evictions"] >= 1
    assert st["entries"] == 0
    assert 2 in ph["quarantined"]
    assert ph["host_only"] is True


@lifecycle
def test_breaker_history_records_lifecycle(domain_run):
    """The DeviceHealth history ring carries the whole story: device
    1's open -> half-open -> closed arc and device 2's quarantine."""
    hist = domain_run["breaker_history"]
    changes = [(h["device"], h.get("from"), h.get("to"))
               for h in hist if "from" in h]
    assert (1, "closed", "open") in changes
    assert (1, "half-open", "closed") in changes
    assert (2, "closed", "open") in changes
    events = [(h["device"], h.get("event"), h.get("reason"))
              for h in hist if "event" in h]
    assert (2, "quarantine", "audit-mismatch") in events


# ---------------- deterministic audit sampler ----------------


def test_audit_sampler_deterministic_and_bounded():
    m = b"chunk material"
    a = audit.sample_indices(m, 100, 0.05)
    b = audit.sample_indices(m, 100, 0.05)
    assert a == b  # content-seeded: replicas agree
    assert len(a) == 5 and len(set(a)) == 5
    assert all(0 <= i < 100 for i in a)
    # different content -> (almost surely) different sample
    assert audit.sample_indices(b"other", 100, 0.05) != a


def test_audit_sampler_edge_rates():
    assert audit.sample_indices(b"x", 100, 0.0) == []
    assert audit.sample_indices(b"x", 0, 1.0) == []
    # min one row per part, even at tiny rates
    assert len(audit.sample_indices(b"x", 8, 0.001)) == 1
    # rate >= 1 audits every row, in order
    assert audit.sample_indices(b"x", 8, 1.0) == list(range(8))


def test_audit_sample_rows_only_draws_eligible():
    """The audit must never burn its sample on rows the host policy
    gate already rejected — those compare False==False regardless of
    device bits (a vacuous check, and a blind spot a corrupting chip
    could predict from the batch bytes it holds)."""
    eligible = [2, 5, 7]
    rows = audit.sample_rows(b"material", eligible, 1.0)
    assert rows == eligible  # every eligible row, nothing else
    rows = audit.sample_rows(b"material", eligible, 0.01)
    assert len(rows) == 1 and rows[0] in eligible
    # deterministic in (content, eligibility)
    assert rows == audit.sample_rows(b"material", eligible, 0.01)
    # no eligible rows -> nothing to audit (no device bit can reach a
    # verdict in such a part)
    assert audit.sample_rows(b"material", [], 1.0) == []


# ---------------- per-device fault modes ----------------


def test_per_device_fault_modes():
    faults.clear()
    faults.set_fault("p.fail", "fail-device", 1)
    faults.inject("p.fail", device=0)           # other device: no-op
    faults.inject("p.fail", device=None)        # unattributed: no-op
    with pytest.raises(faults.FaultInjected):
        faults.inject("p.fail", device=1)
    faults.set_fault("p.flaky", "flaky-device", 2)
    faults.inject("p.flaky", device=2)          # matching call 1: passes
    with pytest.raises(faults.FaultInjected):
        faults.inject("p.flaky", device=2)      # matching call 2: fires
    c = faults.counters()
    assert c["p.fail"] == {"mode": "fail-device", "calls": 1, "fired": 1}
    assert c["p.flaky"] == {"mode": "flaky-device", "calls": 2,
                            "fired": 1}
    faults.clear()


def test_corrupt_device_verdict_flip():
    faults.clear()
    faults.set_fault("p.res", "corrupt-device", 3)
    arr = np.array([True, False, True])
    # inject() never raises for corrupt mode — the corruption rides
    # the fetched verdicts
    faults.inject("p.res", device=3)
    assert (faults.corrupt_verdicts("p.res", 1, arr) == arr).all()
    assert (faults.corrupt_verdicts("p.res", None, arr) == arr).all()
    flipped = faults.corrupt_verdicts("p.res", 3, arr)
    assert (flipped == ~arr).all()
    assert faults.counters()["p.res"]["fired"] == 1
    faults.clear()
    assert (faults.corrupt_verdicts("p.res", 3, arr) == arr).all()


def test_device_fault_requires_index():
    with pytest.raises(ValueError):
        faults.set_fault("p.x", "fail-device")


# ---------------- DeviceHealth registry ----------------


def test_device_health_lifecycle():
    h = DeviceHealth(failure_threshold=2, backoff_min_s=0.05,
                     backoff_max_s=0.2)
    assert h.available_devices(3) == [0, 1, 2]
    h.record_failure(1)
    assert h.available_devices(3) == [0, 1, 2]  # below threshold
    h.record_failure(1)
    assert h.quarantined(3) == [1]
    assert h.available_devices(3) == [0, 2]
    time.sleep(0.1)
    # backoff expired: ONE half-open probe grant for device 1
    avail = h.available_devices(3)
    assert avail == [0, 1, 2]
    assert h.available_devices(3) == [0, 2]  # grant consumed
    h.record_success(1)
    assert h.available_devices(3) == [0, 1, 2]
    changes = [(e["device"], e.get("from"), e.get("to"))
               for e in h.history() if "from" in e]
    assert (1, "closed", "open") in changes
    assert (1, "half-open", "closed") in changes


def test_assign_parts_round_robins_survivors_and_honors_grants():
    h = DeviceHealth(failure_threshold=1, backoff_min_s=0.05,
                     backoff_max_s=0.2)
    # all healthy: identity assignment
    assert h.assign_parts(4, 4) == [0, 1, 2, 3]
    # short batch: only as many parts as carry rows
    assert h.assign_parts(4, 2) == [0, 1]
    # device 1 quarantined (backoff NOT expired): survivors round-robin
    h.record_failure(1)
    assert h.assign_parts(4, 4) == [0, 2, 3, 0]
    time.sleep(0.1)
    # backoff expired: device 1 gets exactly ONE probation part
    parts = h.assign_parts(4, 4)
    assert parts.count(1) == 1
    assert set(parts) == {0, 1, 2, 3}
    # grant consumed: immediately after, device 1 is out again
    assert h.assign_parts(4, 4) == [0, 2, 3, 0]


def test_assign_parts_short_batch_preserves_unused_grants():
    """A probation grant must not be burned on a batch too short to
    reach the device — the regrow probe waits for a batch that will
    actually carry it (the half-open-parking hazard)."""
    h = DeviceHealth(failure_threshold=1, backoff_min_s=0.05,
                     backoff_max_s=0.2)
    h.record_failure(1)
    h.record_failure(2)
    time.sleep(0.1)  # both grants available
    # one part: only device 1's grant is consulted/consumed
    assert h.assign_parts(4, 1) == [1]
    # device 2's grant survived the short batch and is used next
    assert h.assign_parts(4, 1) == [2]
    # both consumed now: healthy rotation
    assert h.assign_parts(4, 1) == [0]


def test_assign_parts_all_quarantined_falls_back_to_host():
    h = DeviceHealth(failure_threshold=1, backoff_min_s=10.0)
    for i in range(3):
        h.record_failure(i)
    assert h.assign_parts(3, 3) == [None, None, None]


def test_record_failure_reports_quarantine_onset():
    """The True return marks the OPEN transition exactly once — the
    hook batch_verifier uses to escalate correlated outages to the
    global breaker. The transition is claimed under the breaker's own
    lock, so concurrent failure reports can't double-count an onset."""
    h = DeviceHealth(failure_threshold=2, backoff_min_s=10.0)
    assert h.record_failure(0) is False   # below threshold
    assert h.record_failure(0) is True    # opened now
    assert h.record_failure(0) is False   # already open
    # hammer one device from many threads: exactly one onset claimed
    h2 = DeviceHealth(failure_threshold=4, backoff_min_s=10.0)
    onsets = []
    lk = threading.Lock()

    def fail():
        if h2.record_failure(1):
            with lk:
                onsets.append(1)

    threads = [threading.Thread(target=fail) for _ in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(onsets) == 1


def test_device_health_hard_quarantine():
    h = DeviceHealth(failure_threshold=5, backoff_min_s=10.0)
    h.quarantine(2, reason="audit-mismatch")  # no failure streak needed
    assert h.quarantined(4) == [2]
    assert h.available_devices(4) == [0, 1, 3]
    events = [(e["device"], e.get("event"), e.get("reason"))
              for e in h.history() if "event" in e]
    assert (2, "quarantine", "audit-mismatch") in events
    snap = h.snapshot()
    assert snap["devices"]["2"]["state"] == "open"
    assert snap["quarantined"] == [2]


def test_device_health_configure_applies_to_existing_breakers():
    h = DeviceHealth(failure_threshold=5)
    h.record_failure(0)  # creates breaker 0 at threshold 5
    h.configure(failure_threshold=1)
    h.record_failure(0)
    assert h.quarantined(1) == [0]  # new threshold in force


# ---------------- pooled resolve watchdog ----------------


def test_watchdog_pool_reuses_workers():
    pool = resilience.WatchdogPool(name="t-pool")
    for _ in range(10):
        job = pool.submit(lambda: 7)
        assert job["done"].wait(5) and job["box"]["out"] == 7
    stats = pool.stats()
    # sequential submits reuse the worker (a just-finished worker may
    # lose the race back to the idle set once or twice — but nothing
    # like thread-per-call)
    assert stats["spawned_total"] <= 3
    assert stats["idle"] >= 1


def test_watchdog_pool_concurrent_and_hang_self_heal():
    pool = resilience.WatchdogPool(name="t-pool2", max_idle=2)
    ev = threading.Event()
    hung = pool.submit(ev.wait)             # parks one worker
    jobs = [pool.submit(lambda: 1) for _ in range(4)]
    for j in jobs:
        assert j["done"].wait(5) and j["box"]["out"] == 1
    # the hung worker never blocked the others
    assert not hung["done"].is_set()
    ev.set()                                # hang resolves
    assert hung["done"].wait(5)
    time.sleep(0.05)
    assert pool.stats()["idle"] >= 1        # worker rejoined the pool

def test_call_with_deadline_uses_shared_pool():
    before = resilience.watchdog_stats()["spawned_total"]
    for _ in range(10):
        assert resilience.call_with_deadline(lambda: 3, 2.0) == 3
    after = resilience.watchdog_stats()["spawned_total"]
    assert after - before <= 3  # pooled: no thread-per-call
