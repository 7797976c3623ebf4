"""Subprocess driver for the per-device fault-domain chaos suite.

Runs under ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (a
forced 4-device CPU host — tier-1's pytest process is single-device,
and jax device count is fixed at backend init, so the multi-device
scenarios need their own process). Executes the full quarantine
lifecycle against REAL per-device dispatch and prints one JSON line of
phase records; ``tests/test_chaos_device_domains.py`` asserts on them.

Compile budget: only ONE kernel shape is ever compiled (sub-chunk =
bucket 8 // 4 devices = 2 rows), but jax compiles it once PER DEVICE
(~55s each on CPU). Two mitigations keep this inside the tier-1
budget: the per-device warm-up runs in parallel threads (XLA's C++
compile releases the GIL), and the persistent compilation cache
(``stellar_tpu.utils.jax_setup``) makes every run after the first load
instead of compile.
"""

import json
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))

from stellar_tpu.utils.jax_setup import (  # noqa: E402
    enable_compile_cache, force_cpu,
)

force_cpu()
enable_compile_cache()

import jax  # noqa: E402

jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

import numpy as np  # noqa: E402

from stellar_tpu.crypto import batch_verifier as bv  # noqa: E402
from stellar_tpu.crypto import ed25519_ref as ref  # noqa: E402
from stellar_tpu.parallel import device_health, mesh as mesh_mod  # noqa: E402
from stellar_tpu.utils import faults  # noqa: E402

N_DEV = 4
BUCKET = 8
SUB = BUCKET // N_DEV


def tiled_items(n):
    """n items tiled from a small signed pool (pure-Python signing is
    ~25ms/sig) with host-oracle expectations computed once per pool
    entry. Pool layout keeps every device's sub-chunk rows dominated
    by VALID signatures so verdict corruption is observable."""
    import secrets
    pool = []
    for i in range(6):
        seed = secrets.token_bytes(32)
        pk = ref.secret_to_public(seed)
        msg = secrets.token_bytes(1 + i)
        pool.append((pk, msg, ref.sign(seed, msg)))
    pk0, m0, s0 = pool[0]
    pool.append((pk0, m0 + b"!", s0))        # tampered message
    pool.append((pk0[:31], m0, s0))          # bad pk length
    want_pool = np.array([ref.verify(p, m, s) for p, m, s in pool])
    idx = np.arange(n) % len(pool)
    return [pool[i] for i in idx], want_pool[idx]


def main():
    out = {"phases": {}}
    devs = jax.devices()
    out["n_devices"] = len(devs)
    assert len(devs) == N_DEV, f"expected {N_DEV} devices, got {devs}"

    mesh = mesh_mod.batch_mesh()
    v = bv.BatchVerifier(mesh=mesh, bucket_sizes=(BUCKET,))
    bv._reset_dispatch_state_for_testing()
    bv.configure_dispatch(deadline_ms=30_000, dispatch_retries=0,
                          failure_threshold=3,
                          audit_rate=1.0,  # every row: corruption is a
                                           # guaranteed catch
                          device_failure_threshold=2,
                          device_backoff_min_s=0.3,
                          device_backoff_max_s=0.6)
    health = device_health.get()
    items, want = tiled_items(16)  # 2 chunks of bucket 8

    def verify_and_record(name):
        t0 = time.monotonic()
        got = v.verify_batch(items)
        rec = {
            "bit_identical": bool((got == want).all()),
            "served": dict(v.served),
            "device_served": {str(k): n
                              for k, n in sorted(v.device_served.items())},
            "kernel_shapes": sorted(v._kernels),
            "coalesced_dispatches": v.coalesced_dispatches,
            "quarantined": health.quarantined(N_DEV),
            "host_only": bv.host_only_mode(),
            "audit_mismatches": v.audit_mismatches,
            "elapsed_s": round(time.monotonic() - t0, 2),
        }
        out["phases"][name] = rec
        print(f"# phase {name}: {rec}", file=sys.stderr, flush=True)
        return rec

    # warm every device's sub-chunk executable in parallel BEFORE the
    # phases (XLA compiles release the GIL; a 2-core host still halves
    # the wall time, and the persistent cache makes reruns ~free)
    t0 = time.monotonic()
    kern = v._kernel_for(SUB)
    rows = [np.repeat(x, SUB, 0) for x in
            (bv._PAD_A, bv._PAD_R, bv._PAD_S, bv._PAD_H)]
    # the hot-signer phases (6-7) dispatch the cached-table kernel
    # variant — warm it here too, or its first compile lands inside a
    # phase and blows the 30s dispatch deadline
    hkern = v._kernel_for(SUB, plugin=v._hot)
    hrows = [np.repeat(x, SUB, 0) for x in v._hot.pad_rows()]

    def warm(d):
        np.asarray(kern(*[jax.device_put(x, d) for x in rows]))
        np.asarray(hkern(*[jax.device_put(x, d) for x in hrows]))

    threads = [threading.Thread(target=warm, args=(d,)) for d in devs]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    out["warm_s"] = round(time.monotonic() - t0, 1)
    print(f"# warm-up: {out['warm_s']}s", file=sys.stderr, flush=True)

    # ---- phase 0: healthy baseline — all 4 devices serve ----
    verify_and_record("baseline")

    # ---- phase 1: device 1 dies mid-run (dispatch raises) ----
    faults.set_fault(faults.DISPATCH, "fail-device", 1)
    verify_and_record("fail_device_1")

    # ---- phase 2: degraded steady state — re-shard over survivors,
    # no new kernel shapes, no host fallback ----
    served_before = dict(v.served)
    verify_and_record("degraded")
    out["phases"]["degraded"]["host_fallback_delta"] = \
        v.served["host-fallback"] - served_before["host-fallback"]
    out["phases"]["degraded"]["device_delta"] = \
        v.served["device"] - served_before["device"]

    # ---- phase 3: device 1 heals — half-open probe regrows it ----
    faults.clear()
    time.sleep(0.8)  # past the 0.3s (+jitter, doubled once at most) backoff
    dev1_before = v.device_served.get(1, 0)
    # two rounds: the first carries the half-open probe sub-chunk that
    # re-closes the breaker; the second runs the full healthy rotation
    v.verify_batch(items)
    verify_and_record("healed")
    out["phases"]["healed"]["dev1_delta"] = \
        v.device_served.get(1, 0) - dev1_before

    # ---- phase 4: device 2 silently corrupts verdict bits ----
    faults.set_fault(faults.RESOLVE, "corrupt-device", 2)
    verify_and_record("corrupt_device_2")
    out["phases"]["corrupt_device_2"]["device2_state"] = \
        health.breaker(2).state

    # ---- phase 5: host-only steady state ----
    faults.clear()
    served_before = dict(v.served)
    verify_and_record("host_only_steady")
    out["phases"]["host_only_steady"]["device_delta"] = \
        v.served["device"] - served_before["device"]

    out["dispatch_health"] = {
        k: bv.dispatch_health()[k]
        for k in ("host_only", "audit", "device_health")}
    out["breaker_history"] = health.history()

    # ---- phases 6-7: hot-signer table cache vs audit conviction
    # (ISSUE 16). Fresh dispatch story — the quarantine/host-only arc
    # above already captured its records, and a conviction is only
    # reachable while devices still serve. One signer repeated across
    # the whole bucket: its cached table serves every row, so the
    # corrupt-device conviction MUST evict that exact entry (the table
    # is re-derived from the pubkey on next sight — a convicted chip
    # may have returned us poisoned residency, so nothing it served
    # stays trusted).
    bv._reset_dispatch_state_for_testing()
    bv.configure_dispatch(deadline_ms=30_000, dispatch_retries=0,
                          failure_threshold=3, audit_rate=1.0,
                          device_failure_threshold=2,
                          device_backoff_min_s=0.3,
                          device_backoff_max_s=0.6)
    health = device_health.get()
    import secrets
    hseed = secrets.token_bytes(32)
    hpk = ref.secret_to_public(hseed)
    items = [(hpk, b"hot-%d" % i, ref.sign(hseed, b"hot-%d" % i))
             for i in range(BUCKET)]
    want = np.array([ref.verify(p, m, s) for p, m, s in items])

    def cache_snap():
        return bv.dispatch_health()["signer_tables"]

    # serve pass 1 installs the table (the first occurrence rides the
    # cold kernel); pass 2 is the recorded all-hot steady state
    v.verify_batch(items)
    verify_and_record("hot_signer_serve")
    out["phases"]["hot_signer_serve"]["signer_tables"] = cache_snap()

    faults.set_fault(faults.RESOLVE, "corrupt-device", 2)
    verify_and_record("hot_signer_audit_evict")
    out["phases"]["hot_signer_audit_evict"]["signer_tables"] = \
        cache_snap()
    faults.clear()
    print(json.dumps(out, default=str))


if __name__ == "__main__":
    main()
