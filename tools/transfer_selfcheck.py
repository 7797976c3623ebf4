#!/usr/bin/env python
"""TRANSFER_LEDGER_OK self-check (run by ``tools/tier1.sh``).

Proves the transfer ledger end-to-end on a forced-4-device CHAOS
resolve — CPU backend, the SHA-256 engine workload (its scan-based
kernel compiles in seconds, against the shared persistent cache), with
``flaky-device:0`` armed so the recorded window includes real
fault-domain traffic (failed dispatches, host fallback) and not just
the happy path. Three phases:

1. **detector**: two resolves of the SAME batch must yield nonzero
   ``round_trips`` AND nonzero ``redundant_constant_bytes`` — the
   redundancy instrument convicts re-uploads;
2. **steady state** (the chaos window): resolves of FRESH batches, as
   every benchmark cell sends. Their message words are new bytes, so
   at most a minority of the shipped bytes may be convicted as
   re-uploads (the small per-row ``active`` masks of same-length
   messages repeat);
3. the ledger's byte totals must RECONCILE (>= MIN_RECONCILE both
   directions) against the engine's own independent shape-derived
   accounting of what it shipped and fetched, so a placement path
   that forgets its ledger hook shows up as a byte gap; the
   ``crypto.transfer.*`` counters must ride the Prometheus
   exposition; and digests stay bit-identical to hashlib through the
   flap.

The TOP-LEVEL fields are the steady-state window; the ``detector``
block carries the conviction evidence. Prints one JSON line; exit 0 =
every check passed.
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N_DEV = 4
BUCKET = 8
MIN_RECONCILE = 0.95


def _env_setup() -> None:
    """CPU-only multi-device env — must run before jax imports (same
    shapes + persistent cache as the device-domain chaos driver)."""
    from stellar_tpu.utils.jax_setup import enable_compile_cache, force_cpu
    force_cpu(N_DEV)
    enable_compile_cache()


def _corpus(n: int, salt: int = 0):
    return [bytes(((7 * j + k + 31 * salt) % 256)
                  for k in range(40 + 13 * j))
            for j in range(n)]


def _ratio(a: int, b: int):
    if max(a, b) == 0:
        return None
    return min(a, b) / max(a, b)


def run() -> dict:
    import hashlib

    from stellar_tpu.crypto import batch_hasher as bh
    from stellar_tpu.crypto import batch_verifier as bv
    from stellar_tpu.parallel.mesh import batch_mesh
    from stellar_tpu.utils import faults
    from stellar_tpu.utils.metrics import registry
    from stellar_tpu.utils.transfer_ledger import transfer_ledger

    import jax

    devs = jax.devices()
    if len(devs) < 2:
        raise SystemExit(
            f"self-check needs a multi-device host (got {len(devs)}): "
            "run with XLA_FLAGS=--xla_force_host_platform_device_"
            "count=4")
    h = bh.BatchHasher(mesh=batch_mesh(), bucket_sizes=(BUCKET,))
    bv.configure_dispatch(
        deadline_ms=30_000, dispatch_retries=0,
        failure_threshold=8, backoff_min_s=0.3, backoff_max_s=0.6,
        audit_rate=0.25, device_failure_threshold=2,
        device_backoff_min_s=0.2, device_backoff_max_s=0.5)
    msgs = _corpus(BUCKET)
    want = [hashlib.sha256(m).digest() for m in msgs]

    def mismatched(batch, expect):
        return sum(1 for g, w in zip(h.hash_batch(batch), expect)
                   if g != w)

    # warm compile (clean)
    mismatches = mismatched(msgs, want)

    # ---- phase 1: detector — the same batch again must be convicted
    # as a re-upload ----
    det_before = transfer_ledger.totals()
    for _ in range(2):
        mismatches += mismatched(msgs, want)
    det_after = transfer_ledger.totals()
    detector = {k: det_after[k] - det_before[k]
                for k in ("round_trips", "bytes_h2d",
                          "redundant_constant_bytes",
                          "redundant_uploads")}
    detector["redundancy_frac"] = round(
        detector["redundant_constant_bytes"]
        / max(1, detector["bytes_h2d"]), 4)

    # ---- phase 2: steady state — fresh batches under the flap ----
    fresh = [_corpus(BUCKET, salt) for salt in (1, 2)]
    before = transfer_ledger.totals()
    faults.set_fault(faults.DISPATCH, "flaky-device", 0)
    try:
        for batch in fresh:
            mismatches += mismatched(
                batch, [hashlib.sha256(m).digest() for m in batch])
    finally:
        fault_counters = faults.counters()
        faults.clear()
    after = transfer_ledger.totals()
    with h._stats_lock:
        shipped1, fetched1 = h.shipped_bytes, h.fetched_bytes

    delta = {k: after[k] - before[k]
             for k in ("round_trips", "bytes_h2d", "bytes_d2h",
                       "device_puts", "fetches",
                       "redundant_constant_bytes",
                       "redundant_uploads")}
    # reconciliation: ledger totals vs the engine's OWN shape-derived
    # accounting, over the whole run (warm + detector + steady phases
    # on both sides)
    rec_h2d = _ratio(after["bytes_h2d"], shipped1)
    rec_d2h = _ratio(after["bytes_d2h"], fetched1)
    reconciliation = min(x for x in (rec_h2d, rec_d2h)
                         if x is not None) \
        if (rec_h2d or rec_d2h) else None
    prom = registry.to_prometheus()

    problems = []
    if mismatches:
        problems.append(f"{mismatches} digests mismatched hashlib "
                        "under the flap")
    if detector["redundant_constant_bytes"] == 0:
        problems.append("re-shipping the same batch recorded zero "
                        "redundant constant bytes — the redundancy "
                        "detector has rotted")
    if detector["round_trips"] == 0:
        problems.append("detector window recorded zero round trips")
    if delta["round_trips"] == 0:
        problems.append("chaos window recorded zero round trips")
    if 2 * delta["redundant_constant_bytes"] >= delta["bytes_h2d"]:
        problems.append(
            "fresh batches convicted as re-uploads: "
            f"{delta['redundant_constant_bytes']} of "
            f"{delta['bytes_h2d']} h2d bytes")
    if delta["bytes_d2h"] == 0:
        problems.append(f"d2h byte accounting empty: {delta}")
    if reconciliation is None or reconciliation < MIN_RECONCILE:
        problems.append(
            f"ledger/engine byte reconciliation {reconciliation} < "
            f"{MIN_RECONCILE} (ledger h2d={after['bytes_h2d']} vs "
            f"engine {shipped1}; d2h={after['bytes_d2h']} vs "
            f"{fetched1})")
    if not fault_counters.get("device.dispatch", {}).get("fired"):
        problems.append("flaky-device:0 never fired — not a chaos "
                        "window")
    if "crypto_transfer_bytes_h2d" not in prom or \
            "crypto_transfer_redundant_constant_bytes" not in prom:
        problems.append("transfer counters missing from the "
                        "Prometheus exposition")
    per_resolve = transfer_ledger.recent(2)
    if not per_resolve:
        problems.append("no per-resolve ledger records")

    return {
        "ok": not problems,
        "devices": len(devs),
        "bucket": BUCKET,
        # steady-state window — the gated trajectory
        "round_trips": delta["round_trips"],
        "bytes_h2d": delta["bytes_h2d"],
        "bytes_d2h": delta["bytes_d2h"],
        "device_puts": delta["device_puts"],
        "fetches": delta["fetches"],
        "redundant_constant_bytes": delta["redundant_constant_bytes"],
        "redundant_uploads": delta["redundant_uploads"],
        "reconciliation": round(reconciliation, 4)
        if reconciliation is not None else None,
        # scale-free redundancy fraction of the steady-state window
        "redundancy_frac": round(
            delta["redundant_constant_bytes"] /
            max(1, delta["bytes_h2d"]), 4)
        if delta["bytes_h2d"] else 0.0,
        "engine_shipped_bytes": shipped1,
        "engine_fetched_bytes": fetched1,
        # conviction evidence: the detector still works
        "detector": detector,
        "last_resolves": per_resolve,
        "workload": "sha256",
        "chaos": "flaky-device:0",
        "problems": problems,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", action="store_true",
                    help="(default) print one JSON line")
    args = ap.parse_args()  # noqa: F841 — flag kept for symmetry
    _env_setup()
    rec = run()
    print(json.dumps(rec))
    return 0 if rec["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
