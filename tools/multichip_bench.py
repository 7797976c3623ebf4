#!/usr/bin/env python
"""Multi-chip verify capture WITH fault-domain evidence (ISSUE 5
satellite, closing the PR 4 ROADMAP item: "surface per-device health
in multi-chip capture runs").

Runs the production per-device dispatch path (``BatchVerifier`` over
the auto mesh — one attributable sub-chunk dispatch per chip) and
prints ONE JSON line that a multi-chip record can embed verbatim. Alongside the p50 it carries everything needed to judge
whether the number is HONEST:

- ``fault_domain``: per-device breaker states, quarantine onsets,
  audit verdicts and re-shard history from
  ``stellar_tpu.parallel.device_health`` — a mid-run chip death or a
  corrupting chip can no longer hide inside a multi-chip aggregate;
- ``per_device_served``: items served per chip (a chip serving zero
  items means the "multi-chip" number wasn't);
- ``dispatch_attribution``: per-phase span breakdown of the measured
  reps (docs/observability.md);
- ``verify_backend``: the served-count attribution bench.py uses — a
  silent host fallback can't claim a device number.

On a 4-chip host: ``chiprun --chips 4 -- python tools/multichip_bench.py``.
For a CPU rehearsal: ``python tools/multichip_bench.py --force-cpu-devices
4 --sigs 64`` (each sub-chunk shape pays an XLA CPU compile — keep
sigs small).
"""
import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def fault_domain_evidence(verifier=None) -> dict:
    """The per-device health payload a MULTICHIP record carries:
    breaker states + audit tallies (snapshot), quarantine onsets and
    re-shard-relevant transitions (history), per-device served counts,
    and the host-only posture. Safe to call with no verifier (probe
    tooling) — served counts are then omitted."""
    from stellar_tpu.crypto import batch_verifier
    from stellar_tpu.parallel import device_health
    dh = device_health.get()
    hist = dh.history()
    out = {
        "device_health": dh.snapshot(),
        "quarantine_onsets": [
            h for h in hist
            if h.get("event") == "quarantine" or h.get("to") == "open"],
        "audit_mismatch_events": [
            h for h in hist if h.get("event") == "audit-mismatch"],
        "history_tail": hist[-64:],
        "host_only": batch_verifier.host_only_mode(),
    }
    if verifier is not None:
        with verifier._stats_lock:
            out["per_device_served"] = {
                str(k): v for k, v in
                sorted(verifier.device_served.items())}
            out["served"] = dict(verifier.served)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sigs", type=int, default=2048)
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--force-cpu-devices", type=int, default=0,
                    help="rehearsal: N-way virtual CPU mesh")
    args = ap.parse_args()
    from stellar_tpu.utils.jax_setup import enable_compile_cache, force_cpu
    if args.force_cpu_devices:
        force_cpu(args.force_cpu_devices)
    enable_compile_cache()

    import numpy as np

    from bench import gen_sigs
    from stellar_tpu.crypto import batch_verifier
    from stellar_tpu.crypto.batch_verifier import (
        BatchVerifier, _auto_mesh,
    )
    from stellar_tpu.utils import tracing

    mesh = _auto_mesh()
    n_devices = 1 if mesh is None else mesh.size
    items = gen_sigs(args.sigs)
    v = BatchVerifier(mesh=mesh, bucket_sizes=(args.sigs,))

    platform = "unknown"
    try:
        import jax
        platform = jax.devices()[0].platform
    except Exception:
        pass

    # warm/compile (per-device sub-chunk executables)
    for _ in range(2):
        out = v.verify_batch(items)
    assert out.all(), "capture signatures must verify"

    from bench import _phase_backend
    served_before = batch_verifier.served_counts()
    spans_before = tracing.span_totals()
    times = []
    for _ in range(args.reps):
        t0 = time.perf_counter()
        out = v.verify_batch(items)
        times.append((time.perf_counter() - t0) * 1000.0)
    assert out.all()
    attribution = batch_verifier.dispatch_attribution(
        spans_before, tracing.span_totals(), reps=args.reps)
    p50 = float(np.median(times))
    attribution["headline_p50_ms"] = round(p50, 3)

    rec = {
        "metric": "multichip_txset_sigverify_p50_ms",
        "value": round(p50, 3),
        "unit": "ms",
        "n_sigs": args.sigs,
        "reps": args.reps,
        "n_devices": n_devices,
        "platform": platform,
        "forced_cpu_mesh": bool(args.force_cpu_devices),
        "verify_backend": _phase_backend(
            served_before, batch_verifier.served_counts(), platform),
        "dispatch_attribution": attribution,
        "fault_domain": fault_domain_evidence(v),
        "dispatch_health": batch_verifier.dispatch_health(),
    }
    print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
