#!/usr/bin/env python
"""METRICS_EXPORT_OK self-check (run by ``tools/tier1.sh`` after the
static-analysis gate; ISSUE 5).

Proves the observability surface end-to-end on a SYNTHETIC resolve —
no device, no jax dispatch, seconds of wall time:

1. flips the dispatch layer host-only and runs one real
   span-instrumented ``BatchVerifier.verify_batch`` (the exact
   production code path minus the device phases);
2. asserts the per-phase span sum reconciles to >= MIN_COVERAGE of the
   blocking root span, with every phase of
   ``batch_verifier.RESOLVE_PHASES`` present in the breakdown
   (zero-count device phases included — the host-only completeness
   guarantee);
3. renders the registry's Prometheus text exposition and parses every
   sample line back, requiring the span histograms to be present.

Exit 0 = exportable; anything else fails the tier-1 gate. Prints one
JSON line either way.
"""
import json
import os
import re
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

MIN_COVERAGE = 0.95
N_SIGS = 64

# one exposition sample: name, optional {labels}, numeric value
_PROM_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\]|\\.)*\""
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\]|\\.)*\")*\})?"
    r" [-+]?([0-9]*\.?[0-9]+([eE][-+]?[0-9]+)?|inf|nan)$")


def synthetic_resolve():
    from stellar_tpu.crypto import batch_verifier as bv
    from stellar_tpu.crypto import ed25519_ref as ref
    from stellar_tpu.utils import tracing

    # host-only: the span/histogram path is identical to a live
    # resolve minus the device phases, and nothing can hang
    bv._enter_host_only("metrics self-check: synthetic resolve")
    pool = []
    for i in range(8):
        seed = bytes([i + 1]) * 32
        pk = ref.secret_to_public(seed)
        msg = b"metrics-selfcheck-%d" % i
        pool.append((pk, msg, ref.sign(seed, msg)))
    items = [pool[i % len(pool)] for i in range(N_SIGS)]
    v = bv.BatchVerifier(bucket_sizes=(N_SIGS,))
    before = tracing.span_totals()
    t0 = time.perf_counter()
    out = v.verify_batch(items)
    wall_ms = (time.perf_counter() - t0) * 1000.0
    assert out.all(), "self-check signatures must verify"
    att = bv.dispatch_attribution(before, tracing.span_totals(),
                                  reps=1)
    return att, wall_ms


def check_attribution(att) -> list:
    from stellar_tpu.crypto import batch_verifier as bv
    problems = []
    missing = [p for p in bv.RESOLVE_PHASES
               if p not in att.get("phases", {})]
    if missing:
        problems.append(f"phases missing from breakdown: {missing}")
    cov = att.get("coverage")
    if cov is None or cov < MIN_COVERAGE:
        problems.append(
            f"span sum covers {cov} of the blocking root span "
            f"(need >= {MIN_COVERAGE})")
    if att.get("blocking_span_count") != 1:
        problems.append("blocking root span did not record exactly "
                        f"once: {att.get('blocking_span_count')}")
    return problems


def check_prometheus() -> tuple:
    from stellar_tpu.crypto import batch_verifier as bv
    from stellar_tpu.utils.metrics import _prom_name, registry
    text = registry.to_prometheus()
    problems = []
    bad = [ln for ln in text.splitlines()
           if ln and not ln.startswith("#")
           and not _PROM_SAMPLE.match(ln)]
    if bad:
        problems.append(f"unparseable exposition lines: {bad[:5]}")
    for phase in bv.RESOLVE_PHASES + (bv.RESOLVE_ROOT,):
        base = _prom_name(f"span.{phase}")
        # zero-count phases legitimately have no timer yet; the root
        # and the phases the synthetic resolve exercised must export
        # verify.bucket (padding build) only runs for dispatch-bound
        # chunks, so the host-only synthetic resolve never records it
        if f"{base}_ms_count" not in text and phase in (
                bv.RESOLVE_ROOT, "verify.prep",
                "verify.host_fallback"):
            problems.append(f"span histogram {base} missing from "
                            "exposition")
    return len(text.splitlines()), problems


def main() -> int:
    att, wall_ms = synthetic_resolve()
    problems = check_attribution(att)
    prom_lines, prom_problems = check_prometheus()
    problems += prom_problems
    print(json.dumps({
        "ok": not problems,
        "coverage": att.get("coverage"),
        "blocking_wall_ms": round(wall_ms, 3),
        "span_sum_per_rep_ms": att.get("span_sum_per_rep_ms"),
        "prometheus_lines": prom_lines,
        "problems": problems,
    }))
    return 0 if not problems else 1


if __name__ == "__main__":
    sys.exit(main())
