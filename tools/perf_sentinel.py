#!/usr/bin/env python
"""Perf-drift sentinel (ISSUE 8): diff the last two bench records
against TYPED tolerance rules, so ``BENCH_*.json`` drift can never
again pass silently (the bench trajectory list was empty precisely
because nothing consumed it).

A bench record is the wrapper the driver commits ({"n", "cmd", "rc",
"tail": "<one JSON line>"}) or the bare bench.py line; both parse. The
sentinel compares the newest record (HEAD) against the previous one
(BASE) under the rule table below:

* ``max_increase_frac`` — HEAD may exceed BASE by at most ``tol``
  (kernel-cost ledgers, transfer redundancy, lane latencies: bigger is
  worse);
* ``max_decrease_frac`` — HEAD may fall short of BASE by at most
  ``tol`` (pipeline busy fraction: smaller is worse). Zero baselines
  are skipped and listed, like the increase rule;
* ``max_decrease_abs`` — HEAD must be >= BASE - ``tol`` (pipeline
  overlap fraction: an absolute min-delta, meaningful even off a 0.0
  baseline — the async dispatch loop's win must not silently erode);
* ``max_abs`` — HEAD must be <= ``tol``, no BASE needed (redundant
  constant re-upload bytes: the resident-table rework drove these to
  ~0, and a near-zero CEILING — not a growth ratio — is what keeps
  them there: a ratio rule off a ~0 baseline would either skip
  forever or fire on noise);
* ``min_value`` — HEAD must be at least ``tol`` (attribution coverage,
  transfer/pipeline reconciliation: the record's own quality gates);
* ``require_true`` — HEAD must carry a truthy value (analysis proof
  state: a bench number from an unproven kernel is not quotable);
* ``note_change`` — reported when BASE != HEAD, never fatal (the
  proven-envelope hash changes on DELIBERATE kernel work; the sentinel
  flags it for review instead of blocking the gate forever).

A rule whose path is missing from the relevant record(s) is SKIPPED
and listed — older records legitimately lack newer fields. The ``kernel_cost.*`` family is additionally VERSION-SCOPED:
when the two records carry different ``kernel_cost.ledger_version``
values (a deliberate window-scheme rework, bumped in
tools/kernel_cost.py beside the docs/kernel_design.md §3 ledger), the
family is re-baselined — skipped with a note — instead of trended
across incomparable cost shapes; the next same-version pair resumes
enforcement. Exit 0 = no fatal drift; anything else fails the tier-1 gate
(``PERF_DRIFT_OK``). ``docs/observability.md`` "Perf sentinel" carries
the same table.

Usage:
    python tools/perf_sentinel.py                    # last two BENCH_r*.json
    python tools/perf_sentinel.py --records A B      # explicit pair
"""

import argparse
import glob
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ---------------- the typed tolerance rules ----------------
# (path, type, tol, why) — path walks dotted keys through the record.
RULES = [
    # kernel-cost ledgers: the hardware-independent perf trajectory.
    # Static op counts are deterministic, so even 2% growth means the
    # kernel got WORSE without anyone saying so.
    ("kernel_cost.dsm_static_mul_ops", "max_increase_frac", 0.02,
     "traced dsm multiply ops regressed"),
    ("kernel_cost.kernel_static_mul_ops", "max_increase_frac", 0.02,
     "traced kernel-total multiply ops regressed"),
    ("kernel_cost.dsm_weighted_mul_elems", "max_increase_frac", 0.02,
     "executed dsm MAC volume regressed"),
    ("kernel_cost.select_macs_per_verify", "max_increase_frac", 0.02,
     "window-select MAC volume regressed"),
    # PR 13 batched-affine rows: the executed-MAC headline under its
    # enforced ledger name, and the affine-table build + Montgomery
    # batch-inversion chains — if batch_inv decays toward per-lane
    # inversions (~2.5x these elems), this is where it surfaces.
    ("kernel_cost.dsm.executed_macs_per_call", "max_increase_frac",
     0.02, "executed dsm MACs/call regressed (the PR 13 win eroding)"),
    ("kernel_cost.affine_table.build_weighted_mul_elems",
     "max_increase_frac", 0.02,
     "affine A-table build volume regressed"),
    ("kernel_cost.affine_table.batch_inv_weighted_mul_elems",
     "max_increase_frac", 0.02,
     "Montgomery batch-inversion chain volume regressed"),
    # PR 16 hot-signer rows (ledger v3): the cached-table radix-256
    # arm's executed volume, and the hot/cold ratio itself — the
    # acceptance quantity (<= 0.80) must not creep back toward parity.
    ("kernel_cost.dsm.hot.executed_macs_per_call",
     "max_increase_frac", 0.02,
     "hot-signer executed dsm MACs/call regressed (the PR 16 win "
     "eroding)"),
    ("kernel_cost.dsm.hot.vs_cold_frac", "max_abs", 0.80,
     "hot-signer dsm must stay >= 20% below cold (ISSUE 16 "
     "acceptance)"),
    ("kernel_cost.signer_table.bytes_per_signer",
     "max_increase_frac", 0.0,
     "per-signer table bytes changed — cache budgets and the "
     "table-stack sizing assume 15 KiB/signer"),
    ("kernel_cost.sha256.weighted_ops", "max_increase_frac", 0.02,
     "sha256 weighted op volume regressed"),
    # analysis envelope: proof state must hold; the envelope HASH may
    # change deliberately (--write-golden) — flagged, not fatal.
    ("analysis.ok", "require_true", None,
     "static-analysis gate not green in the measured record"),
    ("analysis.overflow_proven", "require_true", None,
     "verify kernel not proven overflow-free in the measured record"),
    ("analysis.sha256_overflow_proven", "require_true", None,
     "sha256 kernel not proven overflow-free in the measured record"),
    ("analysis.lints_ok", "require_true", None,
     "lint findings open in the measured record"),
    # ISSUE 18 concurrency + coverage gates: the dispatch tier the
    # bench number rode must be deadlock-clean, and every kernel
    # variant it could have dispatched must carry an overflow proof.
    ("analysis.lockorder_ok", "require_true", None,
     "lock-order / hold-and-block findings open in the measured "
     "record"),
    ("analysis.proof_coverage_ok", "require_true", None,
     "an engine kernel variant without a proven overflow envelope "
     "in the measured record"),
    ("analysis.envelope_sha256", "note_change", None,
     "proven limb envelope changed (deliberate? review the golden)"),
    ("analysis.sha256_envelope", "note_change", None,
     "proven sha256 envelope changed (deliberate? review the golden)"),
    # attribution coverage: the breakdown must keep explaining the
    # headline, or the next dispatch-floor claim is unattributed.
    ("dispatch_attribution.coverage", "min_value", 0.95,
     "per-phase span sum no longer reconciles the blocking root"),
    # transfer ledger: the dispatch-floor quantities. Reconciliation
    # is the record's own self-check; redundancy growth means MORE
    # constant re-uploads than the last record — the exact regression
    # the resident-tables work must drive to zero.
    ("transfer_ledger.reconciliation", "min_value", 0.95,
     "transfer ledger no longer reconciles engine byte accounting"),
    ("transfer_ledger.round_trips", "min_value", 1,
     "transfer probe recorded no device round trips"),
    # scale-free: redundant bytes / shipped bytes — comparable across
    # probe-sized and live-sized windows, unlike absolute byte counts
    ("transfer_ledger.redundancy_frac", "max_increase_frac", 0.25,
     "redundant-constant re-upload FRACTION grew >25%"),
    # steady-state re-uploads must stay ~0 — an absolute near-zero
    # bound, because a growth ratio off a zero baseline would skip
    # forever and never catch a re-shipped constant. The 4 KiB
    # headroom tolerates a stray small operand, never a re-shipped
    # table.
    ("transfer_ledger.redundant_constant_bytes", "max_abs", 4096,
     "steady-state constant re-uploads regrew past the near-zero "
     "ceiling (identical operand bytes shipped again)"),
    # per-lane service latency (soak-captured): generous tolerance —
    # wall-clock percentiles across different hosts/windows are noisy;
    # only egregious drift (3x) fails.
    ("service.lane_latency_ms.scp.p50_ms", "max_increase_frac", 2.0,
     "scp lane p50 wait grew >3x"),
    ("service.lane_latency_ms.scp.p99_ms", "max_increase_frac", 2.0,
     "scp lane p99 wait grew >3x"),
    ("service.lane_latency_ms.auth.p99_ms", "max_increase_frac", 2.0,
     "auth lane p99 wait grew >3x"),
    ("service.lane_latency_ms.bulk.p99_ms", "max_increase_frac", 4.0,
     "bulk lane p99 wait grew >5x (the sheddable lane drifts widest)"),
    ("service.conservation_gap", "note_change", None,
     "service conservation gap changed (must stay 0)"),
    # closed-loop control (ISSUE 15): the scp latency burn captured in
    # a committed record is a HEAD-only ceiling — past 1.0 means the
    # consensus lane's error budget was burning faster than the
    # objective allows in the measured window, which is exactly the
    # regression the controller exists to prevent; the decision count
    # is note-only (closed-loop activity legitimately varies with the
    # window's load shape — flagged for review, never fatal).
    ("service.slo.scp.latency_burn_rate", "max_abs", 1.0,
     "scp latency burn rate past 1.0 in the measured window (the "
     "controller failed the objective it exists to keep)"),
    ("service.control.decisions", "note_change", None,
     "closed-loop controller decision count changed (expected to "
     "vary with load; review the control log if surprising)"),
    # replicated fleet (ISSUE 17): the fleet-level conservation
    # residual is a HARD zero — every item routed through the
    # FleetRouter lands in exactly one replica terminal (verified /
    # rejected / shed / failed / handoff) or the router's own refusal
    # counter, even through a mid-run replica kill; conviction counts
    # are note-only because Byzantine-injection scenarios
    # legitimately vary them between captures.
    ("fleet.conservation_gap", "max_abs", 0,
     "fleet conservation residual nonzero — the router lost or "
     "double-counted work across replicas"),
    ("fleet.divergence_convictions", "note_change", None,
     "fleet divergence conviction count changed (expected to vary "
     "with injected-fault scenarios; review the conviction log if "
     "surprising)"),
    # wire ingress (ISSUE 19): the WIRE-level conservation residual
    # is a HARD zero — every frame that crossed the socket lands in
    # decoded or malformed, every decoded item in accepted or
    # refused, and every accepted item in exactly one typed terminal
    # (resolved / shed / failed), even through torn frames, killed
    # connections and a mid-run server stop; malformed-frame counts
    # are note-only because chaos windows legitimately vary how many
    # frames the misbehaving flooder tears.
    ("ingress.conservation_gap", "max_abs", 0,
     "wire-ingress conservation residual nonzero — a frame or item "
     "was lost between the socket and a typed terminal"),
    ("ingress.malformed_frames", "note_change", None,
     "malformed wire-frame count changed (expected to vary with the "
     "armed wire fault shapes; review the ingress record if "
     "surprising)"),
    # unified system journal (ISSUE 20): the journal completeness
    # residual is a HARD zero — the merged journal's admissions and
    # terminals reconcile EXACTLY with the service/fleet/ingress
    # conservation counters and every admitted trace reaches exactly
    # one terminal over the retained window; and on a selfcheck
    # window every sampled verdict trace must stitch end-to-end
    # (seam-free through any handoff hops).
    ("journal.completeness_gap", "max_abs", 0,
     "journal completeness residual nonzero — the merged journal "
     "disagrees with the conservation counters or a trace carries "
     "the wrong number of terminals"),
    ("trace.stitch_frac", "min_value", 1.0,
     "a sampled verdict trace failed to reconstruct its stitched "
     "end-to-end timeline on a selfcheck window"),
    # pipeline-bubble profiler (ISSUE 10): the async-dispatch PR's
    # before/after numbers. busy_frac down = more device idle per
    # resolve; overlap_frac down = host prep stopped hiding behind
    # in-flight device work; reconciliation is the record's own
    # hook-coverage self-check.
    ("pipeline.busy_frac", "max_decrease_frac", 0.10,
     "device busy fraction regressed >10% (pipeline bubbles grew)"),
    ("pipeline.overlap_frac", "max_decrease_abs", 0.05,
     "host/device overlap fraction dropped (async-dispatch win "
     "eroding)"),
    ("pipeline.reconciliation", "min_value", 0.95,
     "pipeline timeline no longer reconciles resolve wall-clock"),
    # the headline itself, when both windows were live
    ("value", "max_increase_frac", 0.25,
     "blocking headline p50 regressed >25%"),
]


def load_record(path: str) -> dict:
    """Parse one bench artifact: the driver wrapper ({'tail': text})
    or a bare bench.py JSON line. A wrapper's tail may carry log noise
    (jax platform warnings) around the record — consumers read the
    LAST stdout line that parses, exactly as the driver does."""
    with open(path) as f:
        rec = json.load(f)
    if isinstance(rec, dict) and isinstance(rec.get("tail"), str):
        for line in reversed(rec["tail"].strip().splitlines()):
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                return json.loads(line)
            except ValueError:
                continue
        raise ValueError(f"no JSON record line in {path} tail")
    return rec


def walk(rec, path: str):
    """Dotted-path lookup; returns (found, value)."""
    cur = rec
    for part in path.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return False, None
        cur = cur[part]
    return True, cur


def apply_rules(base: dict, head: dict, rules=None) -> dict:
    rules = RULES if rules is None else rules
    findings = []
    notes = []
    skipped = []
    # A DELIBERATE kernel-cost rework (new window scheme, new ledger —
    # tools/kernel_cost.py bumps LEDGER_VERSION alongside the
    # docs/kernel_design.md §3 tables) re-baselines the whole
    # kernel_cost.* family: trending the new scheme against the old
    # one's numbers would either fail the gate forever (static ops
    # traded for executed volume) or silently bless regressions within
    # the new scheme. The version change itself is surfaced as a note;
    # the first same-version record pair resumes trend enforcement.
    _, bver = walk(base, "kernel_cost.ledger_version")
    _, hver = walk(head, "kernel_cost.ledger_version")
    ledger_rebased = bver != hver
    if ledger_rebased:
        notes.append({"path": "kernel_cost.ledger_version",
                      "base": bver, "head": hver,
                      "why": "kernel-cost ledger version changed — "
                             "family re-baselined (deliberate rework; "
                             "review docs/kernel_design.md §3)"})
    for path, kind, tol, why in rules:
        if ledger_rebased and path.startswith("kernel_cost."):
            skipped.append({"path": path,
                            "reason": "ledger-version-rebase"})
            continue
        b_found, b = walk(base, path)
        h_found, h = walk(head, path)
        if kind == "require_true":
            if not h_found:
                skipped.append({"path": path, "reason": "missing"})
            elif not h:
                findings.append({"path": path, "rule": kind,
                                 "head": h, "why": why})
            continue
        if kind == "min_value":
            if not h_found or h is None:
                skipped.append({"path": path, "reason": "missing"})
            elif not isinstance(h, (int, float)) or h < tol:
                findings.append({"path": path, "rule": kind,
                                 "head": h, "tol": tol, "why": why})
            continue
        if kind == "max_abs":
            # HEAD-only ceiling: meaningful with no baseline at all
            # (the quantity is pinned near zero, not trended)
            if not h_found or h is None:
                skipped.append({"path": path, "reason": "missing"})
            elif not isinstance(h, (int, float)) or h > tol:
                findings.append({"path": path, "rule": kind,
                                 "head": h, "tol": tol, "why": why})
            continue
        # two-record rules need BOTH sides
        if not b_found or not h_found or b is None or h is None:
            skipped.append({"path": path, "reason": "missing"})
            continue
        if kind == "note_change":
            if b != h:
                notes.append({"path": path, "base": b, "head": h,
                              "why": why})
            continue
        if kind in ("max_increase_frac", "max_decrease_frac"):
            if not isinstance(b, (int, float)) or \
                    not isinstance(h, (int, float)):
                skipped.append({"path": path, "reason": "non-numeric"})
                continue
            if b == 0:
                # a zero baseline has no meaningful growth ratio (an
                # idle lane in the base window would flag ANY traffic
                # in the next); the first nonzero record becomes the
                # baseline instead
                skipped.append({"path": path,
                                "reason": "zero-baseline"})
                continue
            if kind == "max_increase_frac":
                ceiling = b * (1.0 + tol) if b >= 0 else \
                    b * (1.0 - tol)
                drifted = h > ceiling + 1e-9
            else:
                floor = b * (1.0 - tol) if b >= 0 else b * (1.0 + tol)
                drifted = h < floor - 1e-9
            if drifted:
                findings.append({"path": path, "rule": kind,
                                 "base": b, "head": h, "tol": tol,
                                 "why": why})
            continue
        if kind == "max_decrease_abs":
            if not isinstance(b, (int, float)) or \
                    not isinstance(h, (int, float)):
                skipped.append({"path": path, "reason": "non-numeric"})
                continue
            if h < b - tol - 1e-9:
                findings.append({"path": path, "rule": kind,
                                 "base": b, "head": h, "tol": tol,
                                 "why": why})
            continue
        skipped.append({"path": path, "reason": f"unknown rule {kind}"})
    return {"ok": not findings, "findings": findings, "notes": notes,
            "skipped": skipped}


def _record_index(path: str):
    """Run counter extracted from BENCH_r<N>.json — NUMERIC ordering,
    so r100 sorts after r99 once the counter outgrows its zero
    padding (lexicographic sort would read that diff backwards)."""
    stem = os.path.basename(path)
    digits = "".join(c for c in stem if c.isdigit())
    return (int(digits) if digits else -1, stem)


def latest_records(root: str):
    paths = sorted(glob.glob(os.path.join(root, "BENCH_r*.json")),
                   key=_record_index)
    if len(paths) < 2:
        return None
    return paths[-2], paths[-1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--records", nargs=2, metavar=("BASE", "HEAD"),
                    help="explicit record pair (default: the last two "
                         "BENCH_r*.json in the repo root)")
    args = ap.parse_args()
    if args.records:
        base_path, head_path = args.records
    else:
        pair = latest_records(REPO)
        if pair is None:
            # a single-record repo has no trajectory to guard yet —
            # that is "nothing to diff", not drift
            print(json.dumps({"ok": True, "findings": [],
                              "notes": [],
                              "skipped": [{"reason":
                                           "fewer than 2 records"}]}))
            return 0
        base_path, head_path = pair
    try:
        base = load_record(base_path)
        head = load_record(head_path)
    except (OSError, ValueError) as e:
        print(json.dumps({"ok": False,
                          "findings": [{"path": "<load>",
                                        "why": repr(e)[:200]}]}))
        return 1
    out = apply_rules(base, head)
    out["base"] = os.path.basename(base_path)
    out["head"] = os.path.basename(head_path)
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
