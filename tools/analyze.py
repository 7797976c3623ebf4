"""Static analysis gate: overflow prover, hot-path/lock/nondet lints,
the whole-program lock-order prover, and the kernel proof-coverage
gate.

Runs the full ``stellar_tpu.analysis`` suite and exits nonzero on ANY
open finding — wired into ``tools/tier1.sh`` after the pytest gate so
every kernel or dispatch PR is checked, and into ``bench.py`` so a
bench record carries the proof's pass/fail + envelope hash.

  python tools/analyze.py                  # pretty report, full sweep
  python tools/analyze.py --json           # one JSON line (CI / bench)
  python tools/analyze.py --buckets=128,2048
  python tools/analyze.py --lint-only      # AST lints only (fast)
  python tools/analyze.py --overflow-only  # interval prover only
  python tools/analyze.py --write-golden   # refresh docs/limb_bounds.json
                                           # (a DELIBERATE act: the diff
                                           # is the proof change)

The overflow prover traces the verify kernel's three stages at every
jit bucket size (``stellar_tpu.analysis.overflow.DEFAULT_BUCKETS``) and
proves every integer intermediate fits its dtype with the loose-limb
headroom of ``docs/kernel_design.md`` §1; the proven per-stage envelope
must match the committed golden ``docs/limb_bounds.json``. How to read
a failure: ``docs/static_analysis.md``.
"""

from __future__ import annotations

import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _force_cpu():
    """Pin jax to CPU before any backend initializes: the proofs trace
    jaxprs and need no chip."""
    from stellar_tpu.utils.jax_setup import force_cpu
    force_cpu()


def run_lints() -> dict:
    from stellar_tpu.analysis import hotpath, lockorder, locks, nondet
    return {rep.name: rep.to_dict()
            for rep in (hotpath.run(), locks.run(), nondet.run(),
                        lockorder.run())}


def run_proof_coverage() -> dict:
    """Kernel proof-coverage gate: every registered Workload variant
    must map to a proven envelope stage in a committed golden."""
    _force_cpu()  # enumerating kernels imports the engine (jax)
    from stellar_tpu.analysis import coverage
    return coverage.run()


def _check_golden(rec: dict, golden, path: str) -> dict:
    from stellar_tpu.analysis import overflow
    if golden is None:
        rec["golden"] = "missing"
        rec["golden_diff"] = [
            f"{path} not committed — run "
            "tools/analyze.py --write-golden and review the envelope"]
        rec["ok"] = False
    else:
        diff = overflow.diff_golden(rec["envelope"], golden)
        rec["golden"] = "match" if not diff else "MISMATCH"
        rec["golden_diff"] = diff
        rec["ok"] = rec["ok"] and not diff
    return rec


def run_overflow(buckets) -> dict:
    _force_cpu()
    from stellar_tpu.analysis import overflow
    rec = overflow.prove_buckets(buckets)
    return _check_golden(rec, overflow.load_golden(_REPO),
                         overflow.GOLDEN_PATH)


def run_overflow_sha256(buckets=None) -> dict:
    """Prove the SHA-256 workload kernel — separate golden, so the
    ed25519 envelope (docs/limb_bounds.json) diffs independently."""
    _force_cpu()
    from stellar_tpu.analysis import overflow
    rec = overflow.prove_sha256_buckets(buckets)
    return _check_golden(rec, overflow.load_sha_golden(_REPO),
                         overflow.SHA_GOLDEN_PATH)


def main(argv) -> int:
    as_json = "--json" in argv
    lint_only = "--lint-only" in argv
    overflow_only = "--overflow-only" in argv
    write_golden = "--write-golden" in argv
    from stellar_tpu.analysis.overflow import (
        DEFAULT_BUCKETS, GOLDEN_PATH, SHA_GOLDEN_PATH)
    buckets = list(DEFAULT_BUCKETS)
    sha_buckets = None  # batch_hasher.DEFAULT_HASH_BUCKET_SIZES
    for a in argv:
        if a.startswith("--buckets="):
            buckets = [int(b) for b in a.split("=", 1)[1].split(",")]
            sha_buckets = buckets

    def _maybe_write_golden(rec, path):
        if not write_golden:
            return rec
        with open(os.path.join(_REPO, path), "w") as f:
            json.dump(rec["envelope"], f, indent=1, sort_keys=True)
            f.write("\n")
        rec["golden"] = "written"
        rec["golden_diff"] = []
        rec["ok"] = (not rec["violations"]
                     and not rec["contract_breaches"]
                     and not rec["unsupported"]
                     and not rec["envelope_mismatch_buckets"])
        return rec

    out = {"ok": True}
    if not overflow_only:
        lints = run_lints()
        out["lints"] = lints
        out["ok"] &= all(rep["ok"] for rep in lints.values())
    if not lint_only and not overflow_only:
        cov = run_proof_coverage()
        out["proof_coverage"] = cov
        out["ok"] &= cov["ok"]
    if not lint_only:
        for key, rec, path in (
                ("overflow", run_overflow(buckets), GOLDEN_PATH),
                ("overflow_sha256", run_overflow_sha256(sha_buckets),
                 SHA_GOLDEN_PATH)):
            rec = _maybe_write_golden(rec, path)
            # the full envelope rides the golden file, not every record
            out[key] = {k: v for k, v in rec.items() if k != "envelope"}
            out["ok"] &= rec["ok"]

    if as_json:
        print(json.dumps(out, default=str))
    else:
        _pretty(out)
    return 0 if out["ok"] else 1


def _pretty(out: dict) -> None:
    for name, rep in out.get("lints", {}).items():
        status = "ok" if rep["ok"] else "FAIL"
        print(f"[{status}] lint:{name}  files={rep['files_scanned']} "
              f"open={len(rep['findings'])} "
              f"allowlisted={len(rep['allowlisted'])} "
              f"stale={len(rep['stale_allowlist'])}")
        for f in rep["findings"]:
            print(f"    {f['file']}:{f['line']}: [{f['key']}] "
                  f"{f['message']}")
        for e in rep["stale_allowlist"]:
            print(f"    stale allowlist entry (delete it): {e}")
    for key in ("overflow", "overflow_sha256"):
        ov = out.get(key)
        if not ov:
            continue
        status = "ok" if ov["ok"] else "FAIL"
        print(f"[{status}] {key}  buckets={ov.get('buckets')} "
              f"violations={len(ov['violations'])} "
              f"contract={len(ov['contract_breaches'])} "
              f"golden={ov.get('golden')}")
        for v in ov["violations"][:20]:
            print(f"    {v['path']}[{v['eqn_index']}] {v['primitive']} "
                  f"-> [{v['lo']}, {v['hi']}] escapes {v['dtype']} at "
                  f"{v['where']}")
        for c in ov["contract_breaches"][:20]:
            print(f"    {c}")
        for u in ov["unsupported"][:20]:
            print(f"    unsupported: {u}")
        for d in ov.get("golden_diff", [])[:20]:
            print(f"    golden: {d}")
        print(f"    envelope_sha256={ov.get('envelope_sha256')}")
    cov = out.get("proof_coverage")
    if cov:
        status = "ok" if cov["ok"] else "FAIL"
        print(f"[{status}] proof-coverage  "
              f"kernels={cov['files_scanned']} "
              f"proven={cov['proven']} open={len(cov['findings'])} "
              f"stale={len(cov['stale_allowlist'])}")
        for f in cov["findings"]:
            print(f"    {f['file']}: [{f['key']}] {f['message']}")
        for e in cov["stale_allowlist"]:
            print(f"    stale allowlist entry (delete it): {e}")
    # machine-readable gate lines for the tier-1 harness: open
    # lock-order/hold-and-block findings and proven kernel count
    lo = out.get("lints", {}).get("lockorder")
    if lo:
        print(f"LOCKORDER_OK={len(lo['findings']) + len(lo['stale_allowlist'])}")
    if cov:
        print(f"PROOF_COVERAGE_OK={cov['proven'] if cov['ok'] else 0}")
    print("ANALYSIS_OK" if out["ok"] else "ANALYSIS_FAIL")


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
