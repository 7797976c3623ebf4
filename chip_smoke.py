#!/usr/bin/env python3
"""On-chip smoke test of the signature-verify path.

Drives the production path once on a TPU, through the entry points a
node uses, and fails unless the device decided every row:

1. ``device``  — ``jax.devices()`` must be a TPU; kind and count are
   printed. Any other platform fails: this script never runs on the
   CPU instead.
2. ``txset``   — a 2,048-signature tx-set-shaped batch (fresh keys from
   ``--seed``, seeded with invalid rows of every class libsodium
   rejects) through ``batch_verifier.default_verifier()``, the
   production wiring (``_auto_mesh()``, ``DEFAULT_BUCKET_SIZES``).
   Verdicts must equal ``ed25519_ref`` row for row.
3. ``txset-hot`` — the same batch again: every signer now has a cached
   table, so the rows ride the hot signer-table kernel. Same check.
4. ``node``    — ``apply-load --scenario multisig --ledgers 3 --txs
   1000 --verify device`` in-process through ``cli.main``: every
   transaction applies and the device serves at least one signature
   per transaction.

Around every phase the dispatch layer's accounting is read before and
after (``served_counts()``, ``dispatch_health()``); any host fallback,
device failure or retry, open breaker, quarantine, host-only posture,
audit mismatch or armed fault fails the run. The host-oracle failover
stays in the program (``docs/robustness.md``); here every use of it is
a failure.

``--chips 4`` runs only the mesh phase: the same batch on a 4-device
mesh (4 x 512-row per-device sub-chunks), compared with the host
reference and a single-device ``BatchVerifier(mesh=None)``; all four
devices must serve rows.

``--cpu-rehearsal`` (builder's use) runs the same phases on
``JAX_PLATFORMS=cpu`` at 128 signatures and ``--txs 64``. It still ends
``"ok": false``, because the platform is not a TPU.

Earlier lines are one JSON record per phase (seconds, compile seconds
and persistent-cache hits, served counts). The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
on success; on failure it has ``"ok": false`` and the exit code is 1.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import time
import traceback

N_SIGS = 2048
REHEARSAL_SIGS = 128
NODE_LEDGERS = 3
NODE_TXS = 1000
REHEARSAL_TXS = 64
BAD_PER_CLASS = 4


class PhaseFailed(Exception):
    pass


class CompileMeter:
    """Compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit is counted in the compile seconds
    as its load time)."""

    def __init__(self):
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0

    def install(self) -> "CompileMeter":
        import jax.monitoring as mon

        def on_duration(event, duration, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += duration

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)
        return self

    def snapshot(self) -> tuple:
        return self.compile_s, self.hits, self.misses


def _snapshot() -> dict:
    from stellar_tpu.crypto import batch_verifier
    from stellar_tpu.utils.metrics import registry
    h = batch_verifier.dispatch_health()
    return {
        "served": dict(h["served"]),
        "fallback_chunks": h["fallback_chunks"],
        "deadline_misses": h["deadline_misses"],
        "retries": h["retries"],
        "short_circuits": h["short_circuits"],
        "audit_mismatches": h["audit"]["mismatches"],
        "audit_sampled": h["audit"]["sampled"],
        "breaker": h["breaker"]["state"],
        "quarantined": list(h["device_health"]["quarantined"]),
        "host_only": h["host_only"],
        "hot_rows": registry.meter(
            "crypto.verify.signer_table.hot_rows").count,
    }


def strict_problems(before: dict, after: dict) -> list:
    """Everything in one phase's accounting that says a row was not
    decided by a healthy device."""
    from stellar_tpu.utils import faults
    problems = []
    if os.environ.get("STELLAR_TPU_FAULTS"):
        problems.append("STELLAR_TPU_FAULTS is set")
    if faults.counters():
        problems.append(f"faults armed: {sorted(faults.counters())}")
    fb = after["served"]["host_fallback"] - before["served"]["host_fallback"]
    if fb:
        problems.append(f"host_fallback rows: {fb}")
    for key in ("fallback_chunks", "deadline_misses", "retries",
                "short_circuits", "audit_mismatches"):
        d = after[key] - before[key]
        if d:
            problems.append(f"{key}: {d}")
    if after["breaker"] != "closed":
        problems.append(f"breaker {after['breaker']}")
    if after["quarantined"]:
        problems.append(f"quarantined devices: {after['quarantined']}")
    if after["host_only"]:
        problems.append("host_only")
    return problems


def run_phase(name: str, fn, meter=None, min_device_rows: int = 0,
              min_hot_rows: int = 0) -> dict:
    """Run one phase under strict accounting and print its record.
    ``fn`` returns a dict of phase facts, or raises PhaseFailed; the
    phase also fails if the device served fewer than
    ``min_device_rows`` rows, or the hot kernel fewer than
    ``min_hot_rows``."""
    before = _snapshot()
    c0 = meter.snapshot() if meter else None
    t0 = time.perf_counter()
    rec = {"phase": name}
    problems = []
    try:
        rec.update(fn() or {})
    except PhaseFailed as e:
        problems.append(str(e))
    except Exception:
        problems.append(traceback.format_exc(limit=8))
    rec["seconds"] = time.perf_counter() - t0
    after = _snapshot()
    problems += strict_problems(before, after)
    rec["device_rows"] = after["served"]["device"] - before["served"]["device"]
    rec["hot_rows"] = after["hot_rows"] - before["hot_rows"]
    rec["audit_sampled"] = after["audit_sampled"] - before["audit_sampled"]
    if rec["device_rows"] < min_device_rows:
        problems.append(f"device served {rec['device_rows']} rows, "
                        f"expected at least {min_device_rows}")
    if rec["hot_rows"] < min_hot_rows:
        problems.append(f"hot kernel served {rec['hot_rows']} rows, "
                        f"expected at least {min_hot_rows}")
    if meter:
        c1 = meter.snapshot()
        rec["compile_s"] = c1[0] - c0[0]
        rec["cache_hits"] = c1[1] - c0[1]
        rec["cache_misses"] = c1[2] - c0[2]
    rec["ok"] = not problems
    if problems:
        rec["problems"] = problems
    print(json.dumps(rec), flush=True)
    return rec


# ---------------- the tx-set batch ----------------

def _off_curve_y() -> bytes:
    """The smallest y encoding that decodes to no curve point."""
    from stellar_tpu.crypto import ed25519_ref as ref
    for y in range(2, 1000):
        enc = y.to_bytes(32, "little")
        if ref.point_decompress(enc) is None:
            return enc
    raise AssertionError("no off-curve y below 1000")


def txset_batch(n: int, seed: int):
    """``n`` (pk, msg, sig) rows signed by ``n`` fresh keys over 32-byte
    tx hashes, with BAD_PER_CLASS rows of each invalid class at seeded
    positions. Returns (items, bad_row_count)."""
    import numpy as np

    from stellar_tpu.crypto import ed25519_ref as ref
    rng = np.random.default_rng(seed)
    seeds = rng.bytes(32 * n)
    hashes = rng.bytes(32 * n)
    items = []
    for i in range(n):
        sk, msg = seeds[32 * i:32 * i + 32], hashes[32 * i:32 * i + 32]
        items.append((ref.secret_to_public(sk), msg, ref.sign(sk, msg)))

    small = sorted(ref.SMALL_ORDER_ENCODINGS)
    off_curve = _off_curve_y()

    def flip(b: bytes, i: int) -> bytes:
        return b[:i] + bytes([b[i] ^ 1]) + b[i + 1:]

    def noncanon_s(pk, m, s):
        big = int.from_bytes(s[32:], "little") + ref.L
        return pk, m, s[:32] + big.to_bytes(32, "little")

    def noncanon_a(pk, m, s):
        return (ref.P + int(rng.integers(0, 19))).to_bytes(
            32, "little"), m, s

    classes = [
        lambda pk, m, s: (pk, m, flip(s, 32)),           # tampered sig
        lambda pk, m, s: (pk, flip(m, 0), s),            # wrong message
        noncanon_s,                                      # s >= L
        noncanon_a,                                      # y(A) >= p
        lambda pk, m, s: (small[int(rng.integers(len(small)))], m, s),
        lambda pk, m, s: (pk, m, off_curve + s[32:]),    # R off curve
        lambda pk, m, s: (pk, m, small[int(rng.integers(len(small)))]
                          + s[32:]),                     # R small order
    ]
    pos = rng.choice(n, size=len(classes) * BAD_PER_CLASS, replace=False)
    for k, i in enumerate(pos):
        items[int(i)] = classes[k // BAD_PER_CLASS](*items[int(i)])
    return items, len(pos)


def reference_verdicts(items):
    import numpy as np

    from stellar_tpu.crypto import ed25519_ref as ref
    return np.array([ref.verify(pk, m, s) for pk, m, s in items])


def check_verdicts(got, want, label: str) -> None:
    import numpy as np
    got = np.asarray(got, dtype=bool)
    if got.shape != want.shape:
        raise PhaseFailed(f"{label}: shape {got.shape} != {want.shape}")
    diff = np.flatnonzero(got != want)
    if len(diff):
        raise PhaseFailed(f"{label}: {len(diff)} verdicts differ from "
                          f"ed25519_ref, rows {diff[:16].tolist()}")


# ---------------- phases ----------------

def device_phase(rec: dict, expect_count=None) -> dict:
    """Fills ``rec`` before checking, so a failing run still reports
    what JAX found."""
    import jax

    from stellar_tpu.crypto import batch_verifier, native_prep
    devs = jax.devices()
    rec["device"] = {"platform": devs[0].platform,
                     "kind": devs[0].device_kind, "count": len(devs)}
    rec["native_prep"] = native_prep.available()
    rec["jax"] = jax.__version__
    if devs[0].platform != "tpu":
        raise PhaseFailed(f"platform is {devs[0].platform!r}, not 'tpu'")
    if expect_count is not None and len(devs) != expect_count:
        raise PhaseFailed(f"{len(devs)} devices, expected {expect_count}")
    if not batch_verifier.device_available(timeout_s=120.0):
        raise PhaseFailed("device probe did not report the TPU usable")
    return rec


def txset_phase(items, want, calls: int) -> dict:
    """``calls`` blocking verifies of the batch through the production
    verifier, each checked row for row; the first includes compiling."""
    from stellar_tpu.crypto import batch_verifier
    v = batch_verifier.default_verifier()
    call_s = []
    for i in range(calls):
        t0 = time.perf_counter()
        got = v.verify_batch(items)
        call_s.append(time.perf_counter() - t0)
        check_verdicts(got, want, f"call {i}")
    return {"rows": len(items), "n_devices": len(v._devices or ()) or 1,
            "call_s": call_s}


def node_phase(txs: int) -> dict:
    from stellar_tpu.main import cli
    argv = ["apply-load", "--scenario", "multisig",
            "--ledgers", str(NODE_LEDGERS), "--txs", str(txs),
            "--verify", "device"]
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = cli.main(argv)
    lines = out.getvalue().strip().splitlines()
    if rc != 0 or not lines:
        raise PhaseFailed(f"apply-load rc={rc}: {out.getvalue()[-500:]}")
    stats = json.loads(lines[-1])
    if stats.get("total_applied") != NODE_LEDGERS * txs:
        raise PhaseFailed(f"applied {stats.get('total_applied')} of "
                          f"{NODE_LEDGERS * txs} transactions")
    return {"argv": argv, "stats": stats}


def mesh_phase(items, want, chips: int) -> dict:
    from stellar_tpu.crypto import batch_verifier
    from stellar_tpu.crypto.batch_verifier import (
        DEFAULT_BUCKET_SIZES, BatchVerifier,
    )
    v_mesh = batch_verifier.default_verifier()
    n_mesh = len(v_mesh._devices or ())
    if n_mesh != chips:
        raise PhaseFailed(f"default mesh spans {n_mesh} devices, "
                          f"expected {chips}")
    t0 = time.perf_counter()
    got_mesh = v_mesh.verify_batch(items)
    mesh_s = time.perf_counter() - t0
    check_verdicts(got_mesh, want, "mesh")
    served = {str(k): v for k, v in sorted(v_mesh.device_served.items())}
    idle = [d for d in range(chips) if v_mesh.device_served.get(d, 0) <= 0]
    if idle:
        raise PhaseFailed(f"devices served no rows: {idle} ({served})")
    v_one = BatchVerifier(mesh=None, bucket_sizes=DEFAULT_BUCKET_SIZES)
    t0 = time.perf_counter()
    got_one = v_one.verify_batch(items)
    one_s = time.perf_counter() - t0
    check_verdicts(got_one, want, "single device")
    return {"rows": len(items), "per_device_rows": served,
            "mesh_first_call_s": mesh_s, "single_first_call_s": one_s}


# ---------------- driver ----------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 4-device mesh phase")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="builder's rehearsal on JAX_PLATFORMS=cpu at "
                    f"{REHEARSAL_SIGS} signatures; always ends ok: false")
    args = ap.parse_args(argv)

    from stellar_tpu.utils.jax_setup import enable_compile_cache, force_cpu
    if args.cpu_rehearsal:
        force_cpu(args.chips if args.chips > 1 else None)
    cache_dir = enable_compile_cache()
    meter = CompileMeter().install()
    print(json.dumps({"compile_cache_dir": cache_dir}), flush=True)

    n = REHEARSAL_SIGS if args.cpu_rehearsal else N_SIGS
    txs = REHEARSAL_TXS if args.cpu_rehearsal else NODE_TXS
    found: dict = {}
    records = [run_phase("device", lambda: device_phase(
        found, args.chips if args.chips > 1 else None))]
    device = found.get("device")
    if records[0]["ok"] or args.cpu_rehearsal:
        items, n_bad = txset_batch(n, args.seed)
        want = reference_verdicts(items)
        if int(want.sum()) != n - n_bad:
            raise AssertionError("the reference accepts a seeded bad row")
        # rows the host policy gate rejects may skip the device; every
        # other row must be served by it
        valid = n - n_bad
        if args.chips > 1:
            records.append(run_phase("mesh", lambda: mesh_phase(
                items, want, args.chips), meter, min_device_rows=valid))
        else:
            # fresh signers ride the cold kernel; the second sight of
            # each signer rides the hot signer-table kernel (all but
            # the rows whose A cannot be tabled)
            records.append(run_phase("txset", lambda: txset_phase(
                items, want, 1), meter, min_device_rows=valid))
            records.append(run_phase(
                "txset-hot", lambda: txset_phase(items, want, 2), meter,
                min_device_rows=2 * valid,
                min_hot_rows=2 * (n - 2 * BAD_PER_CLASS)))
            records.append(run_phase(
                "node", lambda: node_phase(txs), meter,
                min_device_rows=NODE_LEDGERS * txs))
    problems = [r["phase"] for r in records if not r["ok"]]
    if problems:
        print(json.dumps({"ok": False, "device": device,
                          "failed": problems}))
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception as e:  # an import or set-up failure: still no result
        traceback.print_exc()
        print(json.dumps({"ok": False, "error": repr(e)[:300]}))
        sys.exit(1)
