#!/usr/bin/env python
"""HOT_SIGNER_OK self-check (run by ``tools/tier1.sh``; ISSUE 16).

Proves the hot-signer fixed-base acceleration end-to-end on the forced
4-device CPU mesh (same shapes + persistent compile cache as the
device-domain chaos driver):

1. **ledger delta**: the traced kernel-cost ledger's hot dsm arm
   executes >= 20% fewer MACs/call than the cold arm at batch 128 —
   the ISSUE 16 acceptance number, asserted from the SAME tool the
   tier-1 ``KERNEL_COST_OK`` gate runs, not remembered constants;
2. **zipf replicas**: a zipf-signer stream over >1000 DISTINCT
   signers, run twice from a cold cache (replica A / replica B), must
   produce bit-identical verdict streams AND identical hot/cold
   partition tallies (the partition is content-keyed and clock/RNG
   free — replicas must agree on which rows rode which kernel), with
   every verdict matching the ``ed25519_ref`` oracle;
3. **compile reuse**: the whole >1000-signer sweep compiles ZERO
   kernel shapes beyond the pinned sub-chunk executable — for the
   cold kernel AND the hot variant (cached tables are operands, not
   compiled constants);
4. **steady state**: re-dispatches of a fully cached-table batch
   ride the hot kernel on every row, with the transfer ledger
   reconciling against the engine's own byte accounting;
5. **eviction under pressure**: a tiny byte budget (10 tables) forces
   real LRU evictions while the zipf head keeps hitting — the cache
   degrades by evicting tails, never by serving wrong tables
   (verdicts stay oracle-identical through the pressure).

Prints one JSON line; exit 0 = every check passed.
"""

import argparse
import importlib.util
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N_DEV = 4
BUCKET = 8
SUB = BUCKET // N_DEV
N_SIGNERS = 1008          # > 1000: the acceptance floor
FRESH_PER_BATCH = 6       # 6 first-sight + 2 zipf-head rows per batch
HOT_HEAD = 8              # the zipf head the repeats draw from
MIN_RECONCILE = 0.95


def _env_setup() -> None:
    """CPU-only multi-device env — must run before jax imports (same
    shapes + persistent cache as the device-domain chaos driver)."""
    from stellar_tpu.utils.jax_setup import enable_compile_cache, force_cpu
    force_cpu(N_DEV)
    enable_compile_cache()


def _kernel_cost():
    spec = importlib.util.spec_from_file_location(
        "kernel_cost", os.path.join(REPO, "tools", "kernel_cost.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _corpus():
    """>1000 distinct signers, one pre-signed message each, with the
    oracle verdict computed once per signer (the OpenSSL signing path
    makes a thousand keys a few seconds, not minutes). Two structured
    invalid rows ride in the zipf head so gate-decided rows flow
    through the partition too."""
    import numpy as np
    from stellar_tpu.crypto import ed25519_ref as ref
    pool = []
    for i in range(N_SIGNERS):
        seed = (i + 1).to_bytes(4, "little") * 8
        pk = ref.secret_to_public(seed)
        msg = b"hot-selfcheck-%d" % i
        pool.append((pk, msg, ref.sign(seed, msg)))
    pk0, m0, s0 = pool[0]
    pool.append((pk0, m0 + b"!", s0))     # tampered message
    pool.append((pk0[:31], m0, s0))       # bad pk length
    want = np.array([ref.verify(p, m, s) for p, m, s in pool])
    return pool, want


def _batches(pool):
    """Deterministic zipf-flavored batch stream: every batch carries
    FRESH_PER_BATCH first-sight signers (full >1000-signer coverage by
    the end) plus repeats drawn from the zipf head — the repeat-signer
    regime the table cache serves. The two invalid rows ride batch 0's
    head slots."""
    batches = []
    n_batches = N_SIGNERS // FRESH_PER_BATCH
    for b in range(n_batches):
        idx = [b * FRESH_PER_BATCH + j for j in range(FRESH_PER_BATCH)]
        for j in range(BUCKET - FRESH_PER_BATCH):
            if b == 0:
                idx.append(N_SIGNERS + j)          # invalid rows
            else:
                idx.append((b * 3 + j * 5) % HOT_HEAD)
        batches.append(idx)
    return batches


def _run_stream(v, pool, want, batches):
    """One replica pass: resolve every batch, return the concatenated
    verdict stream + the partition/cache tallies for the pass."""
    import numpy as np
    from stellar_tpu.crypto import batch_verifier as bv
    from stellar_tpu.utils.metrics import registry
    hot0 = registry.meter("crypto.verify.signer_table.hot_rows").count
    cold0 = registry.meter("crypto.verify.signer_table.cold_rows").count
    got, exp = [], []
    for idx in batches:
        got.append(v.verify_batch([pool[k] for k in idx]))
        exp.append(want[idx])
    st = bv.dispatch_health()["signer_tables"]
    return {
        "verdicts": np.concatenate(got),
        "expected": np.concatenate(exp),
        "hot_rows": registry.meter(
            "crypto.verify.signer_table.hot_rows").count - hot0,
        "cold_rows": registry.meter(
            "crypto.verify.signer_table.cold_rows").count - cold0,
        "hits": st["hits"],
        "misses": st["misses"],
        "installs": st["installs"],
        "entries": st["entries"],
    }


def run() -> dict:
    import numpy as np

    from stellar_tpu.crypto import batch_verifier as bv
    from stellar_tpu.parallel import signer_tables
    from stellar_tpu.parallel.mesh import batch_mesh
    from stellar_tpu.utils.metrics import registry
    from stellar_tpu.utils.transfer_ledger import transfer_ledger

    import jax

    devs = jax.devices()
    if len(devs) < 2:
        raise SystemExit(
            f"self-check needs a multi-device host (got {len(devs)}): "
            "run with XLA_FLAGS=--xla_force_host_platform_device_"
            "count=4")

    problems = []

    # ---- check 1: the ledger's hot arm >= 20% under cold ----
    kc = _kernel_cost().slim_record(batch=128)
    cold_macs = kc["dsm"]["cold"]["executed_macs_per_call"]
    hot_macs = kc["dsm"]["hot"]["executed_macs_per_call"]
    savings = 1.0 - hot_macs / cold_macs
    if hot_macs > 0.80 * cold_macs:
        problems.append(
            f"hot dsm arm {hot_macs} MACs/call is not >=20% under "
            f"cold {cold_macs} — the acceleration regressed")

    def configure():
        bv.configure_dispatch(
            deadline_ms=30_000, dispatch_retries=0,
            failure_threshold=8, backoff_min_s=0.3, backoff_max_s=0.6,
            audit_rate=0.05, device_failure_threshold=2,
            device_backoff_min_s=0.2, device_backoff_max_s=0.5)

    v = bv.BatchVerifier(mesh=batch_mesh(), bucket_sizes=(BUCKET,))
    bv._reset_dispatch_state_for_testing()
    configure()

    # warm both kernel variants' sub-chunk executables (sequential:
    # after the first device writes/loads the persistent-cache entry
    # the rest LOAD it; parallel deserialization measured slower)
    kern = v._kernel_for(SUB)
    hkern = v._kernel_for(SUB, plugin=v._hot)
    rows = [np.repeat(x, SUB, 0) for x in
            (bv._PAD_A, bv._PAD_R, bv._PAD_S, bv._PAD_H)]
    hrows = [np.repeat(x, SUB, 0) for x in v._hot.pad_rows()]
    for d in devs:
        np.asarray(kern(*[jax.device_put(x, d) for x in rows]))
        np.asarray(hkern(*[jax.device_put(x, d) for x in hrows]))

    # ---- checks 2+3: zipf replicas + compile reuse ----
    pool, want = _corpus()
    batches = _batches(pool)
    rep_a = _run_stream(v, pool, want, batches)
    # replica B: fresh dispatch state (empty table cache, clean
    # health) — same traffic, same content-keyed decisions.
    # The reset also zeroes the transfer ledger, so the engine-side
    # byte counters (cumulative per engine instance) are snapshotted
    # HERE: reconciliation below compares same-window deltas.
    bv._reset_dispatch_state_for_testing()
    configure()
    with v._stats_lock:
        shipped0, fetched0 = v.shipped_bytes, v.fetched_bytes
    rep_b = _run_stream(v, pool, want, batches)

    for name, rep in (("A", rep_a), ("B", rep_b)):
        if not (rep["verdicts"] == rep["expected"]).all():
            bad = int((rep["verdicts"] != rep["expected"]).sum())
            problems.append(
                f"replica {name}: {bad} verdicts mismatched the "
                "ed25519_ref oracle")
        if rep["hot_rows"] == 0:
            problems.append(
                f"replica {name}: zipf stream never rode the hot "
                "kernel")
        if rep["installs"] < N_SIGNERS:
            problems.append(
                f"replica {name}: only {rep['installs']} installs "
                f"for {N_SIGNERS} distinct signers")
    if not np.array_equal(rep_a["verdicts"], rep_b["verdicts"]):
        problems.append("replica verdict streams DIVERGED")
    part_keys = ("hot_rows", "cold_rows", "hits", "misses", "installs")
    if any(rep_a[k] != rep_b[k] for k in part_keys):
        problems.append(
            "replica partitions diverged: "
            f"A={ {k: rep_a[k] for k in part_keys} } "
            f"B={ {k: rep_b[k] for k in part_keys} } — the hot/cold "
            "split is not deterministic")

    cold_shapes = sorted(v._kernels)
    hot_shapes = sorted(
        {n for kerns in v._kernels_variants.values() for n in kerns})
    pinned = {SUB, BUCKET}
    if not (set(cold_shapes) <= pinned and set(hot_shapes) <= pinned):
        problems.append(
            f">1000-signer sweep compiled beyond the pinned shapes: "
            f"cold={cold_shapes} hot={hot_shapes} vs {sorted(pinned)}")

    # ---- check 4: steady-state cached-table re-dispatches ride the
    # hot kernel, ledger reconciled ----
    head = [pool[k] for k in range(HOT_HEAD)]   # all cached by now
    hot_meter = registry.meter("crypto.verify.signer_table.hot_rows")
    hot0 = hot_meter.count
    before = transfer_ledger.totals()
    for _ in range(2):
        got = v.verify_batch(head)
        if not (got == want[:HOT_HEAD]).all():
            problems.append("steady-state hot batch verdicts broke")
    after = transfer_ledger.totals()
    delta = {k: after[k] - before[k]
             for k in ("round_trips", "bytes_h2d",
                       "redundant_constant_bytes")}
    delta["hot_rows"] = hot_meter.count - hot0
    if delta["round_trips"] == 0:
        problems.append("steady-state window recorded zero round "
                        "trips")
    if delta["hot_rows"] != 2 * HOT_HEAD:
        problems.append(
            f"steady-state cached-table batches rode the hot kernel "
            f"on {delta['hot_rows']} of {2 * HOT_HEAD} rows")
    with v._stats_lock:
        shipped = v.shipped_bytes - shipped0
        fetched = v.fetched_bytes - fetched0

    def _ratio(a, b):
        return min(a, b) / max(a, b) if max(a, b) else None

    rec_h2d = _ratio(after["bytes_h2d"], shipped)
    rec_d2h = _ratio(after["bytes_d2h"], fetched)
    reconciliation = min(x for x in (rec_h2d, rec_d2h)
                         if x is not None) \
        if (rec_h2d or rec_d2h) else None
    if reconciliation is None or reconciliation < MIN_RECONCILE:
        problems.append(
            f"ledger/engine byte reconciliation {reconciliation} < "
            f"{MIN_RECONCILE} (ledger h2d={after['bytes_h2d']} vs "
            f"engine {shipped}; d2h={after['bytes_d2h']} vs "
            f"{fetched})")

    # ---- check 5: eviction under pressure ----
    cache = signer_tables.signer_table_cache
    st_before = cache.snapshot()
    cache.configure(max_bytes=10 * signer_tables.TABLE_BYTES)
    try:
        press = _run_stream(v, pool, want, batches[:24])
        snap = cache.snapshot()
    finally:
        cache.configure(max_bytes=signer_tables.DEFAULT_CACHE_BYTES)
    evictions = snap["evictions"] - st_before["evictions"]
    press_hits = snap["hits"] - st_before["hits"]
    if not (press["verdicts"] == press["expected"]).all():
        problems.append("verdicts broke under cache pressure")
    if evictions == 0:
        problems.append("tiny byte budget forced zero evictions — "
                        "the LRU pressure valve is dead")
    if snap["bytes"] > 10 * signer_tables.TABLE_BYTES:
        problems.append(
            f"cache bytes {snap['bytes']} exceed the configured "
            f"budget {10 * signer_tables.TABLE_BYTES}")
    if press_hits == 0:
        problems.append("zipf head stopped hitting under pressure")

    prom = registry.to_prometheus()
    if "crypto_verify_signer_table_hits" not in prom:
        problems.append("signer-table counters missing from the "
                        "Prometheus exposition")

    return {
        "ok": not problems,
        "devices": len(devs),
        "bucket": BUCKET,
        "distinct_signers": N_SIGNERS,
        "ledger_version": kc["ledger_version"],
        "dsm_macs": {"cold": cold_macs, "hot": hot_macs,
                     "savings_frac": round(savings, 4)},
        "replica_a": {k: rep_a[k] for k in part_keys},
        "replica_b": {k: rep_b[k] for k in part_keys},
        "kernel_shapes": {"cold": cold_shapes, "hot": hot_shapes},
        "steady_state": delta,
        "reconciliation": round(reconciliation, 4)
        if reconciliation is not None else None,
        "pressure": {"entries": snap["entries"],
                     "bytes": snap["bytes"],
                     "evictions": evictions,
                     "hits": press_hits},
        "problems": problems,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", action="store_true",
                    help="(default) print one JSON line")
    args = ap.parse_args()  # noqa: F841 — flag kept for symmetry
    _env_setup()
    rec = run()
    print(json.dumps(rec, default=str))
    return 0 if rec["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
