#!/usr/bin/env python
"""Soak/load harness for the resident verify service — the standing
scale scenario (ROADMAP "continuous-batching verify service").

Drives a sustained tx-flood through
:class:`stellar_tpu.crypto.verify_service.VerifyService` with a
flapping device injected (``flaky-device:0`` via
``stellar_tpu.utils.faults``), the result-integrity audit sampling ON,
and a mid-run global-breaker trip, then proves the overload story
end-to-end:

* **work conservation** (the law the tier-1 ``SOAK_OK`` gate pins):
  ``submitted == verified + rejected + shed [+ handoff]`` exactly,
  ``failed == 0`` (the ``handoff`` terminal appears only under
  ``--replicas`` when a killed replica's queue moves to a survivor),
  ``pending == 0`` after drain — no item is ever silently dropped;
* **metrics accounting**: the service's counters agree with the
  ``crypto.verify.service.*`` meters and the conservation totals
  appear in the Prometheus exposition (the PR 5 export layer);
* **lane isolation**: the SCP-priority lane's p99 wait stays bounded
  while the bulk lane rejects at ingress AND sheds from the backlog
  (typed ``Overloaded`` both ways);
* **bit-identical decisions**: every VERIFIED item matches the
  ``ed25519_ref`` oracle, flapping device or not.

``--smoke`` is the short CPU-only tier-1 mode (forced 4 virtual
devices, bucket 8 — the exact shapes the device-domain chaos driver
already compiled into the shared persistent cache, so a tier-1 run
pays zero new XLA compiles). Without ``--smoke`` the flood runs for
``--duration`` seconds and optionally adds a corrupting device
(``--corrupt``) so the audit → host-only → shed-ladder-level-2 path
soaks too.

Per-phase events append to a size-capped JSONL
(``utils.logging.append_jsonl_capped`` — 4 MB + 1 generation
rotation), so long soaks can't fill the disk. Prints one JSON record;
exit 0 = every check passed.
"""

import argparse
import json
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N_DEV = 4
BUCKET = 8              # device-domain chaos shapes: sub-chunk = 2
SUB = BUCKET // N_DEV
# Runaway guard only — scp waits are real 4-device CPU dispatches, so
# the absolute p99 drifts ~2x with host load (observed 2.5-5.0s, the
# worst right after a saturated tier-1 sweep); lane ISOLATION is
# pinned by the relative check (scp p99 < bulk p99) below, which is
# load-invariant. A starved scp lane shows up as tens of seconds.
SMOKE_SCP_P99_BOUND_MS = 8000.0


def _env_setup(real_device: bool) -> None:
    """CPU-only multi-device env unless ``real_device`` — must run
    before jax imports."""
    from stellar_tpu.utils.jax_setup import enable_compile_cache, force_cpu
    if not real_device:
        force_cpu(N_DEV)
    enable_compile_cache()


def ramp_schedule(rounds: int, base_count: int) -> list:
    """Offered-load schedule for ``--ramp``: ``base_count``
    submissions per round for the first half, DOUBLE from the midpoint
    on — the mid-run load shift the closed-loop controller (ISSUE 15)
    must absorb without human knob turns. Shared with
    ``tools/control_selfcheck.py`` (the tier-1 ``CONTROL_OK`` gate
    drives the same shape host-only), so the gate and the chaos-mesh
    soak exercise one schedule."""
    mid = max(1, rounds // 2)
    return [base_count * (2 if r >= mid else 1)
            for r in range(max(1, rounds))]


def _signed_pool():
    """Small pool of valid signatures + structured invalid rows, with
    oracle expectations computed once per entry (pure-Python signing
    is ~25 ms/sig — variety comes from COMPOSITION, not fresh keys)."""
    import numpy as np
    from stellar_tpu.crypto import ed25519_ref as ref
    pool = []
    for i in range(6):
        seed = bytes([17 * (i + 1) % 251]) * 32
        pk = ref.secret_to_public(seed)
        msg = b"soak-%d" % i
        pool.append((pk, msg, ref.sign(seed, msg)))
    pk0, m0, s0 = pool[0]
    pool.append((pk0, m0 + b"!", s0))     # tampered message
    pool.append((pk0[:31], m0, s0))       # bad pk length
    want = np.array([ref.verify(p, m, s) for p, m, s in pool])
    return pool, want


def _submission(pool, want, i, n):
    """One flood submission: a rotating slice of the pool (start and
    stride vary with ``i``) so submissions carry DISTINCT content —
    the shed rule draws per-submission digests, and identical content
    would shed identically by design."""
    start = i % len(pool)
    stride = 1 + i % 3
    idx = [(start + j * stride) % len(pool) for j in range(n)]
    return [pool[k] for k in idx], want[idx]


def _zipf_pool(n_signers: int):
    """Zipf-signer corpus (``--signers zipf``): ``n_signers`` DISTINCT
    keys, one pre-signed message each (oracle expectations computed
    once — the OpenSSL signing path makes hundreds of keys cheap),
    plus the two structured invalid rows. Returns the pool, the oracle
    vector, and a zipf(s~1) rank table: signer ``k`` appears with
    weight ~1/(k+1), so a handful of hot signers dominate the draw —
    the repeat-signer regime the per-pubkey table cache (ISSUE 16)
    exists for — while the long tail keeps installing fresh entries.
    The table is deterministic (no RNG): replicas must partition the
    SAME rows onto the hot kernel or verdict streams diverge."""
    import numpy as np
    from stellar_tpu.crypto import ed25519_ref as ref
    pool = []
    for i in range(n_signers):
        seed = (i + 1).to_bytes(4, "little") * 8
        pk = ref.secret_to_public(seed)
        msg = b"zipf-%d" % i
        pool.append((pk, msg, ref.sign(seed, msg)))
    pk0, m0, s0 = pool[0]
    pool.append((pk0, m0 + b"!", s0))     # tampered message
    pool.append((pk0[:31], m0, s0))       # bad pk length
    want = np.array([ref.verify(p, m, s) for p, m, s in pool])
    weighted = []
    for k in range(n_signers):
        weighted.extend([k] * max(1, n_signers // (8 * (k + 1))))
    weighted.extend([n_signers, n_signers + 1])   # invalid rows ride
    return pool, want, weighted


def _zipf_submission(pool, want, weighted, i, n):
    """Draw ``n`` zipf-ranked rows for submission ``i``: a fixed prime
    stride over the rank table — deterministic, full-cycle (the stride
    is coprime to any table this size), and distinct per submission so
    the shed rule's per-submission digests stay distinct."""
    L = len(weighted)
    idx = [weighted[((i * 7 + j) * 7919) % L] for j in range(n)]
    return [pool[k] for k in idx], want[idx]


def _hash_corpus(i: int, n: int):
    """Rotating deterministic message batch ``i``: every length regime
    (empty through multi-block) with content varying per round so no
    two rounds hash identical bytes."""
    msgs = []
    for j in range(n):
        ln = (7 * i + 13 * j) % 200
        msgs.append(bytes(((i + j + k) % 256) for k in range(ln)))
    return msgs


def run_sha256(smoke: bool, duration_s: float,
               events_path: str) -> dict:
    """The second workload through the SAME flaky-device flap (ISSUE
    7): sustained ``BatchHasher`` floods on a forced multi-device mesh
    with ``flaky-device:0`` armed, audit sampling on, and a mid-run
    global-breaker trip — proving the fault-domain port is real for a
    plugin that is not ed25519. Invariants: every digest bit-identical
    to ``hashlib.sha256`` (flap, quarantine, re-shard, breaker
    short-circuit and all), the flap actually fired, device 0 actually
    quarantined, rows actually fell back AND actually rode devices,
    the audit actually sampled hash rows, and the served accounting
    conserves (device + host-fallback == rows offered)."""
    import hashlib

    from stellar_tpu.crypto import batch_hasher as bh
    from stellar_tpu.crypto import batch_verifier as bv
    from stellar_tpu.utils import faults
    from stellar_tpu.utils.logging import append_jsonl_capped
    from stellar_tpu.utils.metrics import registry

    import jax

    devs = jax.devices()
    if len(devs) < 2:
        raise SystemExit(
            f"soak needs a multi-device host (got {len(devs)}): the "
            "flaky-device fault shape is per-device — run with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=4")

    def event(kind, **fields):
        append_jsonl_capped(events_path, {"event": kind, **fields})

    from stellar_tpu.parallel.mesh import batch_mesh
    h = bh.BatchHasher(mesh=batch_mesh(), bucket_sizes=(BUCKET,))
    bv.configure_dispatch(
        deadline_ms=30_000, dispatch_retries=0,
        failure_threshold=6, backoff_min_s=0.3, backoff_max_s=0.6,
        audit_rate=0.25,                # every part samples hash rows
        device_failure_threshold=2,
        device_backoff_min_s=0.2, device_backoff_max_s=0.5)

    # warm: one clean full-bucket batch compiles the SUB-row hash
    # kernel (scan-based — seconds, not the minutes a fresh verify
    # bucket costs) and every device serves its own sub-chunk once
    t0 = time.monotonic()
    assert h.hash_batch(_hash_corpus(0, BUCKET)) == [
        hashlib.sha256(m).digest() for m in _hash_corpus(0, BUCKET)]
    warm_s = round(time.monotonic() - t0, 1)
    event("warm", seconds=warm_s, devices=len(devs),
          workload="sha256")

    faults.set_fault(faults.DISPATCH, "flaky-device", 0)
    event("fault", spec="device.dispatch=flaky-device:0")

    rounds = 40 if smoke else max(40, int(duration_s * 10))
    mismatches = 0
    rows = BUCKET                    # the warm batch is served too
    breaker_tripped = False
    t_run = time.monotonic()
    for i in range(1, rounds + 1):
        msgs = _hash_corpus(i, BUCKET + (i % 5))  # padding varies
        got = h.hash_batch(msgs)
        want = [hashlib.sha256(m).digest() for m in msgs]
        rows += len(msgs)
        mismatches += sum(1 for g, w in zip(got, want) if g != w)
        if not breaker_tripped and i == rounds // 2:
            bv._breaker.trip()     # correlated outage mid-flood
            breaker_tripped = True
            event("breaker-trip", round=i)
        if not smoke and time.monotonic() - t_run > duration_s:
            break
    fault_counters = faults.counters()   # captured BEFORE clear
    faults.clear()
    # recovery: with the flap gone the mesh heals and serves clean
    post = _hash_corpus(rounds + 1, BUCKET)
    mismatches += sum(
        1 for g, w in zip(h.hash_batch(post),
                          [hashlib.sha256(m).digest() for m in post])
        if g != w)
    rows += BUCKET
    wall_s = round(time.monotonic() - t_run, 1)

    health = bv.dispatch_health()
    sampled = registry.counter("crypto.hash.audit.sampled").count
    event("final", workload="sha256", rows=rows, served=dict(h.served),
          wall_s=wall_s)

    problems = []
    if mismatches:
        problems.append(f"{mismatches} digests mismatched hashlib")
    if h.served["device"] == 0:
        problems.append("no row ever rode a device — flap proved "
                        "nothing")
    if h.served["host-fallback"] == 0:
        problems.append("flap never forced a host fallback")
    if h.served["device"] + h.served["host-fallback"] != rows:
        problems.append(
            f"served accounting leaks rows: {h.served} vs {rows}")
    fc = fault_counters.get("device.dispatch", {})
    if not fc.get("fired"):
        problems.append("flaky-device:0 never fired — no flap soaked")
    if health["device_health"]["transitions_total"] == 0:
        problems.append("device 0 never quarantined under the flap")
    if sampled == 0:
        problems.append("the result-integrity audit never sampled a "
                        "hash row")
    if "crypto_hash_serve" not in registry.to_prometheus():
        problems.append("hash workload metrics missing from the "
                        "Prometheus exposition")

    return {
        "ok": not problems,
        "mode": "smoke" if smoke else "soak",
        "workload": "sha256",
        "wall_s": wall_s,
        "warm_s": warm_s,
        "devices": len(devs),
        "rows": rows,
        "rounds": rounds,
        "served": dict(h.served),
        "device_served": dict(h.device_served),
        "audit_sampled": sampled,
        "audit_mismatches": h.audit_mismatches,
        "fault_counters": fault_counters,
        "breaker": health["breaker"]["state"],
        "quarantines": health["device_health"]["transitions_total"],
        "flight_recorder_dumps": health["flight_recorder"][
            "dump_reasons"],
        "events_path": events_path,
        "problems": problems,
    }


def run(smoke: bool, duration_s: float, corrupt: bool,
        events_path: str, tenants: int = 0,
        flooder: bool = False, ramp: bool = False,
        signers: str = "pool", replicas: int = 0,
        ingress: bool = False) -> dict:
    import numpy as np

    from stellar_tpu.crypto import batch_verifier as bv
    from stellar_tpu.crypto import controller as ctl_mod
    from stellar_tpu.crypto import tenant as tn
    from stellar_tpu.crypto import verify_service as vs
    from stellar_tpu.utils import faults
    from stellar_tpu.utils.logging import append_jsonl_capped
    from stellar_tpu.utils.metrics import registry

    import jax

    devs = jax.devices()
    if len(devs) < 2:
        raise SystemExit(
            f"soak needs a multi-device host (got {len(devs)}): the "
            "flaky-device fault shape is per-device — run with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=4")

    def event(kind, **fields):
        append_jsonl_capped(events_path, {"event": kind, **fields})

    from stellar_tpu.parallel.mesh import batch_mesh
    mesh = batch_mesh()
    v = bv.BatchVerifier(mesh=mesh, bucket_sizes=(BUCKET,))
    bv.configure_dispatch(
        deadline_ms=30_000, dispatch_retries=0,
        failure_threshold=6, backoff_min_s=0.3, backoff_max_s=0.6,
        audit_rate=0.05,                # audit sampling ON
        device_failure_threshold=2,
        device_backoff_min_s=0.2, device_backoff_max_s=0.5)

    # warm every device's sub-chunk executable in parallel (XLA's C++
    # compile releases the GIL; the persistent cache shared with the
    # device-domain chaos driver makes tier-1 runs load, not compile)
    t0 = time.monotonic()
    kern = v._kernel_for(SUB)
    rows = [np.repeat(x, SUB, 0) for x in
            (bv._PAD_A, bv._PAD_R, bv._PAD_S, bv._PAD_H)]

    def warm(d):
        np.asarray(kern(*[jax.device_put(x, d) for x in rows]))

    # sequential on purpose: after the first device writes/loads the
    # persistent-cache entry the rest LOAD it (~8 s each measured vs
    # ~55 s compile), and parallel deserialization was measured 3x
    # SLOWER than sequential on a small host (GIL-bound)
    for d in devs:
        warm(d)
    if signers == "zipf":
        # zipf traffic rides the HOT (cached-table) kernel variant
        # too — warm it now or its first compile lands mid-flood and
        # stalls the scp lane past its p99 bound
        hkern = v._kernel_for(SUB, plugin=v._hot)
        hrows = [np.repeat(x, SUB, 0) for x in v._hot.pad_rows()]
        for d in devs:
            np.asarray(hkern(*[jax.device_put(x, d) for x in hrows]))
    warm_s = round(time.monotonic() - t0, 1)
    event("warm", seconds=warm_s, devices=len(devs))

    # --tenants N: the bulk flood is striped across N synthetic
    # tenants (scp stays un-tenanted — the consensus lane's submitter
    # is the node itself), with per-tenant quotas sized so the
    # OPTIONAL adversarial flooder (--flooder) exhausts its own slice
    # on the same forced-4-device chaos mesh the legacy scenario uses
    tenant_knobs_saved = None
    if tenants > 0:
        tenant_knobs_saved = (tn.TENANT_DEPTH, tn.TENANT_BYTES,
                              tn.tenant_slo._window)
        tn.clear_tenant_policies()
        tn.configure_tenants(depth=6, nbytes=0, window=1024)
        tn.set_tenant_policy("flooder", depth=12)
    # --ramp: attach the closed-loop controller (ISSUE 15) so the
    # mid-run load doubling is absorbed by knob moves, not operators —
    # clamps sized to the chaos-mesh shapes (the verifier chunks any
    # grown batch back into its compiled buckets)
    ctl = None
    ctls = []

    def _mk_controller():
        return ctl_mod.VerifyController(
            BUCKET, 2, 0.75, min_batch=2, batch_ceiling=4 * BUCKET,
            max_pipeline_depth=4, hysteresis=2, cooldown=2)

    # --replicas N (ISSUE 17): the same chaos-mesh scenario, but the
    # submission front is the deterministic fleet router over N
    # VerifyService replicas sharing the one engine — the kill below
    # exercises drain/handoff, the standing divergence detector runs
    # on its route cadence, and the fleet conservation law must stay
    # exact through all of it
    fl = None
    svc = None
    if replicas > 0:
        from stellar_tpu.crypto import fleet as fleet_mod
        shared = fleet_mod.SharedVerifier(v)
        svcs = []
        for i in range(replicas):
            cl = _mk_controller() if ramp else None
            if cl is not None:
                ctls.append(cl)
            svcs.append(vs.VerifyService(
                # per-lane depth (ISSUE 17): rendezvous affinity
                # pins the WHOLE scp key on one replica, so that
                # replica's scp queue must absorb the full scp burst
                # while bulk stays shallow enough that the shed
                # ladder still fires under the flood
                verifier=shared,
                lane_depth={"scp": 24 * replicas, "auth": 24,
                            "bulk": 24},
                lane_bytes=2_000_000, max_batch=BUCKET,
                pipeline_depth=2, aging_every=4, controller=cl,
                control_every=4))
        fl = fleet_mod.FleetRouter(services=svcs,
                                   divergence_every=16).start()
    else:
        if ramp:
            ctl = _mk_controller()
        svc = vs.VerifyService(
            verifier=v, lane_depth=24, lane_bytes=2_000_000,
            max_batch=BUCKET, pipeline_depth=2, aging_every=4,
            controller=ctl, control_every=4).start()
    front = fl if fl is not None else svc

    # the flapping chip: every 2nd dispatch attributed to device 0
    # raises — quarantine, re-shard over survivors, half-open regrow,
    # fail again (docs/robustness.md per-device fault domains)
    faults.set_fault(faults.DISPATCH, "flaky-device", 0)
    event("fault", spec="device.dispatch=flaky-device:0")

    if signers == "zipf":
        zpool, zwant, weighted = _zipf_pool(400 if smoke else 1200)

        def pick(i, n):
            return _zipf_submission(zpool, zwant, weighted, i, n)
    else:
        pool, want = _signed_pool()

        def pick(i, n):
            return _submission(pool, want, i, n)
    results = {"bulk": {"tickets": [], "rejected": 0},
               "scp": {"tickets": [], "rejected": 0}}
    flooder_stats = {"rejected": 0, "quota_rejected": 0,
                     "submitted": 0}
    lock = threading.Lock()

    # --ingress (ISSUE 19): the submission front door becomes the
    # WIRE — a real IngressServer on a loopback socket in front of
    # the service/fleet, the flood threads real WireClients, and the
    # flooder a deliberately MISBEHAVING socket client cycling the
    # five wire fault shapes (faults.WIRE_MODES). Admission refusals
    # then arrive as typed REFUSAL frames at drain time instead of
    # synchronous raises at submit time.
    ingress_srv = None
    wire_clients = {}
    pack_stats = {"ms": 0.0, "n": 0}
    flooder_wire = {"cli": None, "conns": 0}
    if ingress:
        from stellar_tpu.crypto import ingress as ingress_mod
        from stellar_tpu.utils import wire
        ingress_srv = ingress_mod.IngressServer(front)
        ingress_srv.start()
        for ln in ("bulk", "scp"):
            wire_clients[ln] = ingress_mod.WireClient(
                "127.0.0.1", ingress_srv.port)

    def wire_submit(cli, items, lane, tenant):
        """One wire submission with the encode timed — ``pack_ms`` is
        the host-side serialization cost the bench record quotes
        (measured HERE: the scoped ingress module reads no clocks)."""
        tkt = cli.reserve(lane, tenant, len(items))
        t0 = time.perf_counter()
        data = wire.encode_submit(items, lane, tenant, tkt.req_id)
        dt_ms = (time.perf_counter() - t0) * 1000.0
        with lock:
            pack_stats["ms"] += dt_ms
            pack_stats["n"] += 1
        return cli.send_encoded(tkt, data)

    def flood(lane, count, per_sub, pace_s, offset=0):
        for i in range(count):
            items, exp = pick(i + offset, per_sub)
            tenant = None
            if tenants > 0 and lane == "bulk":
                tenant = "t%03d" % ((i + offset) % tenants)
            try:
                if ingress_srv is not None:
                    tkt = wire_submit(wire_clients[lane], items,
                                      lane, tenant)
                else:
                    tkt = front.submit(items, lane=lane,
                                       tenant=tenant)
                with lock:
                    results[lane]["tickets"].append((tkt, exp))
            except vs.Overloaded as e:
                assert e.kind == "rejected", e.kind
                with lock:
                    results[lane]["rejected"] += 1
            if pace_s:
                time.sleep(pace_s)

    def flood_tenant(count, per_sub, offset=0):
        """The adversarial flooder: unpaced bulk bursts under ONE
        tenant id — its quota (not the lane budget) must absorb it.
        Under ``--ingress`` it is a REAL misbehaving socket client:
        every 25th submission re-arms the next wire fault shape, and
        whenever the server kills (or a fault closes) its connection
        it reconnects and keeps flooding."""
        for i in range(count):
            items, exp = pick(i + offset, per_sub)
            with lock:
                flooder_stats["submitted"] += 1
            if ingress_srv is not None:
                from stellar_tpu.crypto import ingress as ingress_mod
                mode = faults.WIRE_MODES[
                    (i // 25) % len(faults.WIRE_MODES)]
                # slow-client at the default 4 KiB/s would stall the
                # round join; the shape (chunked sends with sleeps
                # between) is what matters, not the starvation rate
                arg = 262144.0 if mode == "slow-client" else None
                faults.set_fault("wire.flooder", mode, arg)
                cli = flooder_wire["cli"]
                if cli is None or not cli.alive:
                    if cli is not None:
                        cli.close()
                    try:
                        cli = ingress_mod.WireClient(
                            "127.0.0.1", ingress_srv.port,
                            fault_point="wire.flooder")
                    except OSError:
                        continue
                    flooder_wire["cli"] = cli
                    flooder_wire["conns"] += 1
                try:
                    tkt = wire_submit(cli, items, "bulk", "flooder")
                    with lock:
                        results["bulk"]["tickets"].append((tkt, exp))
                except (ConnectionError, OSError):
                    pass        # ticket failed typed; reconnect above
                continue
            try:
                tkt = front.submit(items, lane="bulk",
                                   tenant="flooder")
                with lock:
                    results["bulk"]["tickets"].append((tkt, exp))
            except vs.Overloaded as e:
                assert e.kind == "rejected", e.kind
                with lock:
                    flooder_stats["rejected"] += 1
                    if e.reason.startswith("tenant-"):
                        flooder_stats["quota_rejected"] += 1

    killed_idx = None
    killed_moved = 0
    max_scp_burn = 0.0
    flood_rounds = 1 if smoke else max(1, int(duration_s / 3.0))
    if ramp:
        # a midpoint needs at least two rounds; the second half
        # offers DOUBLE the load (the conservation law must stay
        # exact through the shift — every extra submission is still
        # verified, rejected or shed, never lost)
        flood_rounds = max(2, flood_rounds)
    sched = ramp_schedule(flood_rounds, 150)
    breaker_tripped = False
    t_run = time.monotonic()
    for rnd in range(flood_rounds):
        # burst well past the bulk lane's depth budget: ingress
        # rejects AND backlog shed are both certain
        bulk = threading.Thread(
            target=flood,
            args=("bulk", sched[rnd] if ramp else 150, 4, 0.002,
                  rnd * 1000))
        scp = threading.Thread(
            target=flood, args=("scp", 25, 2, 0.02, rnd * 1000))
        threads = [bulk, scp]
        if tenants > 0 and flooder:
            threads.append(threading.Thread(
                target=flood_tenant, args=(120, 4, rnd * 1000)))
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if fl is not None and killed_idx is None and \
                rnd >= (flood_rounds - 1) // 2:
            # kill one replica mid-soak while its queues are loaded:
            # the drain/handoff protocol must move every queued
            # ticket to a survivor with trace IDs intact — zero loss
            ksnap = fl.snapshot()
            cands = [i for i, stt in enumerate(ksnap["states"])
                     if stt in ("active", "probation")]
            if len(cands) > 1:
                killed_idx = cands[-1]
                killed_moved = fl.kill_replica(killed_idx,
                                               stop_timeout=60)
                event("replica-kill", replica=killed_idx,
                      handoff_items=killed_moved)
        if not breaker_tripped:
            # mid-run correlated outage: the OPEN global breaker is
            # shed-ladder level 2 (dispatch-degraded) until its
            # half-open probe re-closes it
            bv._breaker.trip()
            breaker_tripped = True
            event("breaker-trip", round=rnd)
        if corrupt and not smoke and rnd == flood_rounds // 2:
            faults.set_fault(faults.RESOLVE, "corrupt-device", 2)
            event("fault", spec="device.resolve=corrupt-device:2")
        max_scp_burn = max(max_scp_burn, vs.slo_health()[
            "lanes"]["scp"]["latency"]["burn_rate"])
        event("round", n=rnd,
              service=front.snapshot()["totals"])

    # drain: every outstanding ticket resolves to verified or shed
    # (wire mode adds two typed terminals: REFUSAL frames carrying
    # admission rejections, and connection errors on the flooder's
    # deliberately killed sockets — never on a well-behaved client)
    mismatches = 0
    shed = {"bulk": 0, "scp": 0}
    verified_items = 0
    wire_dead = 0
    wire_dead_good = 0
    for lane in ("bulk", "scp"):
        for tkt, exp in results[lane]["tickets"]:
            try:
                got = tkt.result(timeout=120)
            except vs.Overloaded as e:
                if ingress_srv is not None and e.kind == "rejected":
                    results[lane]["rejected"] += 1
                    if getattr(tkt, "tenant", None) == "flooder":
                        flooder_stats["rejected"] += 1
                        if e.reason.startswith("tenant-"):
                            flooder_stats["quota_rejected"] += 1
                    continue
                assert e.kind == "shed", e.kind
                shed[lane] += 1
                continue
            except (ConnectionError, OSError, RuntimeError):
                wire_dead += 1
                if getattr(tkt, "tenant", None) != "flooder":
                    wire_dead_good += 1
                continue
            verified_items += len(got)
            if not (got == exp).all():
                mismatches += 1
    ingress_snap = None
    if ingress_srv is not None:
        for cli in wire_clients.values():
            cli.close()
        if flooder_wire["cli"] is not None:
            flooder_wire["cli"].close()
        ingress_srv.stop()
        ingress_snap = ingress_srv.snapshot()
    front.stop(drain=True, timeout=60)
    fault_counters = faults.counters()   # captured BEFORE clear
    faults.clear()
    wall_s = round(time.monotonic() - t_run, 1)

    fsnap = None
    if fl is not None:
        fsnap = fl.snapshot()
        lane_counts = {ln: {"shed": 0, "rejected": 0}
                       for ln in vs.LANES}
        for s_ in fl.services():
            rsnap = s_.snapshot()
            for ln in vs.LANES:
                for k in lane_counts[ln]:
                    lane_counts[ln][k] += rsnap["lanes"][ln][k]
        snap = {"conservation_gap": fsnap["conservation_gap"],
                "pending_items": fsnap["pending_items"],
                "totals": fsnap["totals"],
                "lanes": lane_counts}
    else:
        snap = svc.snapshot()
    lanes = vs.lane_latencies()
    totals = snap["totals"]
    meters = {k: registry.meter(f"crypto.verify.service.{k}").count
              for k in ("submitted", "verified", "rejected", "shed",
                        "failed", "handoff")}
    prom = registry.to_prometheus()
    health = bv.dispatch_health()
    event("final", totals=totals, lanes=lanes, wall_s=wall_s)

    problems = []
    if snap["conservation_gap"] != 0 or snap["pending_items"] != 0:
        problems.append(
            f"conservation violated: gap={snap['conservation_gap']} "
            f"pending={snap['pending_items']}")
    if totals["failed"] != 0:
        problems.append(f"failed items: {totals['failed']}")
    if totals["submitted"] != (totals["verified"] + totals["rejected"]
                               + totals["shed"]
                               + totals.get("handoff", 0)):
        problems.append(
            "submitted != verified + rejected + shed + handoff")
    if meters != {k: totals[k] for k in meters}:
        problems.append(
            f"service counters disagree with metrics: {meters} "
            f"vs {totals}")
    if totals["rejected"] == 0 or results["bulk"]["rejected"] == 0:
        problems.append("Overloaded ingress rejection never exercised")
    if totals["shed"] == 0 or shed["bulk"] == 0:
        problems.append("bulk lane never shed under overload")
    if shed["scp"] or snap["lanes"]["scp"]["shed"] or \
            snap["lanes"]["scp"]["rejected"]:
        problems.append("scp lane was shed/rejected — priority broken")
    # N replicas share the one engine, so absolute waits scale with
    # the replica count; lane PRIORITY (the relative gate below) is
    # what the fleet must preserve (ISSUE 17).
    scp_bound = SMOKE_SCP_P99_BOUND_MS * max(1, replicas)
    if lanes["scp"]["count"] == 0 or \
            lanes["scp"]["p99_ms"] > scp_bound:
        problems.append(
            f"scp p99 unbounded (bound {scp_bound}): {lanes['scp']}")
    if lanes["bulk"]["count"] and \
            lanes["scp"]["p99_ms"] > lanes["bulk"]["p99_ms"]:
        problems.append("scp lane waited longer than bulk at p99")
    if mismatches:
        problems.append(
            f"{mismatches} verified tickets mismatched the oracle")
    fc = fault_counters.get("device.dispatch", {})
    if not fc.get("fired"):
        problems.append("flaky-device:0 never fired — no flap soaked")
    if "crypto_verify_service" not in prom:
        problems.append("service metrics missing from the Prometheus "
                        "exposition")

    # ---- zipf-signer scenario record + gates (--signers zipf) ----
    signer_rec = None
    if signers == "zipf":
        st = health["signer_tables"]
        hot_rows = registry.meter(
            "crypto.verify.signer_table.hot_rows").count
        cold_rows = registry.meter(
            "crypto.verify.signer_table.cold_rows").count
        variant_shapes = sorted(
            {n for kerns in v._kernels_variants.values()
             for n in kerns})
        signer_rec = {
            "distinct_signers": len(zpool) - 2,
            "cache": st,
            "hot_rows": hot_rows,
            "cold_rows": cold_rows,
            "variant_kernel_shapes": variant_shapes,
        }
        if not st["enabled"]:
            problems.append(
                "signer-table cache disabled — zipf proved nothing")
        if st["hits"] == 0 or hot_rows == 0:
            problems.append(
                "zipf flood never hit the signer-table cache — hot "
                f"rate is 0 ({st})")
        if st["installs"] == 0:
            problems.append(
                "zipf flood never installed a signer table")
        if not set(variant_shapes) <= {SUB, BUCKET}:
            problems.append(
                "hot kernel compiled beyond the pinned bucket "
                f"shapes: {variant_shapes} vs {{{SUB}, {BUCKET}}}")

    # ---- ramp scenario record + gates (--ramp) ----
    ramp_rec = None
    if ramp and fl is not None:
        csnaps = [c.snapshot() for c in ctls]
        ramp_rec = {
            "schedule": sched,
            "windows": sum(c["windows"] for c in csnaps),
            "moves": sum(c["moves"] for c in csnaps),
            "knobs": csnaps[0]["knobs"],
            "log_tail": ctls[0].control_log(limit=16),
        }
        if ramp_rec["windows"] == 0:
            problems.append(
                "fleet ramp ran but no replica's controller ever "
                "evaluated a window — the batch-cadence hook is dead")
    elif ramp:
        csnap = ctl.snapshot()
        ramp_rec = {
            "schedule": sched,
            "windows": csnap["windows"],
            "moves": csnap["moves"],
            "knobs": csnap["knobs"],
            "log_tail": ctl.control_log(limit=16),
        }
        if csnap["windows"] == 0:
            problems.append(
                "ramp ran but the controller never evaluated a "
                "window — the batch-cadence hook is dead")
        log = ctl.control_log()
        if log and log[0][1] == 1 and \
                ctl.replay(ctl.windows()) != log:
            # replay is exact while the retained history is complete
            # (first entry still seq 1 — no deque eviction yet)
            problems.append(
                "controller replay diverged from the live trajectory")

    # ---- fleet scenario record + gates (--replicas N) ----
    fleet_rec = None
    if fl is not None:
        fleet_rec = {
            "replicas": replicas,
            "states": fsnap["states"],
            "killed": killed_idx,
            "handoff_items": killed_moved,
            "handoffs": fsnap["handoffs"],
            "router_refused": fsnap["router_refused"],
            "divergence_checks": fsnap["divergence_checks"],
            "convictions": fsnap["divergence_convictions"],
            "conservation_gap": fsnap["conservation_gap"],
            "max_scp_burn": round(max_scp_burn, 4),
        }
        if killed_idx is None:
            problems.append(
                "fleet soak never killed a replica — the "
                "drain/handoff path went unexercised")
        elif fsnap["states"][killed_idx] != "dead":
            problems.append(
                f"killed replica {killed_idx} not dead: "
                f"{fsnap['states']}")
        if fsnap["divergence_checks"] == 0:
            problems.append(
                "fleet divergence detector never ran")
        if fsnap["divergence_convictions"] != 0:
            problems.append(
                "healthy fleet convicted a replica (divergence "
                f"false positive): {fsnap['conviction_log']}")
        if fsnap["router_refused"] != 0:
            problems.append(
                "router refused submissions while replicas were "
                f"routable ({fsnap['router_refused']} items)")

    # ---- wire-ingress scenario record + gates (--ingress) ----
    ingress_rec = None
    if ingress_snap is not None:
        isnap = ingress_snap
        ingress_rec = {
            "frames": isnap["decoded_frames"],
            "malformed_frames": isnap["malformed_frames"],
            "malformed_reasons": isnap["malformed_reasons"],
            "items": isnap["items_decoded"],
            "ingress_bytes": isnap["bytes_in"],
            "bytes_out": isnap["bytes_out"],
            "conservation_gap": isnap["conservation_gap"],
            "pending": isnap["pending"],
            "connections_total": isnap["connections_total"],
            "flooder_connections": flooder_wire["conns"],
            "wire_killed_tickets": wire_dead,
            "pack_ms": {
                "count": pack_stats["n"],
                "total_ms": round(pack_stats["ms"], 3),
                "avg_ms": round(
                    pack_stats["ms"] / max(1, pack_stats["n"]), 5),
            },
            "pool": isnap["pool"],
        }
        if isnap["conservation_gap"] != 0:
            problems.append(
                "wire-ingress conservation violated: "
                f"gap={isnap['conservation_gap']}")
        if isnap["pending"] != 0:
            problems.append(
                "wire-ingress pending nonzero after drain: "
                f"{isnap['pending']}")
        if wire_dead_good:
            problems.append(
                f"{wire_dead_good} well-behaved wire tickets died on "
                "connection errors — only the misbehaving flooder's "
                "sockets may be killed")
        if flooder:
            if isnap["malformed_frames"] == 0:
                problems.append(
                    "wire flooder armed but no malformed frame ever "
                    "reached the server — the fault shapes are dead")
            wfc = fault_counters.get("wire.flooder", {})
            if not wfc.get("fired"):
                problems.append(
                    "wire.flooder fault point never fired")

    # ---- tenant scenario gates (--tenants N [--flooder]) ----
    tenant_rec = None
    if tenants > 0:
        if fl is not None:
            # per-tenant counters aggregate across replicas — each
            # replica's own conservation is exact, so the sums are too
            agg = {}
            for s_ in fl.services():
                for t, c in s_.tenant_snapshot()["tenants"].items():
                    a = agg.setdefault(t, {k: 0 for k in c})
                    for k, val in c.items():
                        a[k] += val
            tsnap = {
                "tenants": agg,
                "tracked": len(agg),
                "conservation_violations": {
                    t: c["conservation_gap"] for t, c in agg.items()
                    if c["conservation_gap"] != 0},
            }
        else:
            tsnap = svc.tenant_snapshot()
        tfc = tsnap["tenants"].get("flooder") or {}
        tenant_rec = {
            "tenants": tsnap["tracked"],
            "conservation_violations":
                tsnap["conservation_violations"],
            "flooder": tfc or None,
            "flooder_ingress": dict(flooder_stats),
            "slo_top": tn.tenant_slo.publish_topk(),
        }
        if tsnap["conservation_violations"]:
            problems.append(
                "per-tenant conservation violated: "
                f"{tsnap['conservation_violations']}")
        if any(c["pending"] for c in tsnap["tenants"].values()):
            problems.append("per-tenant pending nonzero after drain")
        if flooder:
            if not (tfc.get("quota_rejected") or tfc.get("shed")):
                problems.append(
                    "flooder quota never exhausted (no typed "
                    "rejections or sheds)")
            if tfc.get("failed"):
                problems.append(
                    f"flooder items FAILED ({tfc['failed']}) — "
                    "exhaustion must be typed, not fatal")
        # restore the process-global tenant knobs: run() is importable
        # (bench/report tooling), so the scenario must not leave its
        # quotas behind for the rest of the process
        tn.clear_tenant_policies()
        tn.configure_tenants(depth=tenant_knobs_saved[0],
                             nbytes=tenant_knobs_saved[1],
                             window=tenant_knobs_saved[2])

    return {
        "ok": not problems,
        "mode": "smoke" if smoke else "soak",
        "wall_s": wall_s,
        "warm_s": warm_s,
        "devices": len(devs),
        "totals": totals,
        "conservation_gap": snap["conservation_gap"],
        "shed_onsets": registry.counter(
            "crypto.verify.service.shed_onsets").count,
        "lane_latency_ms": lanes,
        "verified_items": verified_items,
        "ingress_rejected_submissions": {
            ln: results[ln]["rejected"] for ln in results},
        "shed_submissions": shed,
        "fault_counters": fault_counters,
        "breaker": health["breaker"]["state"],
        "quarantines": health["device_health"]["transitions_total"],
        "flight_recorder_dumps": health["flight_recorder"][
            "dump_reasons"],
        "events_path": events_path,
        "tenant": tenant_rec,
        "ramp": ramp_rec,
        "fleet": fleet_rec,
        "ingress": ingress_rec,
        "signer_tables": signer_rec,
        "problems": problems,
    }


BENCH_SERVICE_CAPTURE = os.path.join(
    REPO, "docs", "bench_service_capture.json")
TELEMETRY_REPORT = os.path.join(REPO, "docs", "telemetry_report.md")


def emit_telemetry_report(path: str) -> None:
    """Render this window's telemetry (time-series + pipeline
    bubbles + SLO budgets + top traces) into one markdown report
    (ISSUE 10 — ``tools/telemetry_report.py`` is the renderer; the
    soak harness is its live-window producer)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import telemetry_report
    text = telemetry_report.render_report(
        telemetry_report.collect_local(),
        title="Soak-window telemetry report")
    with open(path, "w") as f:
        f.write(text)


def emit_bench_service(rec: dict, path: str) -> None:
    """Persist this soak window's per-lane p50/p99 + conservation
    totals as the capture ``bench.py`` embeds in its next record's
    ``service`` section (ISSUE 8 satellite — the ROADMAP's "live
    window capture of bench.py's service record section"). Only a
    GREEN verify-workload window is worth regression-guarding; a red
    one fails the run anyway."""
    import datetime
    cap = {
        "recorded_at": datetime.datetime.now(
            datetime.timezone.utc).strftime("%Y-%m-%dT%H:%M:%SZ"),
        "source": "tools/soak.py",
        "mode": rec["mode"],
        "devices": rec["devices"],
        "wall_s": rec["wall_s"],
        "service": {
            "lane_latency_ms": rec["lane_latency_ms"],
            "totals": rec["totals"],
            "conservation_gap": rec["conservation_gap"],
            "shed_onsets": rec["shed_onsets"],
            "ingress_rejected_submissions":
                rec["ingress_rejected_submissions"],
            "shed_submissions": rec["shed_submissions"],
        },
    }
    if rec.get("fleet"):
        # ISSUE 17 sentinel rows — FLEET windows only: the fleet
        # conservation residual is a hard zero and conviction counts
        # are note-only (they legitimately vary with injected
        # Byzantine scenarios). Absent from non-fleet captures, so
        # the sentinel skips instead of flaking.
        cap["fleet"] = {
            "replicas": rec["fleet"]["replicas"],
            # magnitude: the sentinel's max_abs rule is a one-sided
            # ceiling, and a NEGATIVE residual (double-count) is just
            # as fatal as a positive one (lost work)
            "conservation_gap": abs(rec["fleet"]["conservation_gap"]),
            "divergence_convictions": rec["fleet"]["convictions"],
            "divergence_checks": rec["fleet"]["divergence_checks"],
            "handoffs": rec["fleet"]["handoffs"],
        }
    if rec.get("ingress"):
        # ISSUE 19 sentinel rows — WIRE-INGRESS windows only: the
        # wire-level conservation residual is a hard zero (every byte
        # that became a decoded item lands in exactly one typed
        # terminal), malformed-frame counts are note-only (they vary
        # with the armed fault shapes), and ingress_bytes/pack_ms are
        # the bench quantities docs/benchmarks.md documents. Absent
        # from non-ingress captures, so the sentinel skips instead of
        # flaking.
        ing = rec["ingress"]
        cap["ingress"] = {
            "conservation_gap": abs(ing["conservation_gap"]),
            "malformed_frames": ing["malformed_frames"],
            "frames": ing["frames"],
            "items": ing["items"],
            "ingress_bytes": ing["ingress_bytes"],
            "pack_ms": ing["pack_ms"]["avg_ms"],
        }
    if rec.get("ramp"):
        # ISSUE 15 sentinel rows — CONTROLLER windows only: the scp
        # latency burn ceiling (max_abs 1.0) gates the closed-loop
        # story, and the legacy soak deliberately trips the global
        # breaker mid-run with no controller attached, so its scp
        # waits can burn the 5 s SLO bound inside a legitimately
        # green window (its own gate is the looser
        # SMOKE_SCP_P99_BOUND_MS). Rows absent from non-ramp captures
        # skip in the sentinel instead of flaking tier-1.
        from stellar_tpu.crypto import verify_service as vs
        slo = vs.slo_health()
        cap["service"]["slo"] = {
            "scp": {"latency_burn_rate":
                    slo["lanes"]["scp"]["latency"]["burn_rate"]}}
        cap["service"]["control"] = {
            "decisions": rec["ramp"].get("moves", 0)}
    with open(path, "w") as f:
        json.dump(cap, f, indent=1, sort_keys=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="short CPU-only tier-1 gate mode")
    ap.add_argument("--duration", type=float, default=30.0,
                    help="flood duration (non-smoke), seconds")
    ap.add_argument("--corrupt", action="store_true",
                    help="also inject corrupt-device:2 mid-run "
                         "(audit -> host-only -> ladder level 2)")
    ap.add_argument("--events", default=None,
                    help="JSONL event-log path (size-capped, rotated)")
    ap.add_argument("--real-device", action="store_true",
                    help="don't force the CPU backend (live windows)")
    ap.add_argument("--tenants", type=int, default=0,
                    help="stripe the bulk flood across N synthetic "
                         "tenants with per-tenant quotas (0 = legacy "
                         "un-tenanted scenario); verify workload only")
    ap.add_argument("--flooder", action="store_true",
                    help="with --tenants: add one adversarial "
                         "flooding tenant whose quota (not the lane) "
                         "must absorb its burst — typed rejections/"
                         "sheds, zero failures, per-tenant "
                         "conservation exact")
    ap.add_argument("--replicas", type=int, default=0,
                    help="front the soak with a FleetRouter over N "
                         "VerifyService replicas and kill one mid-run "
                         "(ISSUE 17); 0 = single service")
    ap.add_argument("--ingress", action="store_true",
                    help="front the soak with the streaming wire "
                         "ingress (ISSUE 19): flood threads become "
                         "real loopback WireClients, the --flooder "
                         "tenant a misbehaving socket client cycling "
                         "the five wire fault shapes; gates wire "
                         "conservation gap == 0 and records "
                         "ingress_bytes/pack_ms for the bench "
                         "capture; verify workload only")
    ap.add_argument("--ramp", action="store_true",
                    help="double the offered bulk load at the midpoint"
                         " and attach the closed-loop controller "
                         "(ISSUE 15) — the load shift must be "
                         "absorbed by knob moves with the "
                         "conservation law still exact; verify "
                         "workload only")
    ap.add_argument("--signers", default="pool",
                    choices=("pool", "zipf"),
                    help="flood signer distribution: the 6-key "
                         "rotating pool (default) or a zipf-ranked "
                         "corpus of hundreds of DISTINCT signers — "
                         "the repeat-signer regime the per-pubkey "
                         "table cache (ISSUE 16) serves; gates hot "
                         "hit rate > 0 and no kernel compiles beyond "
                         "the pinned buckets; verify workload only")
    ap.add_argument("--workload", default="verify",
                    choices=("verify", "sha256"),
                    help="which engine plugin to soak: the verify "
                         "service flood (default) or the SHA-256 "
                         "hasher through the same flaky-device flap")
    ap.add_argument("--emit-bench-service", nargs="?",
                    const=BENCH_SERVICE_CAPTURE, default=None,
                    metavar="PATH",
                    help="on a green verify run, write the per-lane "
                         "p50/p99 + conservation capture bench.py "
                         "embeds as its service record section "
                         f"(default path: {BENCH_SERVICE_CAPTURE})")
    ap.add_argument("--emit-telemetry-report", nargs="?",
                    const=TELEMETRY_REPORT, default=None,
                    metavar="PATH",
                    help="render this window's telemetry "
                         "(time-series + pipeline bubbles + SLO "
                         "burn rates + top traces) into one markdown "
                         f"report (default path: {TELEMETRY_REPORT})")
    args = ap.parse_args()
    events = args.events or (
        "/tmp/_soak_events.jsonl" if args.smoke
        else os.path.join(REPO, "SOAK_EVENTS.jsonl"))
    _env_setup(args.real_device)
    if args.emit_telemetry_report:
        # sample the soak window itself: the report's time-series
        # section reads this ring (ISSUE 10)
        from stellar_tpu.utils.metrics import timeseries
        timeseries.start(interval_s=0.25)
    if args.workload == "sha256":
        rec = run_sha256(args.smoke, args.duration, events)
    else:
        rec = run(args.smoke, args.duration, args.corrupt, events,
                  tenants=args.tenants, flooder=args.flooder,
                  ramp=args.ramp, signers=args.signers,
                  replicas=args.replicas, ingress=args.ingress)
    if args.emit_bench_service and args.workload == "verify" \
            and rec["ok"]:
        emit_bench_service(rec, args.emit_bench_service)
        rec["bench_service_capture"] = args.emit_bench_service
    if args.emit_telemetry_report:
        from stellar_tpu.utils.metrics import timeseries
        timeseries.stop()
        emit_telemetry_report(args.emit_telemetry_report)
        rec["telemetry_report"] = args.emit_telemetry_report
    print(json.dumps(rec))
    return 0 if rec["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
