#!/usr/bin/env bash
# Tier-1 verification gate — the EXACT command from ROADMAP.md, so the
# builder and the reviewer run the identical check. Prints DOTS_PASSED=<n>
# (count of passing-test dots in the pytest progress lines) and exits with
# pytest's status.
#
# Usage: bash tools/tier1.sh    (from the repo root or anywhere)

set -o pipefail
cd "$(dirname "$0")/.." || exit 2
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
    -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log \
    | tr -cd . | wc -c)
# Chaos gate: the fault-injection dispatch suite must ALSO pass when
# selected by marker alone (CPU-safe — faults are injected, no device
# needed). The cheap chaos tests already ran inside the sweep above
# ('not slow' includes them); this pass additionally runs the
# chaos+slow PER-DEVICE fault-domain lifecycle (a forced 4-device
# subprocess, tests/test_chaos_device_domains.py) exactly once — its
# driver pays up to 4 per-device kernel compiles on a cold
# compilation cache (~6 min; warm reruns are seconds), hence this
# gate's larger budget.
rm -f /tmp/_t1_chaos.log
timeout -k 10 780 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m chaos -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 \
    | tee /tmp/_t1_chaos.log
crc=${PIPESTATUS[0]}
echo CHAOS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' \
    /tmp/_t1_chaos.log | tr -cd . | wc -c)
# Per-device fault-domain chaos count (ISSUE 4): how many of the chaos
# tests just gated above exercise the per-device quarantine /
# re-shard / audit machinery. Collection only — their pass/fail is
# already pinned by the chaos gate's exit status.
echo DEVICE_CHAOS=$(timeout -k 5 120 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_chaos_device_domains.py -q -m chaos \
    --collect-only -p no:cacheprovider 2>/dev/null | grep -c '::')
# Hash-workload differential count (ISSUE 7): how many of the sweep's
# tests pin the SHA-256 kernel bit-identical to hashlib across the
# edge corpus, every hash bucket size, padding lanes, and the oversize
# host path. Collection only — their pass/fail is already pinned by
# the main gate's exit status above.
echo HASH_DIFF_OK=$(timeout -k 5 120 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_hash_differential.py -q -m 'not slow' \
    --collect-only -p no:cacheprovider 2>/dev/null | grep -c '::')
# A red pytest/chaos gate exits here: its output is already printed,
# and burning ~10 more minutes on the bucket sweep would bury it.
[ "$rc" -ne 0 ] && exit $rc
[ "$crc" -ne 0 ] && exit $crc
# Static-analysis gate (ISSUE 3): the jaxpr overflow prover must prove
# all three verify-kernel stages at EVERY jit bucket size against the
# committed envelope golden (docs/limb_bounds.json), the SHA-256
# workload kernel at every hash bucket size against its own golden
# (docs/sha256_bounds.json, ISSUE 7), and the
# hot-path/lock-discipline/nondet lints must be clean
# (docs/static_analysis.md). Fails the tier-1 gate on any open finding.
_alog=$(mktemp)
timeout -k 10 590 env JAX_PLATFORMS=cpu python tools/analyze.py | tee "$_alog"
arc=${PIPESTATUS[0]}
echo ANALYSIS_RC=$arc
# Lock-order + proof-coverage gate lines (ISSUE 18), lifted from the
# analyze transcript: LOCKORDER_OK counts open lock-cycle /
# hold-and-block / stale-allowlist findings (0 = clean) and
# PROOF_COVERAGE_OK counts proven kernel variants (0 = gate failed) —
# both visible from the tier-1 transcript alone, next to ANALYSIS_RC.
echo "$(grep -o '^LOCKORDER_OK=[0-9]*' "$_alog" | tail -1)"
echo "$(grep -o '^PROOF_COVERAGE_OK=[0-9]*' "$_alog" | tail -1)"
rm -f "$_alog"
# Kernel-cost ledger gate width (ISSUE 13): how many ledger rows the
# cost suite enforces (tools/kernel_cost.py ENFORCED_LEDGER_ROWS,
# asserted row-by-row in tests/test_kernel_cost.py, trend-gated by the
# perf sentinel). Pass/fail is already pinned by the pytest gate
# above; this echoes the enforced width so a PR that silently drops
# ledger rows is visible from the tier-1 transcript alone.
echo KERNEL_COST_OK=$(python -c "import sys; sys.path.insert(0, '.'); \
from tools.kernel_cost import ENFORCED_LEDGER_ROWS as R; print(len(R))" \
    2>/dev/null || echo 0)
[ "$arc" -ne 0 ] && exit $arc
# Metrics/trace export self-check (ISSUE 5): a synthetic host-only
# resolve must produce a complete per-phase dispatch_attribution whose
# span sum reconciles with the blocking root span (>= 95%), and the
# Prometheus exposition of the registry must parse. Seconds of wall
# time, no device, no kernel compile.
timeout -k 10 240 env JAX_PLATFORMS=cpu python tools/metrics_selfcheck.py
mrc=$?
echo METRICS_EXPORT_OK=$([ "$mrc" -eq 0 ] && echo 1 || echo 0)
[ "$mrc" -ne 0 ] && exit $mrc
# Multi-tenant QoS gate (ISSUE 14): a thousand-tenant synthetic soak
# with one adversarial flooder against the resident verify service —
# host-only (stub verifier, no jax), seconds of wall time. Gates: the
# flooder's quota is exhausted via TYPED rejections/sheds (never
# failures) while every other tenant's latency and shed budgets stay
# inside objective, per-tenant work conservation holds exactly
# (submitted == verified + rejected + shed + failed + pending for
# every tenant), two replicas under identical arrival order emit
# bit-identical shed/dispatch decision sequences, weighted fair
# shares converge 4:2:1, and the rank-keyed tenant gauges stay
# bounded (the metric-cardinality guard).
timeout -k 10 240 python tools/tenant_selfcheck.py
tqrc=$?
echo TENANT_QOS_OK=$([ "$tqrc" -eq 0 ] && echo 1 || echo 0)
[ "$tqrc" -ne 0 ] && exit $tqrc
# Closed-loop control gate (ISSUE 15): a ramped synthetic soak
# (offered bulk load x2 at the midpoint — the shared soak.py
# ramp_schedule shape) against the resident service with the
# deterministic feedback controller attached — host-only (paced stub
# verifier, no jax), seconds of wall time. Gates: scp latency burn
# rate stays <= 1.0 with ZERO human knob turns, the controller
# demonstrably moved at least one knob inside its clamps, two
# replicas over the identical window sequence emit bit-identical
# control_log() sequences (and reproduce the live trajectory — the
# replay procedure), conservation holds exactly through the load
# shift, and controller.py carries no nondet allowlist entry.
timeout -k 10 240 python tools/control_selfcheck.py
ctrc=$?
echo CONTROL_OK=$([ "$ctrc" -eq 0 ] && echo 1 || echo 0)
[ "$ctrc" -ne 0 ] && exit $ctrc
# Replicated verify fleet (ISSUE 17): N=3 VerifyService replicas
# behind the deterministic FleetRouter on the forced-4-device chaos
# mesh under flooder load — one replica killed mid-run with zero lost
# tickets, fleet conservation exact, scp burn <= 1.0 throughout; two
# independent routers route bit-identically; a bit-flipped decision
# log is convicted and quarantined, then re-admitted on probation;
# fleet.py sits in BOTH lint scopes with no allowlist entry.
timeout -k 10 560 python tools/fleet_selfcheck.py
flrc=$?
echo FLEET_OK=$([ "$flrc" -eq 0 ] && echo 1 || echo 0)
[ "$flrc" -ne 0 ] && exit $flrc
# Streaming wire ingress (ISSUE 19): the zero-copy wire front door.
# Codec phase: SUBMIT/VERDICT/REFUSAL round trips, a torn-frame fuzz
# sweep over EVERY byte split point (decode identically or die typed,
# never desync), two independent servers refusing byte-identically.
# Throughput phase: >= 100k items/s of real loopback wire traffic
# through a 3-replica stub fleet WHILE five misbehaving clients (one
# per faults.WIRE_MODES shape) hammer the same listener — with the
# wire conservation law EXACT at every live snapshot. Drain phase:
# mid-flood replica kill + server stop with every wire ticket reaching
# a typed terminal (zero unresolved). Chaos phase (subprocess): the
# full forced-4-device soak with the wire ingress as front door
# (tools/soak.py --ingress) — conservation exact at BOTH layers, the
# misbehaving wire flooder's frames killed typed, no well-behaved
# client harmed. Lint phase: ingress.py + wire.py in both lint scopes
# and the lock-order graph with ZERO allowlist entries.
timeout -k 10 560 python tools/ingress_selfcheck.py
inrc=$?
echo INGRESS_OK=$([ "$inrc" -eq 0 ] && echo 1 || echo 0)
[ "$inrc" -ne 0 ] && exit $inrc
# Verify-service soak smoke (ISSUE 6): a short CPU-only overload run
# of the resident verify service (forced 4-device subprocess,
# flaky-device:0 injected, audit sampling on, mid-run breaker trip)
# must uphold the work-conservation law EXACTLY (submitted ==
# verified + rejected + shed, zero unaccounted drops), keep the
# SCP-priority lane's p99 bounded while the bulk lane sheds, and
# exercise a typed Overloaded ingress rejection. Reuses the
# device-domain chaos gate's compiled shapes + persistent cache, so
# after the chaos gate above this pays loads, not compiles
# (~1 min warm; a cold cache can take ~4 min, hence the budget).
timeout -k 10 560 env JAX_PLATFORMS=cpu python tools/soak.py --smoke
src=$?
# Second-workload soak (ISSUE 7): the SHA-256 plugin through the SAME
# flaky-device flap — quarantine, re-shard, breaker trip, audit
# sampling — with every digest pinned to hashlib. The hash kernel
# compiles in seconds (scan-based), so this adds ~1 min cold, seconds
# warm. SOAK_OK covers BOTH workloads.
hsrc=1
if [ "$src" -eq 0 ]; then
    timeout -k 10 300 env JAX_PLATFORMS=cpu \
        python tools/soak.py --smoke --workload sha256
    hsrc=$?
fi
echo SOAK_OK=$([ "$src" -eq 0 ] && [ "$hsrc" -eq 0 ] && echo 1 || echo 0)
[ "$src" -ne 0 ] && exit $src
[ "$hsrc" -ne 0 ] && exit $hsrc
# Perf-drift sentinel (ISSUE 8): the last two BENCH_r*.json records
# diffed against the typed tolerance rules (kernel-cost ledgers,
# analysis proof state, attribution coverage, transfer-ledger totals,
# per-lane p50/p99 — docs/observability.md "Perf sentinel"). Pure
# JSON comparison, sub-second; a kernel/cost/coverage regression that
# reached a committed bench record fails the gate here instead of
# passing silently.
timeout -k 10 120 env JAX_PLATFORMS=cpu python tools/perf_sentinel.py
prc=$?
echo PERF_DRIFT_OK=$([ "$prc" -eq 0 ] && echo 1 || echo 0)
[ "$prc" -ne 0 ] && exit $prc
# Transfer-ledger reconciliation: forced-4-device chaos
# resolves (SHA-256 workload, flaky-device:0 armed). The detector
# phase must convict a re-shipped batch (nonzero redundant bytes),
# the steady-state window of fresh batches must not be convicted
# (redundant bytes under half of h2d), and the ledger's byte totals
# must reconcile >= 95% against the engine's own shape-derived
# accounting — a placement path without a ledger hook fails here as
# a byte gap.
# Reuses the chaos gate's persistent jax cache: seconds warm, ~1 min
# cold.
rm -f /tmp/_t1_transfer.log
timeout -k 10 420 env JAX_PLATFORMS=cpu \
    python tools/transfer_selfcheck.py 2>&1 | tee /tmp/_t1_transfer.log
trc=${PIPESTATUS[0]}
echo TRANSFER_LEDGER_OK=$([ "$trc" -eq 0 ] && echo 1 || echo 0)
# steady-state re-upload bytes of fresh batches (the repeated
# per-row active masks of same-length messages; report only)
echo TRANSFER_REDUNDANT_BYTES=$(grep -a '^{' /tmp/_t1_transfer.log \
    | tail -1 | python -c "import json,sys; \
print(json.loads(sys.stdin.readline()).get('redundant_constant_bytes'))" \
    2>/dev/null)
[ "$trc" -ne 0 ] && exit $trc
# Pipeline-bubble profiler (ISSUE 10 + the ISSUE 12 async loop): the
# forced-4-device chaos resolves must (a) attribute an injected
# inter-dispatch stall (stall-device:1) AND an injected h2d transfer
# stall (stall-transfer:1) as queue_wait bubbles standing out above a
# clean resolve's floor, (b) measure overlap_frac >= 0.5 on a
# multi-sub-chunk PIPELINED resolve — chunk k+1's host prep hidden
# behind chunk k's in-flight device work, the async-dispatch win
# itself, echoed below so a regression is visible at a glance — and
# (c) reconcile busy + attributed bubbles >= 95% of n_devices x wall
# (record wall pinned against an independent clock), with the
# crypto.pipeline.* metrics riding the Prometheus exposition and the
# time-series ring sampling concurrently without raising or tearing.
# Same shapes + persistent cache as the chaos gate: seconds warm,
# ~1 min cold.
rm -f /tmp/_t1_pipeline.log
timeout -k 10 420 env JAX_PLATFORMS=cpu \
    python tools/pipeline_selfcheck.py 2>&1 | tee /tmp/_t1_pipeline.log
porc=${PIPESTATUS[0]}
echo PIPELINE_OBS_OK=$([ "$porc" -eq 0 ] && echo 1 || echo 0)
# the async-dispatch acceptance number (>= 0.5 enforced by the
# selfcheck's exit status above)
echo PIPELINE_OVERLAP_FRAC=$(grep -a '^{' /tmp/_t1_pipeline.log \
    | tail -1 | python -c "import json,sys; \
print(json.loads(sys.stdin.readline()).get('overlap_frac'))" \
    2>/dev/null)
[ "$porc" -ne 0 ] && exit $porc
# Hot-signer table cache (ISSUE 16): a zipf stream over >1000 distinct
# signers on the forced-4-device mesh. Gates: the traced ledger's hot
# dsm arm executes >= 20% fewer MACs/call than cold, two cold-cache
# replicas emit bit-identical verdicts AND identical hot/cold
# partitions, the whole sweep compiles ZERO kernel shapes beyond the
# pinned sub-chunk executable (for BOTH kernel variants), steady-state
# cached-table re-dispatches ride the hot kernel on every row with the
# transfer ledger reconciled, and a tiny byte budget forces real LRU
# evictions while the zipf head keeps hitting. Reuses the chaos gate's
# persistent jax cache: ~2 min warm, ~4 min cold.
rm -f /tmp/_t1_hotsigner.log
timeout -k 10 560 env JAX_PLATFORMS=cpu \
    python tools/hot_signer_selfcheck.py 2>&1 \
    | tee /tmp/_t1_hotsigner.log
hrc=${PIPESTATUS[0]}
echo HOT_SIGNER_OK=$([ "$hrc" -eq 0 ] && echo 1 || echo 0)
# the acceptance number: executed-MAC savings of the hot arm vs cold
echo HOT_SIGNER_SAVINGS_FRAC=$(grep -a '^{' /tmp/_t1_hotsigner.log \
    | tail -1 | python -c "import json,sys; \
print(json.loads(sys.stdin.readline())['dsm_macs'].get('savings_frac'))" \
    2>/dev/null)
[ "$hrc" -ne 0 ] && exit $hrc
# Unified system journal + cross-replica trace stitching (ISSUE 20):
# a flooded 3-replica wire fleet with a mid-run replica kill. Gates:
# 100% of sampled verdict traces reconstruct wire->verdict including
# handoff hops (stitch_frac == 1.0), the journal completeness gap is
# exactly 0 against the fleet+ingress conservation counters, two
# independently-merged journals are bit-identical over deterministic
# components, and journal.py is scoped by both lints with no
# allowlist entry. Host-only (stub verifiers): ~1 min.
rm -f /tmp/_t1_journal.log
timeout -k 10 300 python tools/journal_selfcheck.py 2>&1 \
    | tee /tmp/_t1_journal.log
jrc=${PIPESTATUS[0]}
echo JOURNAL_OK=$([ "$jrc" -eq 0 ] && echo 1 || echo 0)
# the acceptance numbers: stitched fraction + completeness residual
echo JOURNAL_STITCH_FRAC=$(grep -a '^{' /tmp/_t1_journal.log \
    | tail -1 | python -c "import json,sys; \
print(json.loads(sys.stdin.readline())['chaos'].get('stitch_frac'))" \
    2>/dev/null)
exit $jrc
