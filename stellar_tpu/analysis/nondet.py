"""Consensus nondeterminism lint, on the shared lint framework.

Reference ``src/test/check-nondet``: a CI grep banning ``std::rand`` /
unseeded randomness from consensus code. The consensus-critical packages
must not consult wall clocks, unseeded RNGs, or per-process hash salts —
any of those is a consensus-divergence hazard between nodes.

This PR moves the pass out of ``tests/test_nondet_lint.py`` (which now
just drives it) onto the shared framework — same file walking, same
allowlist format with mandatory written safety arguments, same JSON
report through ``tools/analyze.py`` — and extends coverage to the
``stellar_tpu/crypto`` host-oracle modules: the failover path re-verifies
signatures through these (``docs/robustness.md`` — "degraded mode changes
latency, never decisions"), so their decisions must be exactly as
deterministic as the consensus packages'.
"""

from __future__ import annotations

import re
from typing import List, Optional

from stellar_tpu.analysis.lint_base import (
    Allowlist, Finding, LintReport, finish_report, repo_root, walk_py,
)
# quote-aware '#' stripping: a '#' inside a string literal must not
# truncate the line before a banned call that follows it
from stellar_tpu.utils.toml_compat import _strip_comment

__all__ = ["run", "lint_source", "drift_findings", "CONSENSUS_DIRS",
           "HOST_ORACLE_FILES", "DRIFT_ROOTS", "ALLOWLIST", "BANNED",
           "TRACING_SANCTIONED"]

# packages whose behavior must be bit-identical across nodes
CONSENSUS_DIRS = ["stellar_tpu/scp", "stellar_tpu/ledger",
                  "stellar_tpu/tx", "stellar_tpu/bucket",
                  "stellar_tpu/soroban", "stellar_tpu/xdr"]

# crypto host-oracle modules: the host half of every verify decision
# (policy gates, SHA-512 prep, the failover oracle) plus the pure
# primitives under them — one nondeterministic branch here and the
# device and host halves of a verdict could disagree
HOST_ORACLE_FILES = [
    # the result-integrity audit sampler and the quarantine registry:
    # both gate WHICH backend serves a consensus verdict — the sample
    # must be content-derived and the quarantine logic clock/RNG-free,
    # or replicas could diverge in what they re-verify
    "stellar_tpu/crypto/audit.py",
    "stellar_tpu/parallel/device_health.py",
    # the resident verify service decides WHICH queued work gets
    # verified vs shed under overload — the shed rule must stay
    # content-seeded (audit.keep_under_shed) and the scheduler
    # sequence-based, never clocked or RNG-driven
    "stellar_tpu/crypto/verify_service.py",
    # the tenant QoS layer (ISSUE 14): per-tenant quotas, the
    # weighted-fair scheduler's virtual-time accounting, and the
    # tenant-keyed shed fractions all decide WHICH tenant's work
    # dispatches or sheds — pure integer/content arithmetic, zero
    # clock reads, NO allowlist entry (pinned in test_analysis.py)
    "stellar_tpu/crypto/tenant.py",
    # the closed-loop controller (ISSUE 15): its decisions move the
    # service's scheduling knobs (batch size, pipeline depth, shed
    # highwater), so it must be a pure function of the telemetry
    # window it is handed — zero clock reads, NO allowlist entry
    # (pinned in test_analysis.py), or two replicas' knob
    # trajectories could diverge under identical inputs
    "stellar_tpu/crypto/controller.py",
    # the fleet router (ISSUE 17): routing, probation re-admission
    # and divergence conviction all decide WHICH replica serves a
    # submission — pure SHA-256 rendezvous draws over event-count
    # state, zero clock reads, NO allowlist entry (pinned in
    # test_analysis.py), or two independently constructed routers
    # could route the same stream differently (the per-replica
    # breakers keep their clocks inside resilience.py; they are a
    # metric surface, never a routing input)
    "stellar_tpu/crypto/fleet.py",
    # the wire ingress + frame codec (ISSUE 19): what arrived, what
    # was malformed, what was refused and which trace block each
    # frame got must be pure functions of the byte stream — NO
    # allowlist entry (pinned in test_analysis.py), so read deadlines
    # ride socket timeouts and event counts, never a clock read, and
    # two nodes decoding the same bytes always agree
    "stellar_tpu/crypto/ingress.py",
    "stellar_tpu/utils/wire.py",
    # the unified system journal (ISSUE 20): merge order, the
    # completeness residual and the canonical bytes must be pure
    # functions of the logs they are handed — one clock or RNG draw
    # and two replicas' merged journals could differ while both are
    # honest, which is exactly the divergence the merge is built to
    # convict. NO allowlist entry (pinned in test_analysis.py)
    "stellar_tpu/utils/journal.py",
    # the workload-agnostic batch engine owns dispatch, re-shard,
    # audit-sample composition, and host-oracle failover for EVERY
    # plugin — a clock or RNG here would desynchronize which rows any
    # replica audits or sheds, for all workloads at once
    "stellar_tpu/parallel/batch_engine.py",
    # the SHA-256 workload: kernel host helpers (padding/encode) and
    # the hasher plugin feed bucket-list and catchup hashes that must
    # be bit-identical across nodes
    "stellar_tpu/ops/sha256.py",
    "stellar_tpu/crypto/batch_hasher.py",
    # the transfer ledger records every engine upload/fetch and the
    # perf sentinel gates bench-record drift in tier-1: both must stay
    # clock/RNG-free — fingerprints and drift verdicts are
    # content-derived, so two runs over the same bytes always agree
    "stellar_tpu/utils/transfer_ledger.py",
    "tools/perf_sentinel.py",
    # the per-pubkey signer-table cache (ISSUE 16) decides which rows
    # dispatch HOT vs cold: keys must be content-derived and eviction
    # clock/RNG-free — verdicts are path-independent (pinned by the
    # differential suite), but the hot/cold split must still replay
    # identically or replicas' ledgers and audits drift apart
    "stellar_tpu/parallel/signer_tables.py",
    # the trickle batcher + verify collector (ISSUE 18 scope-drift
    # sweep): composes the reference oracle, native prep and the
    # signer-table partitioner into batch verdicts — its one clock
    # (trickle window pacing) decides WHEN a batch dispatches, never
    # what any row's verdict is (allowlisted below)
    "stellar_tpu/crypto/batch_verifier.py",
    # transport sealed boxes over curve25519 (ISSUE 18 scope-drift
    # sweep): pure HSalsa/HMAC composition, zero clock/RNG reads of
    # its own — NO allowlist entry (pinned in test_analysis.py)
    "stellar_tpu/crypto/nacl_box.py",
    "stellar_tpu/crypto/ed25519_ref.py",
    "stellar_tpu/crypto/curve25519.py",
    "stellar_tpu/crypto/keys.py",
    "stellar_tpu/crypto/native_prep.py",
    "stellar_tpu/crypto/native_verify.py",
    "stellar_tpu/crypto/sha.py",
    "stellar_tpu/crypto/keccak.py",
    "stellar_tpu/crypto/shorthash.py",
    "stellar_tpu/crypto/strkey.py",
    "stellar_tpu/crypto/secp256.py",
    "stellar_tpu/crypto/h2c.py",
    "stellar_tpu/crypto/bls12_381.py",
]

BANNED = [
    # (key, pattern, why)
    ("random", re.compile(
        r"\brandom\.(random|randint|randrange|choice|shuffle|"
        r"getrandbits)\b"),
     "unseeded process RNG in consensus code"),
    ("os.urandom", re.compile(r"\bos\.urandom\b"),
     "CSPRNG output must not influence consensus state"),
    ("secrets", re.compile(
        r"\bsecrets\.(token_bytes|randbits|randbelow)\b"),
     "CSPRNG output must not influence consensus state"),
    ("clock", re.compile(
        r"\btime\.time\(\)|\btime\.monotonic\(\)|"
        r"\btime\.perf_counter\(\)"),
     "wall/monotonic clock reads diverge between nodes"),
    ("wallclock", re.compile(
        r"\bdatetime\.now\(\)|\bdatetime\.utcnow\(\)"),
     "wall clock reads diverge between nodes"),
    # bare builtin hash( — NOT .hash() methods (content hashes)
    ("hash", re.compile(r"(?<![.\w])hash\("),
     "builtin hash() is salted per-process (PYTHONHASHSEED)"),
]

# ---------------- tracing fence (ISSUE 5) ----------------
# stellar_tpu/utils/tracing.py is clock-bearing BY DESIGN (perf_counter
# pairs, span records, the flight recorder). Consensus/host-oracle
# modules may use only its duration-blind context managers — zone/span
# etc. time a scope but never EXPOSE a duration to the caller, so their
# clock reads cannot influence a decision. Importing the module itself
# (or any other name, e.g. ``flight_recorder`` or ``span_totals``)
# would hand consensus code readable clock state and is banned.
TRACING_SANCTIONED = frozenset({
    "zone", "span", "LogSlowExecution", "current_zones", "frame_mark",
})

_TRACING_MODULE = re.compile(
    r"^\s*import\s+stellar_tpu\.utils\.tracing\b")
# from stellar_tpu.utils import a, (tracing), ... — names checked
# after paren accumulation, so the parenthesized spelling can't slip
# the module in
_UTILS_FROM = re.compile(
    r"^\s*from\s+stellar_tpu\.utils\s+import\s+(.*)$")
_TRACING_FROM = re.compile(
    r"^\s*from\s+stellar_tpu\.utils\.tracing\s+import\s+(.*)$")


def _lint_tracing_imports(text: str, rel: str) -> List[Finding]:
    """Fence tracing out of consensus modules: only the sanctioned
    duration-blind names may be imported. Handles parenthesized
    multi-line from-imports (the ``ledger_manager`` spelling)."""
    out: List[Finding] = []

    def emit(lineno: int, what: str):
        out.append(Finding(
            file=rel, line=lineno, rule="nondet", symbol="tracing-import",
            message=f"{what} — tracing is clock-bearing by design; "
                    "consensus modules may import only its "
                    "duration-blind context managers "
                    f"({', '.join(sorted(TRACING_SANCTIONED))})"))

    lines = text.splitlines()

    def gather_names(first: str, i: int) -> tuple:
        """Imported names of one from-import, accumulating BOTH
        continuation spellings — parenthesized and backslash-continued
        lines; returns (names, next_i)."""
        src = first
        while i + 1 < len(lines) and (
                ("(" in src and ")" not in src)
                or src.rstrip().endswith("\\")):
            i += 1
            src = src.rstrip().rstrip("\\") + " " + \
                _strip_comment(lines[i])
        names = [tok.split(" as ")[0].strip()
                 for tok in src.replace("(", " ").replace(")", " ")
                 .replace("\\", " ").split(",")]
        return [nm for nm in names if nm], i

    i = 0
    while i < len(lines):
        lineno = i + 1
        line = _strip_comment(lines[i])
        if _TRACING_MODULE.match(line):
            emit(lineno, "module-level tracing import")
            i += 1
            continue
        m = _TRACING_FROM.match(line)
        if m is not None:
            names, i = gather_names(m.group(1), i)
            bad = [nm for nm in names
                   if nm not in TRACING_SANCTIONED]
            if bad:
                emit(lineno, "import of non-sanctioned tracing "
                             f"names {bad}")
            i += 1
            continue
        m = _UTILS_FROM.match(line)
        if m is not None:
            names, i = gather_names(m.group(1), i)
            if "tracing" in names:
                emit(lineno, "module-level tracing import")
        i += 1
    return out

ALLOWLIST = Allowlist({
    # (the seed's allowlist carried a stale tx_test_utils.py entry for
    # secrets.token_bytes — the code it excused is gone; the framework
    # now fails on stale entries, which is how it surfaced)
    "stellar_tpu/crypto/keys.py": {
        "nondet:clock":
            "sign_ops_per_second/verify_ops_per_second mirror the "
            "reference's SecretKey::benchmarkOpsPerSecond "
            "(SecretKey.cpp:193-233): perf_counter pairs measuring a "
            "benchmark loop's own wall time, returned to operators/"
            "bench tooling only — no verify decision or ledger state "
            "ever reads them.",
        "nondet:os.urandom":
            "SecretKey.random()/PublicKey generation: key MATERIAL, "
            "not consensus state — randomness here is the whole point "
            "and never feeds a verify decision (decisions depend only "
            "on the resulting public bytes).",
        "nondet:random":
            "SecretKey.pseudo_random_for_testing mirrors the "
            "reference's test-only generator (SecretKey.h:66-77); "
            "test fixtures, never ledger state.",
    },
    "stellar_tpu/crypto/curve25519.py": {
        "nondet:os.urandom":
            "X25519 ephemeral keypair generation for transport "
            "encryption (overlay auth) — key material consumed only "
            "by the local handshake, never consensus state.",
    },
    "stellar_tpu/crypto/shorthash.py": {
        "nondet:os.urandom":
            "per-process siphash key, mirroring the reference's "
            "shortHash::initialize(): short hashes are process-local "
            "(hashmap seeding) and never cross the wire or enter "
            "consensus state.",
    },
    "stellar_tpu/crypto/verify_service.py": {
        "nondet:clock":
            "time.monotonic() stamps admission and completion for the "
            "per-lane wait-time histograms (the p50/p99 the soak "
            "harness publishes) and the SLO latency accounting that "
            "consumes the SAME stamp (burn rates feed dashboards "
            "only), and ages the adopter cool-down "
            "window (service_verified's wedged-dispatcher bypass). "
            "Neither reads decide a VERDICT: admission verdicts "
            "depend on bounded queue/byte budgets, scheduling order "
            "on priorities plus admission sequence numbers, WHICH "
            "rows shed on the content-seeded rule in crypto/audit.py "
            "(replicas under identical pressure shed identical rows), "
            "and the cool-down only picks WHICH bit-identical path "
            "serves a signature check (service lane vs direct "
            "verify_sig) — the differential gates pin both paths to "
            "the same bools, so a clock-driven bypass can never "
            "diverge replicas' consensus state.",
    },
    "stellar_tpu/crypto/batch_verifier.py": {
        "nondet:clock":
            "time.perf_counter() pairs pace the trickle-batch "
            "window (how long the leader waits for co-riders before "
            "dispatching) — the clock decides WHEN a batch goes to "
            "the device, never WHAT any row's verdict is: verdicts "
            "come from the device kernel or the host oracle, both "
            "pinned bit-identical by the differential gates, so "
            "window jitter can only move latency, not decisions.",
        "nondet:tracing-import":
            "the verify collector is an instrumentation owner like "
            "batch_engine: it opens collection/dispatch spans and "
            "notes trace events for the flight recorder — durations "
            "land in observability records only; verdict composition "
            "reads device/oracle bits, never a span reading.",
    },
    "stellar_tpu/parallel/batch_engine.py": {
        "nondet:clock":
            "time.monotonic() ages the device-probe thread (overdue "
            "probe accounting) — local liveness bookkeeping deciding "
            "only WHICH backend serves, never what a row's verdict "
            "is: device and host-oracle answers are pinned "
            "bit-identical by the differential gates and the sampled "
            "audit, so a clock-driven backend flip cannot diverge "
            "replicas' consensus state.",
        "nondet:tracing-import":
            "the engine IS the instrumentation owner the fence "
            "protects consensus code from: it opens the resolve-phase "
            "spans, dumps the flight recorder on breaker/quarantine/"
            "shed onsets, and feeds dispatch_attribution — durations "
            "land in observability records only, while row verdicts "
            "are composed from device/oracle bits plus the "
            "content-seeded audit sample, never a span reading.",
    },
})


def _lint_lines(text: str, rel: str) -> List[Finding]:
    out: List[Finding] = []
    in_dunder_hash = False
    for lineno, line in enumerate(text.splitlines(), 1):
        if "def " in line:
            # hash() inside __hash__ feeds per-process dict/set
            # identity only — never consensus state
            in_dunder_hash = "def __hash__" in line
        elif line and not line[0].isspace():
            # any module-level statement ends the __hash__ body
            in_dunder_hash = False
        stripped = _strip_comment(line)  # ignore comments (quote-aware)
        for key, pat, why in BANNED:
            m = pat.search(stripped)
            if not m:
                continue
            if key == "hash" and (in_dunder_hash or
                                  re.match(r"\s*def hash\(", stripped)):
                continue
            out.append(Finding(
                file=rel, line=lineno, rule="nondet", symbol=key,
                message=f"{m.group(0)!r} — {why}"))
    return out


def lint_source(src: str, rel: str) -> List[Finding]:
    """Lint one source text (unit-test hook)."""
    return _lint_lines(src, rel) + _lint_tracing_imports(src, rel)


# Where the scope-drift meta-lint looks: the host-oracle package
# itself. A crypto module that composes other host-oracle modules is
# part of the oracle and must be scoped; importers OUTSIDE the package
# (overlay auth, tx validation) consume verdicts, they don't produce
# them, so they stay out of this rule.
DRIFT_ROOTS = ["stellar_tpu/crypto"]

_ORACLE_IMPORT = re.compile(
    r"^\s*(?:from\s+stellar_tpu\.crypto\s+import\s+(?P<names>.+)|"
    r"(?:from\s+)?(?:import\s+)?stellar_tpu\.crypto\.(?P<dotted>\w+))")


def drift_findings(scope: Optional[List[str]] = None) -> List[Finding]:
    """Scope-drift meta-lint: a module in ``stellar_tpu/crypto`` that
    imports a host-oracle crypto module but is itself absent from
    :data:`HOST_ORACLE_FILES` composes oracle primitives outside the
    nondeterminism fence — new crypto files can no longer silently
    escape the lint. One finding per offending module, at its first
    oracle import."""
    scoped = set(HOST_ORACLE_FILES if scope is None else scope)
    oracle_stems = {f.rsplit("/", 1)[-1][:-3] for f in scoped
                    if f.startswith("stellar_tpu/crypto/")}
    root = repo_root()
    out: List[Finding] = []
    for path in walk_py(DRIFT_ROOTS, root):
        rel = str(path.relative_to(root))
        if rel in scoped:
            continue
        for lineno, line in enumerate(
                path.read_text().splitlines(), 1):
            m = _ORACLE_IMPORT.match(_strip_comment(line))
            if not m:
                continue
            if m.group("dotted"):
                names = [m.group("dotted")]
            else:
                names = [tok.split(" as ")[0].strip() for tok in
                         m.group("names").split(",")]
            hit = sorted(set(names) & oracle_stems)
            if hit:
                out.append(Finding(
                    file=rel, line=lineno, rule="scope-drift",
                    symbol="host-oracle-import",
                    message=f"imports host-oracle module(s) {hit} "
                            "but is not in nondet.HOST_ORACLE_FILES "
                            "— add it (with written allowlist "
                            "arguments for any clock/RNG use) so new "
                            "crypto composition stays inside the "
                            "nondeterminism fence"))
                break
    return out


def run(allowlist: Optional[Allowlist] = None) -> LintReport:
    allowlist = allowlist or ALLOWLIST
    root = repo_root()
    findings: List[Finding] = []
    files = 0
    for path in walk_py(CONSENSUS_DIRS + HOST_ORACLE_FILES, root):
        rel = str(path.relative_to(root))
        files += 1
        text = path.read_text()
        findings.extend(_lint_lines(text, rel))
        findings.extend(_lint_tracing_imports(text, rel))
    findings.extend(drift_findings())
    return finish_report("nondet", files, findings, allowlist)
