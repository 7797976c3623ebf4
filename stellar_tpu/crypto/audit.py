"""Deterministic sampler for the result-integrity audit.

A corrupting accelerator returns WRONG BITS instead of hanging —
invisible to deadlines and breakers, which only see failures. Hardware
verify engines treat result cross-checking as mandatory for exactly
this reason (FPGA ECDSA verification engines re-verify on an
independent path); the dispatch layer therefore re-verifies a sampled
subset of every device-served chunk through the host oracle
(``docs/robustness.md`` "Sampled result-integrity audit").

The sample must be DETERMINISTIC IN THE BATCH CONTENT: consensus
replicas verifying the same txset must audit the same rows, or one
replica could quarantine its device (and change its serving backend)
on a batch where another did not — a latency divergence that is fine,
but it must never come from per-process randomness that the nondet
lint exists to ban. So indices are derived counter-mode from
SHA-256 of the chunk's encoded bytes, as the workload plugin's
``audit_material`` selects them: all of them by default; the hot
verify path leaves out each row's signer table, which is a function
of the public key that ``h = SHA-512(R‖A‖M) mod L`` already commits
to. Same batch → same sample, on every node, in every process. No
clocks, no RNG state, no hash salts.
"""

from __future__ import annotations

import hashlib
from typing import List, Optional, Sequence

__all__ = ["sample_indices", "sample_rows", "verdict_record",
           "keep_under_shed"]


def sample_indices(material: bytes, n: int, rate: float) -> List[int]:
    """Indices in ``[0, n)`` to audit, derived deterministically from
    ``material`` (the chunk's encoded bytes).

    ``rate <= 0`` disables the audit (empty sample). Otherwise the
    sample size is ``max(1, int(n * rate))`` — at least one row per
    chunk, so even a tiny rate cross-checks every dispatch. ``rate >=
    1`` audits every row (the chaos suite uses this to make a single
    corrupted sub-chunk a guaranteed catch).

    Collisions are resolved by drawing more counters, with a bounded
    budget — the sample may come up slightly short of ``k`` for
    mid-range rates, never over, and stays deterministic.
    """
    if n <= 0 or rate <= 0.0:
        return []
    k = min(n, max(1, int(n * rate + 1e-9)))
    if k >= n:
        return list(range(n))
    digest = hashlib.sha256(material).digest()
    picked: List[int] = []
    seen = set()
    ctr = 0
    budget = 4 * k + 16
    while len(picked) < k and ctr < budget:
        h = hashlib.sha256(digest + ctr.to_bytes(4, "little")).digest()
        idx = int.from_bytes(h[:8], "little") % n
        if idx not in seen:
            seen.add(idx)
            picked.append(idx)
        ctr += 1
    return picked


def sample_rows(material: bytes, eligible_rows: Sequence[int],
                rate: float) -> List[int]:
    """Sample among ELIGIBLE rows only — the rows whose device verdict
    actually decides the composed outcome (host policy gate passed).

    Rows the host policy gate already rejected compare ``False ==
    False`` against the oracle no matter what the device returned —
    sampling them would be vacuous, and since the sample is derived
    from bytes the device holds, a corrupting chip could even predict
    such a blind spot. Restricting to eligible rows keeps
    every drawn sample a REAL cross-check; the eligibility mask is
    host-computed and deterministic, so replicas still agree.

    Returns row indices (in the caller's row numbering), possibly
    empty — a part with no eligible rows needs no audit, because no
    device bit in it can reach a verdict.
    """
    picks = sample_indices(material, len(eligible_rows), rate)
    return [eligible_rows[p] for p in picks]


def keep_under_shed(material: bytes, keep_fraction: float,
                    tenant: bytes = b"") -> bool:
    """Deterministic content-seeded keep/drop draw — the verify
    service's load-shed rule (``docs/robustness.md`` "Overload and
    load-shed"), same discipline as the audit sampler above: under
    identical overload pressure, replicas holding the same queued work
    shed IDENTICAL rows, because the draw is SHA-256 of the work's own
    bytes mapped uniformly into [0, 1) — no clocks, no RNG state, no
    hash salts. The draw itself never depends on queue composition (a
    submission's draw is fixed by its bytes), so survivors keep
    surviving as long as their effective keep fraction holds; only a
    pressure-level or tenant-pressure change in the FRACTION can shed
    a previous survivor.

    ``tenant`` (ISSUE 14) mixes the submitting tenant's key into the
    draw — length-prefixed, so distinct (tenant, material) splits can
    never alias — giving each tenant an independent shed stream: a
    per-tenant keep fraction then sheds a flooding tenant's own rows
    first while replicas still agree row-by-row. The empty key (the
    default/un-tenanted stream) preserves the historical draw bytes
    exactly.

    Returns True = KEEP (verify this work), False = SHED it. The
    boundary cases short-circuit without hashing: ``keep_fraction >=
    1`` keeps everything, ``<= 0`` sheds everything."""
    if keep_fraction >= 1.0:
        return True
    if keep_fraction <= 0.0:
        return False
    if tenant:
        material = (len(tenant).to_bytes(2, "little") + tenant
                    + material)
    h = hashlib.sha256(material).digest()
    draw = int.from_bytes(h[:8], "little") / float(1 << 64)
    return draw < keep_fraction


def verdict_record(device: Optional[int], lo: int, hi: int,
                   sampled: int, ok: bool) -> dict:
    """The evidence shape of one audit verdict, shared by the flight
    recorder's ``verify.audit.verdict`` events and the fault-domain
    payload of multi-chip captures (``tools/multichip_bench.py``)
    — one definition so both streams stay comparable. Pure data: no
    clocks, no RNG (this module is in the nondet-lint scope; consumers
    that need timestamps stamp their own)."""
    return {"device": -1 if device is None else int(device),
            "rows": [int(lo), int(hi)],
            "sampled": int(sampled),
            "ok": bool(ok)}
