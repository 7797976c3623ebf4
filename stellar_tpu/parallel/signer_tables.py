"""Per-pubkey precomputed A-table cache for hot signers (PR 16).

The consensus workload is dominated by a SMALL signer set — hundreds
of validators sign nearly all SCP envelopes and peer-auth traffic —
yet every verify rebuilds the signer's window table from scratch
inside the kernel (``build_point_table_affine``, ~10% of the dsm MAC
budget, and the narrow 16-entry windows it forces cost far more in
doublings). This module caches, per 32-byte pubkey, the 128-entry
affine cached table of multiples of ``-A`` that the hot-path kernel
(:func:`stellar_tpu.ops.verify.verify_kernel_hot`) consumes as a plain
operand: repeat signers skip the in-kernel build entirely AND run
byte-aligned radix-256 windows the live build could never afford
(docs/kernel_design.md §5 carries the amortization math).

Shape discipline:

* keys are CONTENT fingerprints (SHA-256 of the pubkey encoding, no
  salts, no clocks — two replicas always cache the same signers given
  the same traffic);
* the byte budget (``VERIFY_SIGNER_TABLE_BYTES``, Config-pushed by
  Application like every dispatch knob) bounds host retention with
  recency eviction: hot validators re-hit every batch and stay, one-off
  signers churn through the tail;
* a table evicted by the budget before it served a single lookup
  counts in ``evicted_unused``: a build whose cost bought nothing,
  the waste a long tail of one-off signers makes;
* admission (:meth:`SignerTableCache.admit`): while the budget has
  room a signer's table is built on its first sight; once an install
  would evict, a key must be seen twice before it is built. A
  full-cache first sight only records the key's fingerprint in a
  seen-once set (insertion-ordered, bounded at the number of tables
  the budget holds, oldest dropped first) and counts in ``deferred``;
  its row rides cold, as a first sight always does. A long tail of
  one-off signers then stops building tables LRU flushes unserved,
  and stops flushing the tables that were serving;
* :func:`SignerTableCache.evict` exists for the AUDIT path — a
  ``corrupt-device`` conviction while a cached table served the batch
  evicts that signer's entry (a poisoned resident table must never
  outlive the audit that caught it; the next sight rebuilds from the
  pubkey bytes, which the oracle re-checks row by row).

The cached value is host numpy (``(ENTRIES, 3, 20) int16`` canonical
limbs). Nothing keeps a table on the device: the assembled per-batch
table stack is one operand of the hot dispatch and is uploaded by the
engine's ``_place_operands`` with every call that uses it (about
31 MB for a 2,048-row bucket, far over the transfer ledger's
fingerprint cap, so the ledger counts it bytes-only).

Correctness: an entry is installed only after ``point_decompress``
succeeded and the table rows were derived from the decompressed point
by the pure-Python oracle (:func:`ed25519_ref.affine_table_rows`), so
hot-path rows skip the in-kernel decompression stage with no loss —
cache membership IS the decompression proof. A pubkey that fails
decompression is never cached (and never dispatches hot).

Determinism (nondet-lint scope): content-derived keys, no clocks, no
RNG; recency order and the seen-once set depend only on the call
sequence. All shared state mutates under the instance lock (lock-lint
scope). This module must stay importable WITHOUT jax — the table
builder is pure Python + numpy (``batch_verifier`` defers jax the same
way).
"""

from __future__ import annotations

import hashlib
import os
import threading
from collections import OrderedDict
from typing import Optional

import numpy as np

from stellar_tpu.crypto import ed25519_ref as ref
from stellar_tpu.utils.metrics import registry

__all__ = ["SignerTableCache", "signer_table_cache",
           "build_signer_table", "signer_fingerprint",
           "TABLE_ENTRIES", "TABLE_BYTES",
           "DEFAULT_CACHE_BYTES"]

_NS = "crypto.verify.signer_table"

# Table geometry — MUST match the hot kernel's operand contract
# (ops/edwards.py TABLE_ENTRIES256 / AFFINE_COORDS / fe.NLIMBS; pinned
# by tests). Spelled as literals so this module never imports jax.
TABLE_ENTRIES = 128   # multiples 1..128 of -A (radix-256 windows)
_COORDS = 3           # (Y+X, Y-X, 2d*T), Z == 1 implied
_NLIMBS = 20          # 13-bit limbs of GF(2^255-19)
_LIMB_BITS = 13
_LIMB_MASK = (1 << _LIMB_BITS) - 1
TABLE_BYTES = TABLE_ENTRIES * _COORDS * _NLIMBS * 2  # int16

# Byte budget for cached signer tables (host retention; the device
# copy lives only for the dispatch that uses it). 64 MiB holds ~4.3k
# distinct hot signers at 15 KiB/table — an order of magnitude above
# any validator set. Config pushes VERIFY_SIGNER_TABLE_BYTES through
# configure().
DEFAULT_CACHE_BYTES = int(os.environ.get(
    "VERIFY_SIGNER_TABLE_BYTES", str(64 << 20)))
_ENABLED_DEFAULT = os.environ.get(
    "VERIFY_SIGNER_TABLE_ENABLED", "1") not in ("0", "false", "no")


def signer_fingerprint(pk: bytes) -> bytes:
    """Content key of one signer: SHA-256 of the 32-byte pubkey
    encoding, truncated like the transfer-ledger keys. The
    encoding (not the point) is the key on purpose — a non-canonical
    alias of a cached key must MISS and take the cold path, where the
    host canonical-A gate vetoes it."""
    return hashlib.sha256(pk).digest()[:16]


def _limbs(x: int) -> list:
    """13-bit little-endian limb split of one canonical field element —
    the pure-Python twin of ``field25519.from_int`` (pinned equal by
    tests/test_signer_tables.py; this module must not import jax)."""
    x %= ref.P
    return [(x >> (_LIMB_BITS * i)) & _LIMB_MASK for i in range(_NLIMBS)]


def build_signer_table(pk: bytes) -> Optional[np.ndarray]:
    """Host-build the hot-path table for one pubkey: decompress, negate
    (the kernel computes s*B + h*(-A)), derive the 128 affine cached
    rows with the pure-Python oracle (incremental chain + ONE batched
    inversion, about 3 ms on a TPU v5e host), and pack canonical
    13-bit limbs as ``(TABLE_ENTRIES, 3, 20) int16``. Returns None when the pubkey has
    the wrong length or fails decompression — such a signer is never
    cached and never dispatches hot (the cold path's host gates and
    decompress stage handle it)."""
    if len(pk) != 32:
        return None
    pt = ref.point_decompress(pk)
    if pt is None:
        return None
    neg = (ref.P - pt[0], pt[1], pt[2], (ref.P - pt[3]) % ref.P)
    rows = ref.affine_table_rows(neg, TABLE_ENTRIES)
    out = np.empty((TABLE_ENTRIES, _COORDS, _NLIMBS), dtype=np.int16)
    for i, row in enumerate(rows):
        for j, c in enumerate(row):
            out[i, j] = _limbs(c)
    return out


class _Entry:
    """One cached table and whether it has served a lookup yet."""

    __slots__ = ("table", "served")

    def __init__(self, table: np.ndarray):
        self.table = table
        self.served = False


class SignerTableCache:
    """Process-wide LRU of per-pubkey hot-path tables, byte-bounded.

    Lock-guarded, sentinel-gated byte budget, content-derived keys,
    holding HOST arrays keyed by signer (every table has one
    shape/dtype, so the key is the fingerprint alone), with an
    explicit :meth:`evict` for audit convictions."""

    def __init__(self, max_bytes: int = DEFAULT_CACHE_BYTES,
                 enabled: bool = _ENABLED_DEFAULT):
        self._lock = threading.Lock()
        self._entries: OrderedDict = OrderedDict()  # fp -> _Entry
        self._seen_once: OrderedDict = OrderedDict()  # fp -> None
        self._max_bytes = max(0, int(max_bytes))
        self._enabled = bool(enabled)
        self._hits = 0
        self._misses = 0
        self._installs = 0
        self._evictions = 0
        self._evicted_unused = 0
        self._audit_evictions = 0
        self._deferred = 0

    # ---------------- knobs ----------------

    def configure(self, max_bytes: Optional[int] = None,
                  enabled: Optional[bool] = None) -> None:
        """Config push (VERIFY_SIGNER_TABLE_*); None keeps current.
        Shrinking the budget evicts immediately and trims the seen-once
        set to the new table count; disabling clears both (a table
        must not outlive the decision to stop serving hot — the next
        batch runs all-cold, verdicts unchanged)."""
        with self._lock:
            if max_bytes is not None:
                self._max_bytes = max(0, int(max_bytes))
            if enabled is not None:
                self._enabled = bool(enabled)
                if not self._enabled:
                    self._entries.clear()
                    self._seen_once.clear()
            self._evict_locked()
            self._trim_seen_locked()

    @property
    def enabled(self) -> bool:
        return self._enabled

    # ---------------- the cache ----------------

    def lookup(self, pk: bytes) -> Optional[np.ndarray]:
        """The cached table for this signer, or None (miss/disabled).
        A hit refreshes recency — hot validators never age out."""
        if not self._enabled:
            return None
        fp = signer_fingerprint(pk)
        with self._lock:
            hit = self._entries.get(fp)
            if hit is None:
                self._misses += 1
                registry.counter(f"{_NS}.misses").inc()
                return None
            self._entries.move_to_end(fp)
            hit.served = True
            self._hits += 1
        registry.counter(f"{_NS}.hits").inc()
        return hit.table

    def admit(self, pk: bytes) -> bool:
        """Build this missed signer's table now? True while an install
        would evict nothing (first sight builds, as ever). Once the
        budget is full, a key's first sight records its fingerprint in
        the seen-once set, counts in ``deferred`` and returns False
        (its row rides cold, nothing is built); its next sight while
        still recorded leaves the set and returns True. False when no
        table can be installed at all (disabled, or a budget below
        one table)."""
        fp = signer_fingerprint(pk)
        with self._lock:
            capacity = self._max_bytes // TABLE_BYTES
            if not self._enabled or capacity < 1:
                return False
            if len(self._entries) < capacity:
                return True
            if fp in self._seen_once:
                del self._seen_once[fp]
                return True
            self._seen_once[fp] = None
            self._trim_seen_locked()
            self._deferred += 1
        registry.meter(f"{_NS}.deferred").mark()
        return False

    def install(self, pk: bytes, table: np.ndarray) -> bool:
        """Retain one freshly-built table; returns True when cached.
        Tables are read-only from here on (the flag guards aliasing —
        the same array is handed to every future hot batch)."""
        if not self._enabled or self._max_bytes < TABLE_BYTES:
            return False
        table.setflags(write=False)
        fp = signer_fingerprint(pk)
        with self._lock:
            self._entries.pop(fp, None)
            self._entries[fp] = _Entry(table)
            self._installs += 1
            self._evict_locked()
        registry.counter(f"{_NS}.installs").inc()
        return True

    def evict(self, pk: bytes, reason: str = "audit") -> bool:
        """Drop one signer's entry — the audit-conviction hook: a
        ``corrupt-device`` conviction over a batch a cached table
        served must evict that table (it is re-derived from the pubkey
        on next sight). Returns True when an entry was present."""
        fp = signer_fingerprint(pk)
        with self._lock:
            present = self._entries.pop(fp, None) is not None
            if present:
                self._audit_evictions += 1
        if present:
            registry.counter(f"{_NS}.audit_evictions").inc()
        return present

    def _evict_locked(self) -> None:
        while len(self._entries) * TABLE_BYTES > self._max_bytes \
                and self._entries:
            _fp, entry = self._entries.popitem(last=False)
            self._evictions += 1
            registry.counter(f"{_NS}.evictions").inc()
            if not entry.served:
                self._evicted_unused += 1
                registry.meter(f"{_NS}.evicted_unused").mark()

    def _trim_seen_locked(self) -> None:
        bound = self._max_bytes // TABLE_BYTES
        while len(self._seen_once) > bound:
            self._seen_once.popitem(last=False)

    # ---------------- introspection ----------------

    def snapshot(self) -> dict:
        """Observability payload (``dispatch_health()["signer_tables"]``)."""
        with self._lock:
            return {
                "enabled": self._enabled,
                "entries": len(self._entries),
                "bytes": len(self._entries) * TABLE_BYTES,
                "max_bytes": self._max_bytes,
                "table_bytes": TABLE_BYTES,
                "hits": self._hits,
                "misses": self._misses,
                "installs": self._installs,
                "evictions": self._evictions,
                "evicted_unused": self._evicted_unused,
                "audit_evictions": self._audit_evictions,
                "deferred": self._deferred,
                "seen_once": len(self._seen_once),
            }

    def _reset_for_testing(self) -> None:
        """Drop every table and tally AND restore the knob defaults —
        process-start equivalence (a test that disabled the partition
        or shrank the budget must not leak that into the next test).
        Cumulative registry counters are untouched (the dispatch
        layer's policy)."""
        with self._lock:
            self._entries.clear()
            self._seen_once.clear()
            self._max_bytes = max(0, int(DEFAULT_CACHE_BYTES))
            self._enabled = bool(_ENABLED_DEFAULT)
            self._hits = 0
            self._misses = 0
            self._installs = 0
            self._evictions = 0
            self._evicted_unused = 0
            self._audit_evictions = 0
            self._deferred = 0


# process-wide cache (one node per process — signer hotness is a
# property of the node's traffic, shared by every verifier instance,
# like the device-health registry)
signer_table_cache = SignerTableCache()
