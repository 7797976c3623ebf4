"""Host↔TPU bridge for batch ed25519 verification — workload #1 of the
generic batch-dispatch engine.

This is the TPU-native replacement for the reference's verify boundary
(``PubKeyUtils::verifySig``, ``src/crypto/SecretKey.cpp:435-468``): callers
hand over (pubkey, message, signature) triples; they get back a bool per
triple with **bit-identical accept/reject decisions to libsodium's**
``crypto_sign_verify_detached``.

Division of labor (mirrors libsodium's own decomposition):

* host (cheap, byte-level): length checks, canonical-s (s < L),
  canonical-A (y < p), small-order blocklist for R and A — vectorized
  numpy; SHA-512 of R||A||M and reduction mod L — multithreaded C++
  (:mod:`stellar_tpu.crypto.native_prep`), ~12 ms → <1 ms for 2k sigs;
* device (the FLOPs): point decompression + 252-doubling Strauss-Shamir
  double-scalar multiplication + encode-compare, batched over the trailing
  lane axis (:mod:`stellar_tpu.ops.verify`). The device receives only raw
  32-byte A/R/s/h rows (256 KB per 2k sigs) and unpacks scalar digits
  itself.

Since ISSUE 7 the dispatch machinery itself — jit bucket cache,
per-device fault domains + degraded re-shard, circuit breakers,
watchdogged fetches, the sampled result-integrity audit, host-oracle
failover, and span instrumentation — lives in the workload-agnostic
:class:`stellar_tpu.parallel.batch_engine.BatchEngine`;
:class:`BatchVerifier` is the engine driven by the
:class:`Ed25519Workload` plugin, bit-identical in behavior to the
pre-refactor module (every chaos / device-domain / soak gate runs
against this composition). The second workload on the same substrate
is batched SHA-256 (:mod:`stellar_tpu.crypto.batch_hasher`).

``submit`` is the asynchronous half of the API: it dispatches the device
kernel without blocking and returns a resolver, so a caller draining a
queue (herder txset validation, catchup replay) can overlap host prep of
the next batch with device execution of the current one — the "two queue
classes" latency strategy from SURVEY §7.

The process-wide verify-result cache (the reference's 0xffff-entry
``RandomEvictionCache``, ``SecretKey.cpp:44-48,318-338``) lives in
``stellar_tpu.crypto.keys``; :meth:`BatchVerifier.install` wires this
verifier in behind it. Fault tolerance and the result-integrity story
are the engine's (``docs/robustness.md``): degraded mode changes
latency, never decisions, and a corrupting accelerator never decides
signature validity.

For compatibility (tests, tools, the admin surface) this module
re-exports the engine's process-wide dispatch state and functions under
their historical names — ``configure_dispatch``, ``dispatch_health``,
``device_available``, the breaker, the probe state, the knobs.
"""

from __future__ import annotations

import threading
from typing import Callable, Optional, Sequence, Tuple

import numpy as np

from stellar_tpu.crypto import ed25519_ref as ref
from stellar_tpu.crypto import native_prep
from stellar_tpu.parallel import batch_engine, signer_tables
from stellar_tpu.parallel.batch_engine import (  # noqa: F401 (re-exports)
    DEFAULT_BUCKET_SIZES, RESOLVE_PHASES, RESOLVE_ROOT, BatchEngine,
    Workload, _auto_mesh, _breaker, _enter_host_only, _note_device_failure,
    _reset_dispatch_state_for_testing, configure_dispatch, device_available,
    dispatch_attribution, dispatch_degraded, dispatch_health,
    fleet_health_snapshot, host_only_mode, note_shed_onset,
    note_trace_event, register_fleet_health, register_service_health,
    served_counts, service_health_snapshot, start_device_probe,
    trace_ranges,
)
from stellar_tpu.utils import resilience, tracing
from stellar_tpu.utils.metrics import registry

__all__ = ["BatchVerifier", "Ed25519Workload", "Ed25519HotWorkload",
           "default_verifier",
           "device_available", "dispatch_health", "configure_dispatch",
           "dispatch_attribution", "dispatch_degraded",
           "note_shed_onset", "note_trace_event", "trace_ranges",
           "register_service_health", "register_fleet_health",
           "fleet_health_snapshot",
           "RESOLVE_PHASES", "RESOLVE_ROOT"]

_L = ref.L
_P = ref.P

# libsodium's blocklist, as a (14, 32) uint8 matrix for vectorized compare.
_SMALL_ORDER = np.stack([np.frombuffer(e, dtype=np.uint8)
                         for e in sorted(ref.SMALL_ORDER_ENCODINGS)])

_L_BYTES = np.frombuffer(_L.to_bytes(32, "little"), dtype=np.uint8)
_P_BYTES = np.frombuffer(_P.to_bytes(32, "little"), dtype=np.uint8)

# Mutable process-wide dispatch state lives in batch_engine (it is
# shared by every workload); module __getattr__ below forwards reads of
# the historical names (bv.DEADLINE_MS, bv._device_state, bv._probe,
# ...) so existing tests and tools keep working against the live
# values, not stale copies.
_ENGINE_STATE = ("DEADLINE_MS", "DISPATCH_RETRIES", "AUDIT_RATE",
                 "_device_state", "_probe", "_host_only")


def __getattr__(name: str):
    if name in _ENGINE_STATE:
        return getattr(batch_engine, name)
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")


def _host_verify_items(items: Sequence[tuple]) -> np.ndarray:
    """Bit-identical host re-verification of (pk, msg, sig) triples —
    the failover path. Libsodium's policy gate stays the single source
    of truth (``ed25519_ref._policy_gate``); curve equations ride the
    threaded native batch when it built, else the pure oracle."""
    from stellar_tpu.crypto import keys
    out = np.zeros(len(items), dtype=bool)
    good = [i for i, (pk, _m, sg) in enumerate(items)
            if len(pk) == 32 and len(sg) == 64]
    if good:
        res = keys._host_oracle_batch(
            [(None,) + tuple(items[i]) for i in good])
        for i, okv in zip(good, res):
            out[i] = bool(okv)
    return out


def _lt_le_bytes(vals: np.ndarray, bound: np.ndarray) -> np.ndarray:
    """Per-row little-endian comparison vals < bound (vals (B,32) uint8)."""
    # compare from most significant byte down
    v = vals[:, ::-1].astype(np.int16)
    b = bound[::-1].astype(np.int16)
    diff = v - b[None, :]
    nz = diff != 0
    first = np.argmax(nz, axis=1)
    any_nz = nz.any(axis=1)
    picked = diff[np.arange(len(vals)), first]
    return np.where(any_nz, picked < 0, False)  # equal -> not less


def _small_order_mask(enc: np.ndarray) -> np.ndarray:
    """(B, 32) uint8 -> bool (B,) True where encoding is small-order,
    sign bit masked (libsodium ge25519_has_small_order)."""
    masked = enc.copy()
    masked[:, 31] &= 0x7F
    return (masked[:, None, :] == _SMALL_ORDER[None, :, :]).all(-1).any(-1)


class Ed25519Workload(Workload):
    """The ed25519 verify workload: host policy gates + SHA-512 prep in
    ``encode``, the signed-window Strauss-Shamir kernel on device, the
    libsodium-exact host oracle for failover and audit. The gate mask
    is the host policy verdict: a gate-rejected row is False regardless
    of device bits (``finalize`` ANDs it in), exactly libsodium's
    composed decision."""

    metrics_ns = "crypto.verify"
    span_ns = "verify"

    def encode(self, items: Sequence[tuple]
               ) -> Tuple[np.ndarray, tuple]:
        n = len(items)
        ok = np.ones(n, dtype=bool)
        with tracing.span(f"{self.span_ns}.prep.challenge"):
            # one frombuffer over joined bytes instead of three numpy
            # row writes per item — the per-item version was the
            # single biggest host-prep cost at 2k-signature batches
            msgs = []
            pk_parts = []
            sig_parts = []
            z32, z64 = bytes(32), bytes(64)
            for i, (pk, msg, sig) in enumerate(items):
                if len(pk) != 32 or len(sig) != 64:
                    ok[i] = False
                    pk_parts.append(z32)
                    sig_parts.append(z64)
                    msgs.append(b"")
                else:
                    pk_parts.append(pk)
                    sig_parts.append(sig)
                    msgs.append(msg)
            a = np.frombuffer(b"".join(pk_parts),
                              dtype=np.uint8).reshape(n, 32)
            sig_mat = np.frombuffer(b"".join(sig_parts),
                                    dtype=np.uint8).reshape(n, 64)
            r = np.ascontiguousarray(sig_mat[:, :32])
            s = np.ascontiguousarray(sig_mat[:, 32:])
            # h = SHA512(R||A||M) mod L — native multithreaded C++
            h = native_prep.prep_batch(r, a, msgs)
        with tracing.span(f"{self.span_ns}.prep.gate"):
            # host policy checks (libsodium order: s canonical,
            # small-order R/A, canonical A)
            ok &= _lt_le_bytes(s, _L_BYTES)
            ok &= ~_small_order_mask(r)
            ok &= ~_small_order_mask(a)
            a_masked = a.copy()
            a_masked[:, 31] &= 0x7F
            ok &= _lt_le_bytes(a_masked, _P_BYTES)
        return ok, (a, r, s, h)

    def pad_rows(self) -> tuple:
        return (_PAD_A, _PAD_R, _PAD_S, _PAD_H)

    def kernel_fn(self):
        from stellar_tpu.ops import verify as vk
        return vk.verify_kernel

    def empty_result(self, n: int) -> np.ndarray:
        return np.zeros(n, dtype=bool)

    def host_result(self, items: Sequence[tuple]) -> np.ndarray:
        return _host_verify_items(items)

    def finalize(self, gate: np.ndarray, out: np.ndarray,
                 items: Sequence[tuple]) -> np.ndarray:
        return gate & out


class Ed25519HotWorkload(Ed25519Workload):
    """The HOT-SIGNER variant of the verify workload (ISSUE 16): rows
    whose pubkey already has a cached 128-entry affine A-table skip the
    in-kernel decompression + table build and run the byte-aligned
    radix-256 kernel (:func:`stellar_tpu.ops.verify.verify_kernel_hot`)
    with the table as a plain operand — ~24% fewer executed dsm MACs
    per lane than the cold radix-32 path (``tools/kernel_cost.py``
    ``dsm.hot`` vs ``dsm.cold``; docs/kernel_design.md §5).

    Items are ``((pk, msg, sig), table)`` pairs — the triple plus the
    cache entry the partitioning :meth:`BatchVerifier.submit` looked
    up for it. ``encode`` runs the UNCHANGED host policy gates over
    the triples (canonical s/A, small-order, lengths — the gate ANDs
    into the verdict exactly like the cold path), then replaces the
    pubkey operand with the stacked per-row tables. ``host_result``
    and the audit oracle see only the triples, so hot-served rows are
    audited against the very same libsodium-exact oracle as cold ones.

    ``variant_name`` keys this plugin's jit wrappers into the engine's
    per-variant cache: the pinned primary bucket shapes never grow.
    """

    variant_name = "hot"

    def encode(self, items: Sequence[tuple]
               ) -> Tuple[np.ndarray, tuple]:
        ok, (_a, r, s, h) = super().encode([it for it, _t in items])
        with tracing.span(f"{self.span_ns}.prep.gather"):
            tables = np.stack([t for _it, t in items])
        return ok, (tables, r, s, h)

    def audit_material(self, encoded: tuple, lo: int, hi: int) -> bytes:
        # the table is a function of the pubkey, and h = SHA-512(R‖A‖M)
        # mod L already commits to it: (R, S, h) seed the sample as
        # deterministically, without hashing ~15 KB of table per row
        return super().audit_material(encoded[1:], lo, hi)

    def pad_rows(self) -> tuple:
        return (_PAD_TABLE, _PAD_R, _PAD_S, _PAD_H)

    def kernel_fn(self):
        from stellar_tpu.ops import verify as vk
        return vk.verify_kernel_hot

    def host_result(self, items: Sequence[tuple]) -> np.ndarray:
        return _host_verify_items([it for it, _t in items])

    def on_audit_conviction(self, items: Sequence[tuple]) -> None:
        # a corrupt-device conviction over a hot-served part evicts
        # every table that served it: a poisoned resident table must
        # never outlive the audit that caught it (the next sight
        # rebuilds from the pubkey bytes)
        for (pk, _m, _s), _t in items:
            signer_tables.signer_table_cache.evict(pk)


class BatchVerifier(BatchEngine):
    """Batched libsodium-exact ed25519 verifier with a jit bucket cache
    — the :class:`Ed25519Workload` riding the generic engine.

    Args:
      mesh: optional 1-D ``jax.sharding.Mesh``; if given (and it spans
        >= 2 devices), buckets divisible by the device count are split
        into per-device SUB-CHUNKS of the plain kernel — one
        attributable dispatch per device, quarantine/re-shard per
        ``stellar_tpu.parallel.device_health`` — instead of one
        ``shard_map`` call. Non-divisible buckets (and mesh=None) use
        a single whole-bucket dispatch under the global breaker.
      bucket_sizes: padded batch sizes, ascending; each dispatch shape
        compiles once (per serving device on the mesh path).
    """

    def __init__(self, mesh=None, bucket_sizes=(128, 512, 2048)):
        super().__init__(Ed25519Workload(), mesh=mesh,
                         bucket_sizes=bucket_sizes)
        self._hot = Ed25519HotWorkload()

    def submit(self, items: Sequence[tuple], trace_ids=None,
               variant=None) -> Callable[[], np.ndarray]:
        """Partitioning submit: rows whose signer already has a cached
        A-table ride the hot radix-256 kernel variant; the rest ride
        the unchanged cold path — which populates the cache, so a
        signer's FIRST sight is cold and its repeats are hot (once the
        cache is full, from the sight after its second: see
        :meth:`SignerTableCache.admit`). The partition is decided per
        row at encode time from the cache alone (content-keyed,
        deterministic — two replicas fed the same traffic split
        identically); verdicts are bit-identical either way, so the
        split can never change a decision. With the
        cache disabled (``VERIFY_SIGNER_TABLE_ENABLED=0`` /
        ``configure_dispatch(signer_table_enabled=False)``) every row
        rides cold and this is exactly the pre-16 engine submit."""
        cache = signer_tables.signer_table_cache
        if variant is not None or not cache.enabled or not len(items):
            return super().submit(items, trace_ids=trace_ids,
                                  variant=variant)
        hot_idx, hot_items = [], []
        cold_idx, cold_items = [], []
        # the partition (cache traffic + first-sight table builds) is
        # host PREP work: it rides the prep phase span so the blocking
        # root's attribution stays >= 95% covered (METRICS_EXPORT_OK)
        ns = self._span_ns
        with tracing.span(f"{ns}.prep"), \
                tracing.span(f"{ns}.prep.partition"):
            for i, it in enumerate(items):
                pk = it[0]
                tab = cache.lookup(pk) if len(pk) == 32 else None
                if tab is not None:
                    hot_idx.append(i)
                    hot_items.append((it, tab))
                    continue
                cold_idx.append(i)
                cold_items.append(it)
                if len(pk) == 32 and cache.admit(pk):
                    # a miss the cache admits: build + install NOW
                    # (one incremental chain + one batched inversion,
                    # about 3 ms per key on a TPU v5e host) so the
                    # next occurrence — even later in this very
                    # batch — hits; THIS row still rides cold (its
                    # verdict needs the full decompress gate the cold
                    # kernel carries). While the budget has room a
                    # first sight builds; once it is full a key must
                    # be seen twice, so a long tail of one-off
                    # signers builds nothing LRU would flush unserved.
                    # The build sits on the call's critical path,
                    # before any dispatch.
                    with tracing.span(f"{ns}.table_build"):
                        built = signer_tables.build_signer_table(pk)
                        if built is not None:
                            cache.install(pk, built)
        if not hot_items:
            return super().submit(items, trace_ids=trace_ids)
        registry.meter(
            "crypto.verify.signer_table.hot_rows").mark(len(hot_items))
        registry.meter(
            "crypto.verify.signer_table.cold_rows").mark(len(cold_items))
        hot_tr = [trace_ids[i] for i in hot_idx] if trace_ids else None
        cold_tr = [trace_ids[i] for i in cold_idx] if trace_ids \
            else None
        resolve_hot = super().submit(hot_items, trace_ids=hot_tr,
                                     variant=self._hot)
        resolve_cold = super().submit(cold_items, trace_ids=cold_tr) \
            if cold_items else None
        hot_ix = np.asarray(hot_idx, dtype=np.intp)
        cold_ix = np.asarray(cold_idx, dtype=np.intp)
        n = len(items)

        def resolve() -> np.ndarray:
            out = np.zeros(n, dtype=bool)
            out[hot_ix] = resolve_hot()
            if resolve_cold is not None:
                out[cold_ix] = resolve_cold()
            return out

        return resolve

    def verify_batch(self, items: Sequence[tuple]) -> np.ndarray:
        """items: sequence of (pk: bytes, msg: bytes, sig: bytes).
        Returns bool array, libsodium-identical per item. The root
        span covers the whole blocking call, so the per-phase spans
        under it attribute the blocking headline
        (:func:`dispatch_attribution`)."""
        return self.compute_batch(items)

    def verify_sig(self, pk: bytes, msg: bytes, sig: bytes) -> bool:
        """Single verify (uncached — the process-wide result cache lives
        in ``stellar_tpu.crypto.keys.verify_sig``; wire this verifier in
        behind it with :meth:`install`)."""
        return bool(self.verify_batch([(pk, msg, sig)])[0])

    def install(self, trickle_window_ms: Optional[float] = None
                ) -> "BatchVerifier":
        """Make this verifier the backend for ``keys.verify_sig`` so all
        single-sig call sites hit the shared cache first, then the TPU.

        ``trickle_window_ms`` wires a :class:`TrickleBatcher` in front:
        worth it when verify callers are CONCURRENT (overlay auth,
        threaded replay); in a purely single-threaded crank it only
        adds the window to each miss, so it stays opt-in."""
        from stellar_tpu.crypto import keys
        if trickle_window_ms is not None:
            batcher = TrickleBatcher(self, window_ms=trickle_window_ms)
            keys.set_verifier_backend(batcher.verify_sig)
        else:
            keys.set_verifier_backend(self.verify_sig)
        return self


class TrickleBatcher:
    """Micro-batch window for single-signature verify misses — the
    "trickle queue class" of SURVEY §7: bulk paths batch explicitly,
    but lone verifies (overlay auth handshakes, single SCP envelopes)
    would each pay a full solo device dispatch. Concurrent arrivals
    collect for up to ``window_ms`` (or ``max_batch``) and ride ONE
    dispatch; the synchronous bool API is preserved by parking callers
    on futures. The first caller of a window is the leader: it waits
    the window out, dispatches everything queued, and resolves every
    future; followers just block on theirs.

    The internal queue is BOUNDED (``max_pending``): a caller arriving
    when it is full gets a typed :class:`resilience.Overloaded` at
    ingress instead of growing the pending list without limit while a
    leader is stuck behind a slow dispatch — the same
    admission-control discipline as the resident verify service
    (``docs/robustness.md`` "Overload and load-shed")."""

    def __init__(self, verifier: BatchVerifier, window_ms: float = 1.0,
                 max_batch: int = 64, max_pending: int = 4096):
        self._verifier = verifier
        self._window = window_ms / 1000.0
        self._max = max_batch
        self._max_pending = max(1, int(max_pending))
        self._cv = threading.Condition()
        self._pending: list = []  # ((pk, msg, sig), Future)
        self._leader_active = False
        self._flush_asap = False
        self.dispatches = 0  # instrumentation (bench / tests)
        self.rejected = 0    # ingress Overloaded count

    def _dispatch_batch(self, batch: list) -> None:
        """Resolve one claimed batch through the verifier, fanning a
        leader-side failure out to every parked future (nobody hangs)."""
        try:
            results = self._verifier.verify_batch(
                [item for item, _f in batch])
        except BaseException as e:
            for _item, f in batch:
                f.set_exception(e)
            raise
        for (_item, f), ok in zip(batch, results):
            f.set_result(bool(ok))

    def verify_sig(self, pk: bytes, msg: bytes, sig: bytes) -> bool:
        from concurrent.futures import Future
        import time
        fut: Future = Future()
        with self._cv:
            if len(self._pending) >= self._max_pending:
                # bounded queue: reject at ingress, typed — the caller
                # decides whether to retry, shed, or fail its request
                self.rejected += 1
                registry.counter("crypto.verify.trickle.rejected").inc()
                raise resilience.Overloaded(
                    f"trickle window full ({self._max_pending} pending)",
                    kind="rejected", lane="trickle",
                    reason="queue-depth")
            self._pending.append(((pk, msg, sig), fut))
            if self._leader_active:
                if len(self._pending) >= self._max:
                    self._cv.notify_all()  # wake the leader early
                lead = False
            else:
                self._leader_active = True
                lead = True
        if lead:
            deadline = time.perf_counter() + self._window
            with self._cv:
                while len(self._pending) < self._max and \
                        not self._flush_asap:
                    left = deadline - time.perf_counter()
                    if left <= 0:
                        break
                    self._cv.wait(left)
                batch = self._pending
                self._pending = []
                self._leader_active = False
                self._flush_asap = False
                # counted under the lock: the next window's leader can
                # already be running by the time this one dispatches
                self.dispatches += 1
            self._dispatch_batch(batch)
        return fut.result()

    def flush(self) -> int:
        """Dispatch everything queued RIGHT NOW instead of waiting the
        window out (service drain / shutdown path). Tolerant of
        enqueues racing a window close: all queue/leader transitions
        happen under the window lock, so an item is owned by exactly
        one dispatcher — if a leader is active it OWNS the pending
        list (flush just wakes it early and returns 0); otherwise
        flush claims the batch itself, and an enqueue arriving after
        the claim simply elects itself the next leader. Returns how
        many items THIS call dispatched."""
        with self._cv:
            if self._leader_active:
                self._flush_asap = True
                self._cv.notify_all()
                return 0
            batch = self._pending
            self._pending = []
            if batch:
                self.dispatches += 1
        if not batch:
            return 0
        self._dispatch_batch(batch)
        return len(batch)


# Padding rows: any syntactically valid inputs work (results are sliced
# off); use the base point with zero scalars so padded lanes stay cheap
# and never hit the decompress-failure path. Under the signed-window
# kernels zero scalars recode to all-zero digit streams, so every
# padded window select rides the identity patch of
# ops.edwards.table_select_affine (radix-32, PR 13) /
# ops.edwards.table_select (radix-16) — still valid, still cheap, and
# R' stays the identity, matching _PAD_R (pinned by
# tests/test_signed_recode.py::test_padding_rows_recode_to_identity_digits
# and its radix-32 sibling test_recode32_padding_rows_are_identity).
_PAD_A = np.frombuffer(ref.point_compress(ref.BASE), np.uint8).copy()[None]
_PAD_R = np.frombuffer(ref.point_compress(ref.IDENTITY), np.uint8).copy()[None]
_PAD_S = np.zeros((1, 32), dtype=np.uint8)
_PAD_H = np.zeros((1, 32), dtype=np.uint8)
# Hot-path padding table: the base point's cached A-table (any valid
# table works — padded lanes' zero scalars select the identity patch of
# table_select_affine and the results are sliced off). Built once at
# import by the same host builder that fills the signer cache.
_PAD_TABLE = signer_tables.build_signer_table(_PAD_A.tobytes())[None]


_default: Optional[BatchVerifier] = None
_default_lock = threading.Lock()


def default_verifier() -> BatchVerifier:
    """Process-wide verifier. Multi-chip hosts shard with ZERO config:
    the default mesh spans every local device and the standard bucket
    sizes divide any power-of-two chip count, so the v5e-8 target uses
    all chips out of the box (single-chip and CPU hosts are unchanged:
    the mesh is None)."""
    global _default
    with _default_lock:
        if _default is None:
            # the large buckets exist for COALESCED dispatches (catchup
            # replay fusing a whole checkpoint's signatures into one
            # round trip — every dispatch pays a fixed host-device floor,
            # so chunking a 16k batch into 8x2048 would cost 8 round
            # trips for 8x less kernel work); small batches bucket as
            # before
            _default = BatchVerifier(
                mesh=_auto_mesh(),
                bucket_sizes=DEFAULT_BUCKET_SIZES)
        return _default
