"""SecretKey / PubKeyUtils: the framework's signing identity layer.

Mirrors the reference's ``src/crypto/SecretKey.h`` surface: seed-based
ed25519 keys, StrKey round-trips, deterministic test keys
(``pseudoRandomForTesting``), and — the north-star boundary —
``verify_sig`` with a 0xffff-entry random-eviction result cache in front
of a *pluggable* verifier backend (``crypto/SecretKey.cpp:44-48,435-468``).

Backends:
  * the pure-Python libsodium-exact oracle (default; always available)
  * the TPU ``BatchVerifier`` (``stellar_tpu.crypto.batch_verifier``) —
    installed via ``set_verifier_backend`` for bulk paths; single-sig
    calls still hit the cache first.
"""

from __future__ import annotations

import os
import threading
from typing import Callable, Optional

from stellar_tpu.crypto import ed25519_ref as _ref
from stellar_tpu.crypto import strkey as _strkey
from stellar_tpu.crypto.sha import sha256
from stellar_tpu.utils.cache import RandomEvictionCache
from stellar_tpu.utils.tracing import span

__all__ = [
    "SecretKey", "PublicKey", "verify_sig", "cached_verify_sig",
    "seed_verify_cache", "set_verifier_backend",
    "get_verifier_backend_name",
    "get_verify_cache_stats", "flush_verify_cache",
    "sign_ops_per_second", "verify_ops_per_second",
]

VERIFY_CACHE_SIZE = 0xFFFF
# below this, batch_verify_into_cache uses the host oracle directly
MIN_DEVICE_BATCH = 32

_cache_lock = threading.Lock()
_verify_cache: RandomEvictionCache = RandomEvictionCache(VERIFY_CACHE_SIZE)
_backend: Optional[Callable[[bytes, bytes, bytes], bool]] = None


class PublicKey:
    """32-byte ed25519 public key with StrKey + XDR conveniences."""

    __slots__ = ("raw",)

    def __init__(self, raw: bytes):
        if len(raw) != 32:
            raise ValueError("public key must be 32 bytes")
        self.raw = bytes(raw)

    @classmethod
    def from_strkey(cls, s: str) -> "PublicKey":
        return cls(_strkey.decode_account(s))

    def to_strkey(self) -> str:
        return _strkey.encode_account(self.raw)

    def to_xdr(self):
        from stellar_tpu.xdr.types import account_id
        return account_id(self.raw)

    @classmethod
    def from_xdr(cls, v) -> "PublicKey":
        return cls(v.value)

    def hint(self) -> bytes:
        """Signature hint: last 4 bytes of the key (reference
        ``SignatureUtils::getHint``)."""
        return self.raw[-4:]

    def __eq__(self, other):
        return isinstance(other, PublicKey) and self.raw == other.raw

    def __hash__(self):
        return hash(self.raw)

    def __repr__(self):
        return f"PublicKey({self.to_strkey()})"


class SecretKey:
    """Seed-based ed25519 secret key (reference ``SecretKey.h:22``)."""

    __slots__ = ("seed", "_pk")

    def __init__(self, seed: bytes):
        if len(seed) != 32:
            raise ValueError("seed must be 32 bytes")
        self.seed = bytes(seed)
        self._pk: Optional[PublicKey] = None

    @classmethod
    def random(cls) -> "SecretKey":
        return cls(os.urandom(32))

    @classmethod
    def from_strkey_seed(cls, s: str) -> "SecretKey":
        return cls(_strkey.decode_seed(s))

    @classmethod
    def pseudo_random_for_testing(cls) -> "SecretKey":
        """Non-CSPRNG key for tests (reference ``SecretKey.h:66-77``)."""
        import random
        return cls(bytes(random.getrandbits(8) for _ in range(32)))

    @classmethod
    def from_seed_str(cls, s: str) -> "SecretKey":
        """Deterministic key from an arbitrary string: seed = SHA256(s)
        (reference tests' getAccount pattern)."""
        return cls(sha256(s.encode() if isinstance(s, str) else s))

    def to_strkey_seed(self) -> str:
        return _strkey.encode_seed(self.seed)

    @property
    def public_key(self) -> PublicKey:
        if self._pk is None:
            self._pk = PublicKey(_ref.secret_to_public(self.seed))
        return self._pk

    def get_public_key(self) -> PublicKey:
        return self.public_key

    def sign(self, msg: bytes) -> bytes:
        return _ref.sign(self.seed, msg)

    def sign_decorated(self, msg: bytes):
        from stellar_tpu.xdr.tx import DecoratedSignature
        return DecoratedSignature(hint=self.public_key.hint(),
                                  signature=self.sign(msg))

    def __eq__(self, other):
        return isinstance(other, SecretKey) and self.seed == other.seed

    def __hash__(self):
        return hash(self.seed)

    def __repr__(self):
        return f"SecretKey({self.public_key.to_strkey()})"


def set_verifier_backend(fn: Optional[Callable[[bytes, bytes, bytes], bool]]):
    """Install a verify backend (pk, msg, sig) -> bool; None restores the
    pure-Python oracle. The result cache stays in front either way."""
    global _backend
    with _cache_lock:
        _backend = fn


def accelerated_verify_available() -> bool:
    """True when bulk verification is worth collecting for: an explicit
    backend is installed, or the device probe says an accelerator is
    live. The shared gate for every prefetch-then-apply path (ledger
    close seeding, catchup checkpoint prefetch) — on the host-oracle
    fallback a prefetch is the same sequential work plus collection
    overhead, so those paths verify lazily instead."""
    if _backend is not None:
        return True
    from stellar_tpu.crypto import batch_verifier
    return batch_verifier.device_available(block=False)


def get_verifier_backend_name() -> str:
    """Which backend serves verification right now — recorded into
    every published benchmark row so numbers are attributable."""
    if _backend is None:
        from stellar_tpu.crypto import batch_verifier
        state = batch_verifier._device_state  # no probe side effect
        if state in ("dead", "cpu"):
            return f"host-oracle(auto; device={state})"
        return f"auto(host<{MIN_DEVICE_BATCH},device-batch>=" \
            f"{MIN_DEVICE_BATCH},device={state or 'unprobed'})"
    self_obj = getattr(_backend, "__self__", None)
    if self_obj is not None:
        name = type(self_obj).__name__
        if name == "TrickleBatcher":
            return "device-batch+trickle"
        if hasattr(self_obj, "verify_batch"):
            return "device-batch"
        return name
    mod = getattr(_backend, "__module__", "")
    if "ed25519_ref" in mod:
        return "host-oracle"
    return getattr(_backend, "__qualname__", "custom")


def _cache_key(pk: bytes, msg: bytes, sig: bytes) -> bytes:
    # Identity of the (key, sig, msg) triple. pk and sig are validated
    # fixed-length (32/64) before this is called, so the concatenation
    # has unambiguous field boundaries.
    return sha256(pk + sig + msg)


def verify_sig(pk, msg: bytes, sig: bytes) -> bool:
    """The ``PubKeyUtils::verifySig`` equivalent — all single-signature
    verification funnels through here."""
    raw = pk.raw if isinstance(pk, PublicKey) else bytes(pk)
    if len(sig) != 64 or len(raw) != 32:
        return False
    # called once per signature during apply: a hit records no span,
    # so the probe is recorded only when it misses
    probe = span.deferred("keys.probe")
    key = _cache_key(raw, msg, sig)
    with _cache_lock:
        got = _verify_cache.maybe_get(key)
    if got is not None:
        return got
    probe.keep()
    fn = _backend or _ref.verify
    ok = bool(fn(raw, msg, sig))
    with span("keys.seed"):
        with _cache_lock:
            _verify_cache.put(key, ok)
    return ok


def cached_verify_sig(pk, msg: bytes, sig: bytes) -> Optional[bool]:
    """Cache-only lookup of a prior ``verify_sig`` answer (``None`` on
    miss) — lets adoption call sites (herder SCP envelopes) honor a
    ``batch_verify_into_cache`` prefetch before paying a verify-service
    round trip for one row. Malformed lengths answer ``False`` exactly
    as ``verify_sig`` would."""
    raw = pk.raw if isinstance(pk, PublicKey) else bytes(pk)
    if len(sig) != 64 or len(raw) != 32:
        return False
    with _cache_lock:
        return _verify_cache.maybe_get(_cache_key(raw, msg, sig))


def seed_verify_cache(results) -> None:
    """Seed the ``verify_sig`` result cache with already-decided
    ``(pk, msg, sig, ok)`` quadruples — how a verify-service verdict
    keeps the flood-dedup cache consistent with the direct path (the
    service's answers are pinned bit-identical to the host oracle, so
    seeding can never teach the cache a different decision)."""
    keyed = [(_cache_key(pk, msg, sig), bool(ok))
             for pk, msg, sig, ok in results
             if len(pk) == 32 and len(sig) == 64]
    with _cache_lock:
        for k, ok in keyed:
            _verify_cache.put(k, ok)


def _host_oracle_batch(todo) -> list:
    """Host verification of (key, pk, msg, sig) tuples: libsodium's
    policy gate in Python (the single source of truth,
    ed25519_ref._policy_gate), curve equations through the threaded
    native libcrypto batch when it built, else the per-call oracle."""
    from stellar_tpu.crypto import native_verify
    if not native_verify.available():
        return [_ref.verify(pk, msg, sig) for _, pk, msg, sig in todo]
    gate = [_ref._policy_gate(pk, sig) for _, pk, msg, sig in todo]
    # compact to gate-passing rows (a flood of malformed sigs must not
    # pay full curve verifications for discarded results), then
    # scatter the equation results back
    idx = [i for i, g in enumerate(gate) if g]
    if not idx:
        return [False] * len(todo)
    eq = native_verify.verify_eq_batch(
        [todo[i][1] for i in idx], [todo[i][2] for i in idx],
        [todo[i][3] for i in idx])
    out = [False] * len(todo)
    for i, e in zip(idx, eq):
        out[i] = bool(e)
    return out


def batch_verify_into_cache(items) -> None:
    """Verify (pk, msg, sig) triples in one device batch and seed the
    result cache, so subsequent ``verify_sig`` calls for the same
    triples are O(1) lookups. This is how bulk validation paths (txset
    checkValid, SCP envelope floods, catchup replay) ride the TPU: they
    prefetch, then the per-signer logic runs unchanged
    (reference boundary: ``PubKeyUtils::verifySig`` cache,
    ``SecretKey.cpp:318-338``)."""
    with span("keys.probe"):
        # hash outside the lock; keep the key alongside the triple
        keyed = [(_cache_key(pk, msg, sig), pk, msg, sig)
                 for pk, msg, sig in items
                 if len(pk) == 32 and len(sig) == 64]
        with _cache_lock:
            todo = [(k, pk, msg, sig) for k, pk, msg, sig in keyed
                    if _verify_cache.maybe_get(k) is None]
    if not todo:
        return
    if len(todo) < MIN_DEVICE_BATCH:
        # tiny batches aren't worth a device round trip; use exactly
        # what verify_sig would (installed backend or host oracle) so
        # both paths cache consistent answers
        fn = _backend or _ref.verify
        results = [fn(pk, msg, sig) for _, pk, msg, sig in todo]
    elif _backend is not None:
        if hasattr(_backend, "__self__") and \
                hasattr(_backend.__self__, "verify_batch"):
            results = _backend.__self__.verify_batch(
                [(pk, msg, sig) for _, pk, msg, sig in todo])
        else:
            # custom scalar backend: stay consistent with verify_sig
            results = [_backend(pk, msg, sig) for _, pk, msg, sig in todo]
    else:
        from stellar_tpu.crypto import batch_verifier
        if batch_verifier.device_available(block=False):
            results = batch_verifier.default_verifier().verify_batch(
                [(pk, msg, sig) for _, pk, msg, sig in todo])
        else:
            # no accelerator (cpu-only jax, a failed probe, or a probe
            # still pending on the close path, which never waits): the
            # host oracle beats XLA-on-CPU for bignum verify; the
            # threaded native batch (same libcrypto, same EVP call,
            # policy gate in Python as always) spreads the equation
            # checks across cores where the host has them
            results = _host_oracle_batch(todo)
    with span("keys.seed"), _cache_lock:
        for (k, _, _, _), ok in zip(todo, results):
            _verify_cache.put(k, bool(ok))


def seed_cache_assume_valid(items) -> int:
    """Mark (pk, msg, sig) triples VALID in the cache without
    verifying. ONLY for replaying history whose results are already
    trusted (reference CATCHUP_SKIP_KNOWN_RESULTS_FOR_TESTING) — the
    outcome of every signature in an archived, hash-verified ledger is
    fixed by its recorded results."""
    keyed = [_cache_key(pk, msg, sig) for pk, msg, sig in items
             if len(pk) == 32 and len(sig) == 64]
    with _cache_lock:
        for k in keyed:
            _verify_cache.put(k, True)
    return len(keyed)


def flush_verify_cache():
    with _cache_lock:
        _verify_cache.clear()
        _verify_cache.hits = 0
        _verify_cache.misses = 0


def get_verify_cache_stats() -> dict:
    with _cache_lock:
        return {"hits": _verify_cache.hits, "misses": _verify_cache.misses,
                "size": len(_verify_cache)}


def sign_ops_per_second(iterations: int = 200) -> float:
    """Reference ``SecretKey::benchmarkOpsPerSecond`` (sign half)."""
    import time
    sk = SecretKey.random()
    msg = b"benchmark-payload" * 4
    t0 = time.perf_counter()
    for _ in range(iterations):
        sk.sign(msg)
    return iterations / (time.perf_counter() - t0)


def verify_ops_per_second(iterations: int = 200) -> float:
    import time
    sk = SecretKey.random()
    msg = b"benchmark-payload" * 4
    sig = sk.sign(msg)
    pk = sk.public_key
    t0 = time.perf_counter()
    for _ in range(iterations):
        flush_verify_cache()
        verify_sig(pk, msg, sig)
    return iterations / (time.perf_counter() - t0)
