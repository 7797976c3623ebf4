"""The one traffic generator: rows ``(pk, msg, sig)`` for each call of a
cell, from a configuration file (the deployment) and a traffic-mix
file (the mix), both plain JSON read by name.

Everything is drawn from ``--seed`` and the call's index, never from
the clock, so the same seed gives the same calls in the same order,
however fast the system runs. Keys, nonces and signatures:

* each signer's key seed is SHA-256 of the run seed and its index;
  OpenSSL derives its public key A;
* each signer keeps ``nonces_per_key`` precomputed nonces ``(r, R =
  r*B)`` (R again from OpenSSL, as the public key of the nonce seed),
  or with 0 a row draws a fresh nonce (~50 us more per row);
* a row is signed as ``S = r + SHA-512(R || A || M) * a mod L`` with a
  nonce drawn per row: a valid signature libsodium accepts, at a few
  microseconds per row, so rows can be made inside the window.

Configuration keys read here (``configs/<name>.json``): ``unit``
(``tx`` or ``envelope``), ``keys_per_account``, ``call_size`` (the
default law), ``validators`` and ``ballot_rounds`` (envelopes).

Traffic keys (``traffic/<name>.json``):

* ``call_size``: ``{"law": "fixed", "units": n}``;
* ``signers``: ``{"law": "round_robin", "accounts": n}`` (unit ``u``
  of a call takes account ``u mod n``, as ``multisig_apply_load``
  does; envelopes continue the rotation across calls), ``{"law":
  "distinct", "accounts": n}`` (a call's units take distinct accounts
  in an order drawn per call: one tx per source account) or ``{"law":
  "zipf", "s": s, "accounts": n}`` (bounded Zipf over n accounts,
  rank 1 heaviest, keys derived on demand);
* ``arrivals``: absent for a closed loop (each call starts when the
  last one ended), or ``{"law": "bursts", "size": k, "every_s": t}``
  -- calls ``b*k .. b*k+k-1`` all arrive ``b*t`` seconds into the
  window, the same instants for every seed;
* ``invalid``: ``{"per_call": {class: count}}`` at distinct positions
  of each call, or ``{"every": k, "classes": [...]}`` -- one row in
  each block of k consecutive rows of the stream, the class cycling
  through the list;
* ``warmup``: ``{"signers": n, "invalid_keys": bool, "calls": m}`` --
  one row for each of the first n signers (``"all"``: every key), one
  for every key encoding the invalid classes use, then m calls of the
  mix from a stream of their own.

Invalid classes (``CLASSES``) are the seven of
``chip_smoke.txset_batch`` and ``a_off_curve``: a row whose policy gate
fails is never dispatched, so only a key that passes the gate and
decompresses to no point makes a call carry a cold-kernel part once
every signer is tabled.
"""

from __future__ import annotations

import hashlib
import struct
from typing import Callable, Dict, List, Tuple

import numpy as np

from benchmark.reference import L, P, SMALL_ORDER_Y, decompress

Row = Tuple[bytes, bytes, bytes]

NETWORK_PASSPHRASE = b"Public Global Stellar Network ; September 2015"
_LOW_BITS = (1 << 64) - 1


def _seed_words(seed: int) -> List[int]:
    """A seed of any size (the driver's exceed 32 bits) as 64-bit words
    for numpy's SeedSequence, which takes non-negative entropy."""
    if seed < 0:
        raise ValueError("seed must be non-negative")
    words = []
    while True:
        words.append(seed & _LOW_BITS)
        seed >>= 64
        if not seed:
            return words


def _rng(seed: int, *label: int) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence(_seed_words(seed) + list(label)))


def _clamp(h: bytes) -> int:
    a = int.from_bytes(h[:32], "little")
    a &= (1 << 254) - 8
    return a | (1 << 254)


def _public(seed32: bytes) -> bytes:
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey,
    )
    return Ed25519PrivateKey.from_private_bytes(
        seed32).public_key().public_bytes_raw()


class Signer:
    """One key with its precomputed nonces."""

    __slots__ = ("a", "pk", "nonces")

    def __init__(self, seed: int, index: int, n_nonces: int):
        tag = _seed_words(seed) + [index]
        base = hashlib.sha256(b"bench-key" + struct.pack(
            f"<{len(tag)}Q", *tag)).digest()
        self.a = _clamp(hashlib.sha512(base).digest())
        self.pk = _public(base)
        self.nonces = []
        for j in range(n_nonces):
            ns = hashlib.sha256(b"bench-nonce" + base + bytes([j])).digest()
            self.nonces.append((_clamp(hashlib.sha512(ns).digest()),
                                _public(ns)))

    def sign(self, msg: bytes, nonce) -> bytes:
        """Sign with precomputed nonce ``nonce`` (an index), or with a
        fresh one derived from ``nonce`` (32 bytes), at ~50 us more."""
        if isinstance(nonce, bytes):
            r, big_r = _clamp(hashlib.sha512(nonce).digest()), _public(nonce)
        else:
            r, big_r = self.nonces[nonce]
        h = int.from_bytes(hashlib.sha512(big_r + self.pk + msg).digest(),
                           "little")
        return big_r + ((r + h * self.a) % L).to_bytes(32, "little")


# ---------------- invalid classes ----------------

def _flip(b: bytes, i: int) -> bytes:
    return b[:i] + bytes([b[i] ^ 1]) + b[i + 1:]


def _enc(y: int) -> bytes:
    return y.to_bytes(32, "little")


# y = p + k for k in 0..18 are the non-canonical encodings of A;
# those whose y mod p lies on no point cannot be tabled
NONCANON_A = [_enc(P + k) for k in range(19)]
SMALL_ORDER = sorted(_enc(y) for y in SMALL_ORDER_Y)
SMALL_ORDER += [_enc(int.from_bytes(e, "little") | (1 << 255))
                for e in SMALL_ORDER]
R_OFF_CURVE = next(_enc(y) for y in range(2, 1000) if decompress(_enc(y))
                   is None)


def _pick(rng, options):
    return options[int(rng.integers(len(options)))]


def _a_off_curve(rng) -> bytes:
    """A canonical y (below p) with no point: passes libsodium's policy
    gate, fails decompression, so the row can never be tabled and rides
    the cold kernel, whose decompress stage rejects it."""
    while True:
        enc = _enc(int.from_bytes(rng.bytes(32), "little") & ((1 << 255) - 1))
        if int.from_bytes(enc, "little") < P and decompress(enc) is None:
            return enc


CLASSES: Dict[str, Callable] = {
    "tampered_sig": lambda row, rng: (row[0], row[1], _flip(row[2], 32)),
    "wrong_message": lambda row, rng: (row[0], _flip(row[1], 0), row[2]),
    "noncanonical_s": lambda row, rng: (
        row[0], row[1], row[2][:32] + (int.from_bytes(
            row[2][32:], "little") + L).to_bytes(32, "little")),
    "noncanonical_a": lambda row, rng: (_pick(rng, NONCANON_A), row[1],
                                        row[2]),
    "small_order_a": lambda row, rng: (_pick(rng, SMALL_ORDER), row[1],
                                       row[2]),
    "r_off_curve": lambda row, rng: (row[0], row[1],
                                     R_OFF_CURVE + row[2][32:]),
    "r_small_order": lambda row, rng: (row[0], row[1],
                                       _pick(rng, SMALL_ORDER) + row[2][32:]),
    "a_off_curve": lambda row, rng: (_a_off_curve(rng), row[1], row[2]),
}

# every key encoding an invalid class can put in the A column
INVALID_KEYS = NONCANON_A + SMALL_ORDER


# ---------------- SCP statements ----------------

def _opaque(b: bytes) -> bytes:
    return struct.pack(">I", len(b)) + b + bytes(-len(b) % 4)


def _ballot(counter: int, value: bytes) -> bytes:
    return struct.pack(">I", counter) + _opaque(value)


def scp_statement(node: bytes, slot: int, step: int, rounds: int,
                  qset: bytes, value: bytes) -> bytes:
    """XDR of an ``SCPStatement`` (``Stellar-SCP.x``) for step ``step``
    of a slot: 0 nominate, 1..rounds prepare ballot ``step``, then
    confirm, then externalize."""
    head = struct.pack(">I", 0) + node + struct.pack(">Q", slot)
    if step == 0:
        body = struct.pack(">I", 3) + qset + struct.pack(">I", 1) \
            + _opaque(value) + struct.pack(">I", 0)
    elif step <= rounds:
        prepared = struct.pack(">I", 0) if step == 1 else \
            struct.pack(">I", 1) + _ballot(step - 1, value)
        body = struct.pack(">I", 0) + qset + _ballot(step, value) \
            + prepared + struct.pack(">III", 0, 0, 0)
    elif step == rounds + 1:
        body = struct.pack(">I", 1) + _ballot(rounds, value) \
            + struct.pack(">III", rounds, 1, rounds) + qset
    else:
        body = struct.pack(">I", 2) + _ballot(1, value) \
            + struct.pack(">I", rounds) + qset
    return head + body


def scp_sign_payload(network_id: bytes, statement: bytes) -> bytes:
    """(networkID || ENVELOPE_TYPE_SCP || statement), what a validator
    signs (stellar-core ``HerderImpl::signEnvelope``)."""
    return network_id + struct.pack(">i", 1) + statement


# ---------------- the generator ----------------

class Traffic:
    """The calls of one cell. ``warmup()`` gives the set-up calls;
    ``call(i)`` the i-th call of the window. Calls must be asked for in
    order (envelope streams carry their position from call to call)."""

    WINDOW, WARMUP = 1, 2  # stream labels

    def __init__(self, config: dict, mix: dict, seed: int):
        self.config = config
        self.mix = mix
        self.seed = seed
        self.unit = config["unit"]
        self.keys_per_account = int(config.get("keys_per_account", 1))
        size_law = mix.get("call_size") or config["call_size"]
        if size_law["law"] != "fixed":
            raise ValueError(f"unknown call_size law {size_law}")
        self.units = int(size_law["units"])
        self.signer_law = mix["signers"]
        self.n_accounts = int(self.signer_law["accounts"])
        self.n_nonces = int(mix.get("nonces_per_key", 4))  # 0: fresh
        self.invalid = mix.get("invalid", {})
        self._signers: Dict[int, Signer] = {}
        self._offset = {self.WINDOW: 0, self.WARMUP: 0}
        self._next = {self.WINDOW: 0, self.WARMUP: 0}
        self.arrivals = mix.get("arrivals")
        if self.arrivals and self.arrivals["law"] != "bursts":
            raise ValueError(f"unknown arrival law {self.arrivals}")
        if self.signer_law["law"] == "zipf":
            ranks = np.arange(1, self.n_accounts + 1, dtype=np.float64)
            cdf = np.cumsum(ranks ** -float(self.signer_law["s"]))
            self._zipf_cdf = cdf / cdf[-1]
        elif self.signer_law["law"] not in ("round_robin", "distinct"):
            raise ValueError(f"unknown signer law {self.signer_law}")
        if self.unit == "envelope":
            self.rounds = int(config["ballot_rounds"])
            self.network_id = hashlib.sha256(NETWORK_PASSPHRASE).digest()
            self.qset = hashlib.sha256(
                b"bench-qset" + struct.pack(">I", self.n_accounts)).digest()
        elif self.unit != "tx":
            raise ValueError(f"unknown unit {self.unit!r}")

    # -- signers --

    def signer(self, key_index: int) -> Signer:
        s = self._signers.get(key_index)
        if s is None:
            s = self._signers[key_index] = Signer(self.seed, key_index,
                                                  self.n_nonces)
        return s

    def _accounts(self, rng, start: int, n: int) -> np.ndarray:
        if self.signer_law["law"] == "round_robin":
            return (start + np.arange(n)) % self.n_accounts
        if self.signer_law["law"] == "distinct":
            if n > self.n_accounts:
                raise ValueError(f"{n} distinct accounts asked of "
                                 f"{self.n_accounts}")
            return rng.permutation(self.n_accounts)[:n]
        return np.minimum(np.searchsorted(self._zipf_cdf, rng.random(n)),
                          self.n_accounts - 1)

    # -- arrivals --

    def arrival_s(self, i: int):
        """Seconds into the window at which call ``i`` arrives, or None
        in a closed loop."""
        if not self.arrivals:
            return None
        return (i // int(self.arrivals["size"])) * float(
            self.arrivals["every_s"])

    def group(self, i: int) -> range:
        """The calls that arrive together with call ``i`` (from ``i``
        on): a burst, or call ``i`` alone in a closed loop."""
        if not self.arrivals:
            return range(i, i + 1)
        k = int(self.arrivals["size"])
        return range(i, (i // k + 1) * k)

    # -- calls --

    def call(self, i: int, stream: int = WINDOW) -> List[Row]:
        if i != self._next[stream]:
            raise ValueError(f"calls are made in order: asked {i}, next "
                             f"is {self._next[stream]}")
        self._next[stream] += 1
        rng = _rng(self.seed, stream, 2, i)
        units = self.units
        offset = self._offset[stream]
        self._offset[stream] += units
        if self.unit == "tx":
            rows = self._tx_rows(rng, units)
        else:
            rows = self._envelope_rows(rng, stream, offset, units)
        self._make_invalid(rows, rng, stream, offset *
                           self.keys_per_account)
        return rows

    def _nonces(self, rng, n: int) -> list:
        """A precomputed nonce index per row, or with ``nonces_per_key``
        0 a fresh nonce seed per row: a row alone in a device call then
        brings an R operand no earlier call had, as real signers'
        per-message nonces do."""
        if self.n_nonces:
            return [int(x) for x in rng.integers(self.n_nonces, size=n)]
        fresh = rng.bytes(32 * n)
        return [fresh[32 * i:32 * i + 32] for i in range(n)]

    def _tx_rows(self, rng, txs: int) -> List[Row]:
        # a tx set starts from account 0, as multisig_apply_load's do
        accounts = self._accounts(rng, 0, txs)
        hashes = rng.bytes(32 * txs)
        k = self.keys_per_account
        nonces = self._nonces(rng, txs * k)
        rows = []
        for t in range(txs):
            msg = hashes[32 * t:32 * t + 32]
            base = int(accounts[t]) * k
            for j in range(k):
                s = self.signer(base + j)
                rows.append((s.pk, msg, s.sign(msg, nonces[t * k + j])))
        return rows

    def _envelope_rows(self, rng, stream: int, offset: int,
                       n: int) -> List[Row]:
        steps = self.rounds + 3
        v = self.n_accounts
        accounts = self._accounts(rng, offset, n)
        nonces = self._nonces(rng, n)
        values: Dict[int, bytes] = {}
        rows = []
        for e in range(n):
            j = offset + e
            slot = 1 + j // (v * steps)
            value = values.get(slot)
            if value is None:
                value = values[slot] = self._stellar_value(stream, slot)
            s = self.signer(int(accounts[e]))
            stmt = scp_statement(s.pk, slot, (j // v) % steps,
                                 self.rounds, self.qset, value)
            msg = scp_sign_payload(self.network_id, stmt)
            rows.append((s.pk, msg, s.sign(msg, nonces[e])))
        return rows

    def _stellar_value(self, stream: int, slot: int) -> bytes:
        """A signed ``StellarValue`` (txSetHash, closeTime, no upgrades,
        STELLAR_VALUE_SIGNED with the slot leader's LCV signature);
        SCP treats it as opaque bytes."""
        r = _rng(self.seed, stream, 3, slot)
        leader = self.signer(slot % self.n_accounts)
        return (r.bytes(32) + struct.pack(">QIII", 1_700_000_000 + 5 * slot,
                                          0, 1, 0)
                + leader.pk + _opaque(r.bytes(64)))

    def _make_invalid(self, rows: List[Row], rng, stream: int,
                      offset: int) -> None:
        per_call = self.invalid.get("per_call")
        if per_call:
            names = [c for c, k in per_call.items() for _ in range(int(k))]
            pos = rng.choice(len(rows), size=len(names), replace=False)
            for c, p in zip(names, pos):
                rows[int(p)] = CLASSES[c](rows[int(p)], rng)
        every = self.invalid.get("every")
        if every:
            every = int(every)
            classes = self.invalid["classes"]
            first_block = offset // every
            last_block = (offset + len(rows) - 1) // every
            for b in range(first_block, last_block + 1):
                at = b * every + int(_rng(self.seed, stream, 4, b)
                                     .integers(every))
                if offset <= at < offset + len(rows):
                    c = classes[b % len(classes)]
                    rows[at - offset] = CLASSES[c](rows[at - offset], rng)

    # -- set-up --

    def warmup(self) -> List[List[Row]]:
        """The calls set-up makes: first sight of the signers and of the
        invalid key encodings, in calls of at most 128 rows (the
        smallest bucket), then ``calls`` calls of the mix."""
        spec = self.mix.get("warmup", {})
        rows: List[Row] = []
        n_sign = spec.get("signers")
        n_keys = self.n_accounts * self.keys_per_account
        n_sign = 0 if n_sign is None else \
            n_keys if n_sign == "all" else min(int(n_sign), n_keys)
        n_bad = len(INVALID_KEYS) if spec.get("invalid_keys") else 0
        nonces = self._nonces(_rng(self.seed, self.WARMUP, 5),
                              n_sign + n_bad)
        msg = hashlib.sha256(b"bench-warmup" + struct.pack(
            ">Q", self.seed & _LOW_BITS)).digest()
        for k in range(n_sign):
            s = self.signer(k)
            m = msg + struct.pack(">Q", k)
            rows.append((s.pk, m, s.sign(m, nonces[k])))
        for j, pk in enumerate(INVALID_KEYS[:n_bad]):
            m = b"bench-invalid-key" + bytes([j])
            rows.append((pk, m, self.signer(0).sign(m, nonces[n_sign + j])))
        calls = []
        if rows:
            n = -(-len(rows) // 128)
            per = -(-len(rows) // n)
            calls = [rows[i:i + per] for i in range(0, len(rows), per)]
        calls += [self.call(i, self.WARMUP)
                  for i in range(int(spec.get("calls", 0)))]
        return calls
