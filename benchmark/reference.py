"""Plain reference for the verdict guarantee both configurations state:
every row's verdict equals libsodium's ``crypto_sign_verify_detached``
(libsodium 1.0.18+, default build, not ``ED25519_COMPAT``).

Written from libsodium's ``crypto_sign/ed25519/ref10/open.c`` and
RFC 8032, importing nothing of the program under test:

1. ``sc25519_is_canonical(S)``: S < L;
2. ``ge25519_has_small_order(R)``: R's encoding, top bit ignored, is
   not one of the seven small-order y values (0, 1, p-1, the two
   order-8 y, p, p+1);
3. ``ge25519_is_canonical(A)``: A's y, top bit ignored, is below p;
4. ``ge25519_has_small_order(A)``;
5. ``ge25519_frombytes_negate_vartime(A)`` decompresses;
6. ``encode(S*B - H(R || A || M)*A) == R`` byte for byte.

Steps 1-4 are the *policy gate*. Step 5-6 (the *equation*) runs
through OpenSSL (the ``cryptography`` package) when the gate passed:
OpenSSL decodes A and compares the re-encoded point with R exactly as
libsodium does, and every row that reaches it has already passed the
stricter gate. A pure-Python equation (:func:`equation_py`) is kept
for the control, which drops the gate, and for the tests that cross
check the two.

:func:`verdicts` runs the reference over many rows in a pool of
spawned worker processes (none of them imports JAX), so the check of a
whole window stays shorter than the window.
"""

from __future__ import annotations

import hashlib
from typing import List, Optional, Sequence, Tuple

P = 2 ** 255 - 19
L = 2 ** 252 + 27742317777372353535851937790883648493
D = -121665 * pow(121666, P - 2, P) % P
SQRT_M1 = pow(2, (P - 1) // 4, P)
_MASK255 = (1 << 255) - 1

Row = Tuple[bytes, bytes, bytes]


def _recover_x(y: int, sign: int) -> Optional[int]:
    """x with x^2 = (y^2 - 1) / (d y^2 + 1), of parity ``sign``; None
    when no square root exists (RFC 8032 5.1.3, without its rejection
    of x = 0 with the sign bit set, which libsodium does not make)."""
    u = (y * y - 1) % P
    v = (D * y * y + 1) % P
    x = (u * pow(v, 3, P) * pow(u * pow(v, 7, P), (P - 5) // 8, P)) % P
    if (v * x * x - u) % P != 0:
        x = x * SQRT_M1 % P
        if (v * x * x - u) % P != 0:
            return None
    if x & 1 != sign:
        x = (P - x) % P
    return x


def decompress(enc: bytes):
    """Extended point of a 32-byte encoding, y taken mod p as
    libsodium's ``fe25519_frombytes`` does; None off the curve."""
    n = int.from_bytes(enc, "little")
    y = (n & _MASK255) % P
    x = _recover_x(y, n >> 255)
    if x is None:
        return None
    return (x, y, 1, x * y % P)


def _add(p1, p2):
    x1, y1, z1, t1 = p1
    x2, y2, z2, t2 = p2
    a = (y1 - x1) * (y2 - x2) % P
    b = (y1 + x1) * (y2 + x2) % P
    c = 2 * D * t1 * t2 % P
    d = 2 * z1 * z2 % P
    e, f, g, h = b - a, d - c, d + c, b + a
    return (e * f % P, g * h % P, f * g % P, e * h % P)


def _mul(k: int, pt):
    acc = (0, 1, 1, 0)
    while k:
        if k & 1:
            acc = _add(acc, pt)
        pt = _add(pt, pt)
        k >>= 1
    return acc


def encode(pt) -> bytes:
    x, y, z, _t = pt
    zi = pow(z, P - 2, P)
    x, y = x * zi % P, y * zi % P
    return (y | ((x & 1) << 255)).to_bytes(32, "little")


_BY = 4 * pow(5, P - 2, P) % P
BASE = (_recover_x(_BY, 0), _BY, 1, _recover_x(_BY, 0) * _BY % P)


def _small_order_ys() -> frozenset:
    """y values (top bit ignored) of the eight torsion points, plus the
    non-canonical aliases y + p that still fit in 255 bits: the seven
    values of libsodium's blocklist, derived rather than copied."""
    ys = set()
    seed = 0
    while len(ys) < 5:  # 0, 1, p-1 and the two order-8 y
        seed += 1
        pt = decompress(hashlib.sha256(bytes([seed])).digest())
        if pt is None:
            continue
        t = _mul(L, pt)  # the torsion part of a random point
        acc = t
        for _ in range(8):
            ys.add(int.from_bytes(encode(acc), "little") & _MASK255)
            acc = _add(acc, t)
    ys |= {y + P for y in ys if y + P <= _MASK255}
    return frozenset(ys)


SMALL_ORDER_Y = _small_order_ys()


def has_small_order(enc: bytes) -> bool:
    return (int.from_bytes(enc, "little") & _MASK255) in SMALL_ORDER_Y


def policy_gate(pk: bytes, sig: bytes) -> bool:
    """libsodium's checks before the equation (steps 1-4)."""
    if len(pk) != 32 or len(sig) != 64:
        return False
    if int.from_bytes(sig[32:], "little") >= L:
        return False
    if has_small_order(sig[:32]):
        return False
    if (int.from_bytes(pk, "little") & _MASK255) >= P:
        return False
    return not has_small_order(pk)


def equation_py(pk: bytes, msg: bytes, sig: bytes) -> bool:
    """Steps 5-6 in pure Python, S used as given (not reduced)."""
    a = decompress(pk)
    if a is None or len(sig) != 64:
        return False
    h = int.from_bytes(hashlib.sha512(sig[:32] + pk + msg).digest(),
                       "little") % L
    s = int.from_bytes(sig[32:], "little")
    neg_a = ((P - a[0]) % P, a[1], a[2], (P - a[3]) % P)
    return encode(_add(_mul(s, BASE), _mul(h, neg_a))) == sig[:32]


def _equation_openssl(pk: bytes, msg: bytes, sig: bytes) -> bool:
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PublicKey,
    )
    try:
        Ed25519PublicKey.from_public_bytes(pk).verify(sig, msg)
        return True
    except (InvalidSignature, ValueError):
        return False


def verify(pk: bytes, msg: bytes, sig: bytes) -> bool:
    """The reference verdict of one row."""
    return policy_gate(pk, sig) and _equation_openssl(pk, msg, sig)


def verify_no_gate(pk: bytes, msg: bytes, sig: bytes) -> bool:
    """The control: the reference with libsodium's policy gate dropped
    (RFC 8032's equation alone, S not checked against L, small-order
    and non-canonical keys let through). Equal to :func:`verify` on
    every row that passes the gate."""
    if policy_gate(pk, sig):
        return _equation_openssl(pk, msg, sig)
    return equation_py(pk, msg, sig)


CHECKS = {"reference": verify, "no_policy_gate": verify_no_gate}


def _check_block(args) -> List[bool]:
    """Worker: the verdicts of one block of rows."""
    name, rows = args
    fn = CHECKS[name]
    return [fn(pk, m, s) for pk, m, s in rows]


def verdicts(rows: Sequence[Row], workers: int, check: str = "reference",
             block: int = 4096) -> Tuple[List[bool], List[bool]]:
    """Verdicts of ``check`` over ``rows``, and the policy gate of each
    row, in ``workers`` spawned processes (inline when ``workers`` is
    0 or 1). Waits for every worker and leaves none running."""
    gate = [policy_gate(pk, s) for pk, _m, s in rows]
    blocks = [(check, list(rows[i:i + block]))
              for i in range(0, len(rows), block)]
    if workers <= 1 or len(blocks) <= 1:
        results = [_check_block(b) for b in blocks]
    else:
        import multiprocessing
        from concurrent.futures import ProcessPoolExecutor
        ctx = multiprocessing.get_context("spawn")
        with ProcessPoolExecutor(max_workers=workers,
                                 mp_context=ctx) as ex:
            results = list(ex.map(_check_block, blocks))
    return [v for block_out in results for v in block_out], gate
