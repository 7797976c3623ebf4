"""Differential tests: native C++ batch SHA-512 / mod-L prep vs hashlib.

The native path (native/ed25519_prep.cpp) computes h = SHA512(R||A||M)
mod L for the device verify kernel; it must agree bit-for-bit with the
Python oracle for every message length (SHA-512 padding boundaries) and
for digests that exercise the mod-L reduction's edge cases.
"""

import hashlib

import numpy as np
import pytest

from stellar_tpu.crypto import native_prep
from stellar_tpu.crypto import ed25519_ref as ref

L = ref.L


def _oracle(r, a, msgs):
    out = np.empty((len(msgs), 32), np.uint8)
    for i, m in enumerate(msgs):
        d = hashlib.sha512(r[i].tobytes() + a[i].tobytes() + m).digest()
        out[i] = np.frombuffer(
            (int.from_bytes(d, "little") % L).to_bytes(32, "little"),
            np.uint8)
    return out


def test_native_available():
    # the image ships g++; the native path must actually build
    assert native_prep.available()


def test_sha512_batch_matches_hashlib():
    rng = np.random.RandomState(7)
    # sweep lengths across all padding boundaries (111/112, 127/128, ...)
    msgs = [rng.bytes(n) for n in
            list(range(0, 130)) + [111, 112, 119, 120, 127, 128, 240, 1000]]
    got = native_prep.sha512_batch(msgs)
    for i, m in enumerate(msgs):
        assert got[i].tobytes() == hashlib.sha512(m).digest(), len(m)


def test_prep_batch_matches_oracle():
    rng = np.random.RandomState(11)
    n = 257
    r = rng.randint(0, 256, (n, 32)).astype(np.uint8)
    a = rng.randint(0, 256, (n, 32)).astype(np.uint8)
    msgs = [rng.bytes(int(rng.randint(0, 300))) for _ in range(n)]
    got = native_prep.prep_batch(r, a, msgs)
    np.testing.assert_array_equal(got, _oracle(r, a, msgs))


def test_mod_l_edge_digests():
    """Test the native Horner/approximate-quotient reduction directly on
    synthetic 512-bit inputs covering 0, L±1, exact multiples of L, powers
    of two around the fold boundary, and 2^512-1."""
    if not native_prep.available():
        pytest.skip("no toolchain")
    import ctypes
    lib = native_prep._load()
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.ed25519_mod_l_raw.argtypes = [u8p, u8p]

    def native_mod(val):
        d = np.frombuffer(val.to_bytes(64, "little"), np.uint8).copy()
        out = np.empty(32, np.uint8)
        lib.ed25519_mod_l_raw(d.ctypes.data_as(u8p), out.ctypes.data_as(u8p))
        return int.from_bytes(out.tobytes(), "little")

    cases = [0, 1, L - 1, L, L + 1, 2 * L, 2 * L - 1,
             L * (2**259) + 12345, 2**252, 2**252 - 1, 2**253, 2**255 - 19,
             2**512 - 1, (2**512 - 1) // L * L, (2**512 - 1) // L * L - 1]
    rng = np.random.RandomState(13)
    cases += [int.from_bytes(rng.bytes(64), "little") for _ in range(200)]
    for val in cases:
        assert native_mod(val) == val % L, hex(val)


def test_signed_digits16_dev_matches_host():
    import jax
    from stellar_tpu.ops.verify import signed_digits16_dev
    rng = np.random.RandomState(5)
    b = rng.randint(0, 256, (16, 32)).astype(np.uint8)
    got = np.asarray(jax.jit(signed_digits16_dev)(b))
    for i in range(16):
        val = int.from_bytes(b[i].tobytes(), "little")
        acc = 0
        for d in got[:, i]:
            acc = acc * 16 + int(d)
        assert acc == val
