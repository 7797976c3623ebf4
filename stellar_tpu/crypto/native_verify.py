"""ctypes bridge to the threaded native batch ed25519 verifier
(``native/ed25519_batch_verify.cpp``): the host-side fallback when no
accelerator is reachable. The system libcrypto's EVP one-shot runs the
same ref10-derived cofactorless equation as the per-call oracle, and
the libsodium policy gate stays in Python
(:func:`stellar_tpu.crypto.ed25519_ref._policy_gate`) exactly as for
the per-call path; agreement is PINNED by the differential test
(tests/test_batch_verifier.py) rather than assumed — the
``cryptography`` wheel may embed its own OpenSSL build (reference
boundary: ``PubKeyUtils::verifySig``, ``src/crypto/SecretKey.cpp:435-468``).
"""

from __future__ import annotations

import ctypes
import os
import threading
from typing import Sequence

import numpy as np

from stellar_tpu.utils.native_build import build_lib

__all__ = ["available", "verify_eq_batch"]

_HERE = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_SRC = os.path.join(_HERE, "native", "ed25519_batch_verify.cpp")

_lock = threading.Lock()
_lib = None
_tried = False

_u8p = ctypes.POINTER(ctypes.c_uint8)
_u64p = ctypes.POINTER(ctypes.c_uint64)


def _load():
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        try:
            lib = ctypes.CDLL(build_lib([_SRC], "libed25519verify",
                                        extra_flags=["-ldl"], timeout=120))
            lib.ed25519_verify_available.restype = ctypes.c_int
            lib.ed25519_verify_batch.argtypes = [
                _u8p, _u8p, _u8p, _u64p, _u64p, ctypes.c_uint64,
                ctypes.c_int, _u8p]
            lib.ed25519_verify_batch.restype = ctypes.c_int
            if lib.ed25519_verify_available() != 1:
                lib = None
            _lib = lib
        except Exception:
            _lib = None
        return _lib


def available() -> bool:
    return _load() is not None


def _u8(a: np.ndarray):
    return a.ctypes.data_as(_u8p)


def verify_eq_batch(pks: Sequence[bytes], msgs: Sequence[bytes],
                    sigs: Sequence[bytes],
                    nthreads: int = 0) -> np.ndarray:
    """Curve-equation verification for n well-formed (32B pk, msg,
    64B sig) items, threaded. Callers apply the libsodium policy gate
    separately (same split as every other verify path)."""
    n = len(pks)
    out = np.zeros(n, dtype=np.uint8)
    if n == 0:
        return out.astype(bool)
    lib = _load()
    assert lib is not None, "native verifier unavailable"
    pk_blob = np.frombuffer(b"".join(pks), dtype=np.uint8)
    sig_blob = np.frombuffer(b"".join(sigs), dtype=np.uint8)
    blob = b"".join(msgs)
    msg_blob = np.frombuffer(blob, dtype=np.uint8) if blob else \
        np.zeros(1, dtype=np.uint8)
    lens = np.fromiter((len(m) for m in msgs), dtype=np.uint64, count=n)
    offs = np.zeros(n, dtype=np.uint64)
    np.cumsum(lens[:-1], out=offs[1:])
    if nthreads <= 0:
        nthreads = min(8, os.cpu_count() or 1)
    rc = lib.ed25519_verify_batch(
        _u8(pk_blob), _u8(sig_blob), _u8(msg_blob),
        offs.ctypes.data_as(_u64p), lens.ctypes.data_as(_u64p),
        n, nthreads, _u8(out))
    assert rc == 0
    return out.astype(bool)
