"""ctypes bridge to the C++ batch host-prep for ed25519 verification
(``native/ed25519_prep.cpp``): multithreaded SHA-512(R||A||M) mod L.

Mirrors the loader pattern of :mod:`stellar_tpu.utils.native`. Pure-Python
fallback (hashlib loop) keeps the framework functional without a
toolchain; differential tests pin the two together.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import threading
from typing import Sequence

import numpy as np

from stellar_tpu.utils.native_build import build_lib

__all__ = ["available", "prep_batch", "sha512_batch"]

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_SRC = os.path.join(_REPO_ROOT, "native", "ed25519_prep.cpp")

_lock = threading.Lock()
_lib = None
_tried = False

_L = 2**252 + 27742317777372353535851937790883648493


def _load():
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        try:
            lib = ctypes.CDLL(build_lib([_SRC], "libed25519prep",
                                        extra_flags=["-pthread"],
                                        timeout=120))
            u8p = ctypes.POINTER(ctypes.c_uint8)
            u64p = ctypes.POINTER(ctypes.c_uint64)
            lib.ed25519_prep_batch.argtypes = [
                u8p, u8p, u8p, u64p, u64p, ctypes.c_uint64, ctypes.c_int,
                u8p]
            lib.sha512_batch.argtypes = [u8p, u64p, u64p, ctypes.c_uint64,
                                         u8p]
            _lib = lib
        except Exception:
            _lib = None
        return _lib


def available() -> bool:
    return _load() is not None


def _u8(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def _u64(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64))


def prep_batch(r: np.ndarray, a: np.ndarray, msgs: Sequence[bytes],
               nthreads: int = 0) -> np.ndarray:
    """h[i] = SHA512(r[i] || a[i] || msgs[i]) mod L as (n, 32) uint8 LE.

    r, a: (n, 32) uint8 C-contiguous arrays.
    """
    n = len(msgs)
    out = np.empty((n, 32), dtype=np.uint8)
    lib = _load()
    if lib is None:
        for i, m in enumerate(msgs):
            d = hashlib.sha512(r[i].tobytes() + a[i].tobytes() + m).digest()
            out[i] = np.frombuffer(
                (int.from_bytes(d, "little") % _L).to_bytes(32, "little"),
                dtype=np.uint8)
        return out
    blob = b"".join(msgs)
    lens = np.fromiter((len(m) for m in msgs), dtype=np.uint64, count=n)
    offs = np.zeros(n, dtype=np.uint64)
    np.cumsum(lens[:-1], out=offs[1:])
    if nthreads <= 0:
        nthreads = min(8, os.cpu_count() or 1)
    blob_arr = np.frombuffer(blob, dtype=np.uint8) if blob else \
        np.zeros(1, dtype=np.uint8)
    r = np.ascontiguousarray(r)
    a = np.ascontiguousarray(a)
    lib.ed25519_prep_batch(_u8(r), _u8(a), _u8(blob_arr), _u64(offs),
                           _u64(lens), n, nthreads, _u8(out))
    return out


def sha512_batch(msgs: Sequence[bytes]) -> np.ndarray:
    """(n, 64) uint8 SHA-512 digests (test helper for the native hash)."""
    n = len(msgs)
    out = np.empty((n, 64), dtype=np.uint8)
    lib = _load()
    if lib is None:
        for i, m in enumerate(msgs):
            out[i] = np.frombuffer(hashlib.sha512(m).digest(), np.uint8)
        return out
    blob = b"".join(msgs)
    lens = np.fromiter((len(m) for m in msgs), dtype=np.uint64, count=n)
    offs = np.zeros(n, dtype=np.uint64)
    np.cumsum(lens[:-1], out=offs[1:])
    blob_arr = np.frombuffer(blob, dtype=np.uint8) if blob else \
        np.zeros(1, dtype=np.uint8)
    lib.sha512_batch(_u8(blob_arr), _u64(offs), _u64(lens), n, _u8(out))
    return out
