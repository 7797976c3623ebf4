"""Build-on-first-use for the repo's C++ libraries (``native/*.cpp``).

A library is keyed by a hash of its sources and compiler flags, and the
hash is part of its file name under ``build/``: a library built from
other sources — another checkout, an older commit — is never loaded,
whatever the file times say. Publishing is atomic, so concurrent
processes never dlopen a half-written file.
"""

from __future__ import annotations

import hashlib
import os
import subprocess

__all__ = ["BUILD_DIR", "build_lib"]

BUILD_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "build")


def build_lib(srcs, name: str, extra_flags=(), timeout: int = 180) -> str:
    """Path of ``build/<name>.<hash>.so`` compiled from ``srcs`` (the
    first is the translation unit, the rest are headers it includes),
    building it if no library of that hash exists yet."""
    flags = ["-O2", "-shared", "-fPIC", *extra_flags]
    h = hashlib.sha256("\0".join(flags).encode())
    for src in srcs:
        with open(src, "rb") as f:
            h.update(f.read())
    out = os.path.join(BUILD_DIR, f"{name}.{h.hexdigest()[:16]}.so")
    if os.path.exists(out):
        return out
    os.makedirs(BUILD_DIR, exist_ok=True)
    tmp = f"{out}.tmp.{os.getpid()}"
    subprocess.run(["g++", *flags, "-o", tmp, srcs[0]],
                   check=True, capture_output=True, timeout=timeout)
    os.replace(tmp, out)
    return out
