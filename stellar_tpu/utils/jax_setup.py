"""JAX process setup shared by every entry point that compiles.

Two decisions live here so no entry point makes its own:

* **Where the persistent compile cache is.** The verify kernel takes
  ~35-55 s per (bucket, variant) to compile for a TPU, so a cache that
  really hits is part of start-up cost. If ``JAX_COMPILATION_CACHE_DIR``
  is set, JAX reads it itself and this module sets nothing; otherwise
  the cache is the fixed path ``<checkout>/.jax_cache`` (gitignored).
  The path is part of the cache's key, so it is never built from a
  temporary name, a pid or the time.
* **How a CPU-only run pins the platform.** ``JAX_PLATFORMS=cpu`` plus
  the matching config update, optionally with N virtual host devices
  for mesh rehearsals. No backend surgery: nothing else is registered.
"""

from __future__ import annotations

import os
import sys

__all__ = ["DEFAULT_CACHE_DIR", "compile_cache_dir", "enable_compile_cache",
           "force_cpu"]

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def compile_cache_dir() -> str:
    """The directory the persistent compile cache uses in this process."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on (idempotent); returns its
    directory. Must run before the first compile to catch it. Imports
    no JAX: before JAX is imported the environment variable is enough
    (JAX reads it at import), after that the config is updated too."""
    cache_dir = compile_cache_dir()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    if "jax" in sys.modules:
        sys.modules["jax"].config.update(
            "jax_compilation_cache_dir", cache_dir)
    return cache_dir


def force_cpu(n_devices: int | None = None) -> None:
    """Pin JAX to its CPU backend, with ``n_devices`` virtual devices
    when given (an ``XLA_FLAGS`` device count already set wins). The
    device count is read when the backend starts, so call this before
    the first array is made."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    if n_devices:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count="
                f"{n_devices}").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
