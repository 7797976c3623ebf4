"""Test configuration: JAX on its CPU backend with an 8-device virtual
mesh, so multi-chip sharding (per-device sub-chunks, ``shard_map``) is
exercised without a TPU. ``chip_smoke.py`` and ``bench.py`` are what
run on the chip.

Must run before jax arrays are created anywhere.
"""

import os
import sys

# persistent XLA compilation cache: the verify-kernel compiles dominate
# suite time; cache across runs (safe to delete any time)
os.environ.setdefault(
    "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "5")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from stellar_tpu.utils.jax_setup import (  # noqa: E402
    enable_compile_cache, force_cpu,
)

force_cpu(n_devices=8)
enable_compile_cache()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavyweight differential sweeps excluded from the tier-1 "
        "gate (run explicitly: pytest -m slow)")
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection dispatch-resilience suite "
        "(tests/test_chaos_dispatch.py) — CPU-safe, faults are "
        "injected via stellar_tpu.utils.faults; part of tier-1 and "
        "also runnable alone: pytest -m chaos")
