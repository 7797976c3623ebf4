#!/usr/bin/env python3
"""Benchmark entry: ``python3 benchmark/run.py --workload <cell> --seed
<n> --seconds <s> --trace <0|1>`` from the root of a checkout.

Runs one cell of ``BENCHMARK.json`` on the chips of this machine and
prints one JSON result line last (``benchmark/harness.py``). Exits
non-zero, printing no result, where JAX finds no TPU or another number
of chips than the cell asks for.
"""

import time

_T_START = time.perf_counter()  # set-up is counted from here

import os  # noqa: E402
import sys  # noqa: E402


def _main() -> int:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import harness
    return harness.main(t_start=_T_START)


if __name__ == "__main__":
    sys.exit(_main())
