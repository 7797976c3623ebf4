"""Bench harness contracts the driver relies on (no device needed)."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_refuses_a_host_without_a_tpu():
    """On a CPU-only host bench.py measures nothing: it exits non-zero
    with one error line and prints no record, old or new."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       capture_output=True, text=True, timeout=240,
                       env=env, cwd=REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    err = [ln for ln in p.stderr.splitlines() if ln.startswith("bench.py:")]
    assert len(err) == 1 and "no TPU" in err[0], p.stderr[-2000:]
