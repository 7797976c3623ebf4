"""chip_smoke.py on the CPU: it must refuse to stand in for the chip,
and its strict accounting must catch a host fallback."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _last_json_line(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])


def test_refuses_the_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, SCRIPT], capture_output=True,
                       text=True, timeout=240, env=env, cwd=REPO)
    assert p.returncode != 0
    last = _last_json_line(p.stdout)
    assert last["ok"] is not True
    assert last["device"]["platform"] == "cpu"


def test_refuses_outside_the_repo(tmp_path):
    """A directory holding chip_smoke.py and nothing else of the repo
    cannot pass: the program it drives is missing."""
    lone = tmp_path / "chip_smoke.py"
    lone.write_bytes(open(SCRIPT, "rb").read())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, str(lone)], capture_output=True,
                       text=True, timeout=240, env=env, cwd=tmp_path)
    assert p.returncode != 0
    assert _last_json_line(p.stdout)["ok"] is not True


def test_reference_batch_rejects_every_seeded_bad_row(smoke):
    items, n_bad = smoke.txset_batch(64, seed=3)
    want = smoke.reference_verdicts(items)
    assert n_bad == 7 * smoke.BAD_PER_CLASS
    assert int(want.sum()) == len(items) - n_bad


def test_strict_accounting_passes_a_clean_device_phase(smoke):
    from stellar_tpu.crypto import batch_verifier as bv
    from stellar_tpu.utils import faults
    faults.clear()
    bv._reset_dispatch_state_for_testing()
    items = smoke.txset_batch(64, seed=5)[0][:8]
    want = smoke.reference_verdicts(items)
    v = bv.BatchVerifier(bucket_sizes=(8,))
    rec = smoke.run_phase(
        "clean", lambda: smoke.check_verdicts(v.verify_batch(items), want,
                                              "clean"))
    assert rec["ok"], rec


@pytest.mark.chaos
def test_strict_accounting_fails_on_an_armed_fault(smoke, caplog):
    """A dispatch fault makes the engine re-verify on the host: the
    verdicts stay right, and the phase must still fail. The first
    failure is logged with its traceback."""
    import logging

    from stellar_tpu.crypto import batch_verifier as bv
    from stellar_tpu.utils import faults
    bv._reset_dispatch_state_for_testing()
    items = smoke.txset_batch(64, seed=5)[0][:8]
    want = smoke.reference_verdicts(items)
    v = bv.BatchVerifier(bucket_sizes=(8,))
    faults.set_fault(faults.DISPATCH, "raise")
    caplog.set_level(logging.WARNING, logger="stellar_tpu.crypto")
    try:
        rec = smoke.run_phase(
            "faulted", lambda: smoke.check_verdicts(
                v.verify_batch(items), want, "faulted"))
    finally:
        faults.clear()
        bv._reset_dispatch_state_for_testing()
    assert not rec["ok"]
    text = " ".join(rec["problems"])
    assert "host_fallback rows" in text and "faults armed" in text
    failures = [r for r in caplog.records if "dispatch failed" in r.message]
    assert failures and failures[0].exc_info is not None
    assert all(r.exc_info is None for r in failures[1:])


@pytest.mark.parametrize("env_dir", [None, "given"])
def test_compile_cache_placement(tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins when set; otherwise the cache is
    the fixed <checkout>/.jax_cache — never a temporary name."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    want = os.path.join(REPO, ".jax_cache")
    if env_dir:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    code = ("import jax\n"
            "from stellar_tpu.utils.jax_setup import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120, env=env, cwd=REPO)
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.split() == [want, want]
