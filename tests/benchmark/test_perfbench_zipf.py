"""The ``txset-zipf`` cell on the CPU: its plan resolves from the files,
the deployment and mix are the ones named, a rehearsal ends correct,
its two readers read the table-build timer, and no row repeats."""

import json
from pathlib import Path

import pytest

from benchmark import harness
from benchmark import traffic as tr
from stellar_tpu.parallel import signer_tables as st

ROOT = Path(__file__).resolve().parents[2]
SEED = 2 ** 42 + 25
CELL = "txset-zipf"


@pytest.fixture(autouse=True)
def _host_backend_restored():
    yield
    from stellar_tpu.crypto import keys
    keys.set_verifier_backend(None)
    keys.flush_verify_cache()


def test_the_plan_resolves_with_the_cells_metrics():
    p = harness.plan(ROOT, CELL)
    assert p["cell"]["config"] == "validator-txset-1000-mainnet-zipf"
    assert p["cell"]["chips"] == 1
    assert {m["name"] for m in p["end_to_end"]} == {
        "verify_p50_ms", "sigs_per_s", "setup_s"}
    assert {m["name"] for m in p["per_layer"]} == {
        "hot_row_share", "prep_ms", "kernel_ms", "fetch_ms",
        "device_idle_share", "table_build_ms", "table_builds_per_call"}


def test_the_deployment_and_mix_are_as_named():
    p = harness.plan(ROOT, CELL)
    cfg, mix = p["config"], p["mix"]
    assert (cfg["unit"], cfg["call_size"], cfg["keys_per_account"],
            cfg["signatures_per_tx"]) == (
        "tx", {"law": "fixed", "units": 1000}, 2, 2)
    assert cfg["reference"] == "benchmark/reference.py"
    assert mix["signers"] == {"law": "zipf", "s": 1.0,
                              "accounts": cfg["accounts"]}
    assert cfg["accounts"] == 1_000_000
    entry = next(c for c in harness.load_spec(ROOT)["configs"]
                 if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"] == ["accounts"]
    assert mix["nonces_per_key"] == 0
    assert mix["invalid"]["per_call"] == {c: 4 for c in tr.CLASSES}
    assert mix["loop"] == "closed, 1 caller" and "arrivals" not in mix
    # set-up fills the default signer cache with the heaviest keys
    capacity = st.DEFAULT_CACHE_BYTES // st.TABLE_BYTES
    assert cfg["signer_table_cache_bytes"] == st.DEFAULT_CACHE_BYTES
    assert mix["warmup"] == {"signers": capacity, "invalid_keys": True,
                             "calls": 2} and capacity == 4369


def test_a_rehearsal_ends_correct_with_the_cells_names(capsys):
    argv = ["--workload", CELL, "--seed", str(SEED), "--seconds", "0.5",
            "--trace", "0", "--cpu-rehearsal", "40"]
    rc = harness.main(argv, root=ROOT)
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    last = lines[-1]
    assert rc == 1
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0 and last["metrics"] == {}
    assert lines[0]["rows"] == 80 * last["attempted"]
    assert lines[0]["compiles_in_window"] == 0
    assert set(lines[1]["rehearsal_metrics"]) == {
        "verify_p50_ms", "sigs_per_s", "setup_s"}


def _ctx(spans, calls=4):
    return {"calls": [{"rows": [b"r"]}] * calls, "window_s": 1.0,
            "setup_s": 1.0, "trace": None, "spans": spans,
            "accounting": {"hot_rows": 0, "cold_rows": 0}}


@pytest.mark.parametrize("name,want", [("table_build_ms", 3300.0 / 4),
                                       ("table_builds_per_call", 1070 / 4)])
def test_the_table_build_readers(name, want):
    read = harness.reader(ROOT, name)
    spans = {"span.verify.table_build": {"count": 1070, "sum_ms": 3300.0},
             "span.verify.prep.partition": {"count": 4, "sum_ms": 3400.0}}
    assert read(_ctx(spans)) == pytest.approx(want)
    assert read(_ctx({"span.verify.prep": {"count": 4, "sum_ms": 9.0}})) \
        is None
    assert read(_ctx(spans, calls=0)) is None


def test_no_row_repeats_within_a_run():
    p = harness.plan(ROOT, CELL)
    mix = dict(p["mix"], call_size={"law": "fixed", "units": 100})
    t = tr.Traffic(p["config"], mix, SEED)
    warm = t.warmup()
    assert sum(len(c) for c in warm[:-2]) == 4369 + len(tr.INVALID_KEYS)
    rows = [r for c in warm for r in c]
    rows += [r for i in range(30) for r in t.call(i)]
    assert len(set(rows)) == len(rows)
    # fresh nonces: no signer's R repeats either (the r_off_curve and
    # r_small_order rows carry fixed encodings in its place)
    fixed = {tr.R_OFF_CURVE, *tr.SMALL_ORDER}
    big_r = [r[2][:32] for r in rows if r[2][:32] not in fixed]
    assert len(set(big_r)) == len(big_r) > len(rows) * 0.9
    # the skew: heavy accounts repeat within a call, the tail does not
    keys = [r[0] for r in t.call(30)]
    assert len(set(keys)) < len(keys)
