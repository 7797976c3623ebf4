"""The benchmark harness on the CPU at tiny sizes: the rehearsal's last
line, the refusal without a chip, a cell added from files alone, and
`correct` coming out false under the control and under each fault the
cells can have."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark import harness

ROOT = Path(__file__).resolve().parents[2]
SEED = 2 ** 41 + 3
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(autouse=True)
def _host_backend_restored():
    yield
    from stellar_tpu.crypto import keys
    keys.set_verifier_backend(None)
    keys.flush_verify_cache()


def _run(capsys, cell, *extra, root=ROOT, path=None, trace=0):
    argv = ["--workload", cell, "--seed", str(SEED), "--seconds", "0.5",
            "--trace", str(trace), "--cpu-rehearsal", "40", *extra]
    rc = harness.main(argv, root=root, path=path)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, [json.loads(line) for line in out]


@pytest.mark.parametrize("cell", ["txset-multisig", "scp-envelopes"])
def test_rehearsal_emits_a_well_formed_last_line(capsys, cell):
    rc, lines = _run(capsys, cell)
    last = lines[-1]
    assert rc == 1  # a rehearsal is never a result
    assert RESULT_KEYS <= set(last) and list(last)[-1] == "checks"
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert last["metrics"] == {}  # no number under a device metric
    assert last["device"]["platform"] == "cpu"
    info = lines[0]
    assert info["compiles_in_window"] == 0 and info["resident_hits"] == 0
    want = {m["name"] for m in harness.plan(ROOT, cell)["end_to_end"]}
    assert set(lines[1]["rehearsal_metrics"]) == want
    assert "setup_s" in want and len(want) >= 3


def test_every_metric_is_read_by_a_reader_of_its_own():
    """Each metric of BENCHMARK.json has ``metrics/<name>.py``; the
    per-envelope names read what their tx-set namesakes read."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    ctx = {"calls": [{"rows": [b"r"] * 4, "latency_s": 0.01 * k}
                     for k in range(1, 21)],
           "window_s": 2.0, "setup_s": 3.0, "trace": None,
           "spans": {"span.attr.verify.prep": {"count": 20, "sum_ms": 40.0},
                     "span.verify.dispatch": {"count": 20, "sum_ms": 1.0}},
           "accounting": {"hot_rows": 3, "cold_rows": 1}}
    got = {m["name"]: harness.reader(ROOT, m["name"])(ctx)
           for m in spec["end_to_end"] + spec["per_layer"]}
    for name, value in got.items():
        base = name.replace(".envelope", "").replace("envelope_", "verify_")
        assert value == got[base]
    assert got["envelope_p50_ms"] == pytest.approx(105.0)
    assert got["prep_ms.envelope"] == 2.0 and got["kernel_ms"] is None


def test_run_command_refuses_without_a_chip(tmp_path):
    """The run command on a host with no TPU exits non-zero and prints
    no result; so does a checkout of the benchmark's files alone."""
    only = tmp_path / "only"
    only.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", only)
    for p in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / p, only / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for root in (ROOT, only):
        r = subprocess.run(
            [sys.executable, "benchmark/run.py", "--workload",
             "txset-multisig", "--seed", str(SEED), "--seconds", "1",
             "--trace", "0"], cwd=root, env=env, capture_output=True,
            text=True, timeout=120)
        assert r.returncode != 0
        assert '"correct"' not in r.stdout


def test_a_cell_is_added_by_files_alone(capsys, tmp_path):
    """A configuration, a traffic mix, a per-layer metric and a cell,
    added as new files and new entries in a copy: the harness finds
    them by name and edits nothing."""
    root = tmp_path / "repo"
    shutil.copytree(ROOT / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "benchmark/configs/validator-txset-1000.json")
                     .read_text())
    (root / "benchmark/configs/dummy-txset.json").write_text(
        json.dumps(dict(cfg, name="dummy-txset", keys_per_account=1)))
    (root / "benchmark/traffic/dummy-mix.json").write_text(json.dumps(
        {"signers": {"law": "zipf", "s": 1.1, "accounts": 500},
         "warmup": {"signers": 8, "calls": 1}}))
    (root / "benchmark/metrics/dummy_rows_per_call.py").write_text(
        "def read(ctx):\n"
        "    return sum(len(c['rows']) for c in ctx['calls'])"
        " / len(ctx['calls'])\n")
    spec["configs"].append(dict(spec["configs"][0], name="dummy-txset",
                                file="benchmark/configs/dummy-txset.json"))
    spec["workloads"].append({"name": "dummy-cell", "config": "dummy-txset",
                              "traffic": "dummy-mix", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "dummy_rows_per_call", "unit": "count",
                              "better": "higher", "source": "host_clock",
                              "layer": "test", "moves": "sigs_per_s",
                              "workloads": ["dummy-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    rc, lines = _run(capsys, "dummy-cell", "--keep-trace",
                     str(tmp_path / "kept"), root=root, trace=1)
    assert lines[-1]["correct"] is True
    got = lines[1]["rehearsal_metrics"]
    assert got["dummy_rows_per_call"]["value"] == 40.0  # 1 key per tx
    assert "prep_ms" not in got  # the host oracle has no verify spans
    from benchmark import trace_reduce
    kept = trace_reduce.load(str(tmp_path / "kept" / "events.json.gz"))
    assert [e[0] for e in kept["host"]][:2] == ["bench.window",
                                                "bench.generate"]


class _AlteredAnswer(harness.HostOraclePath):
    """An answer altered where it is produced: the backend flips every
    seventh verdict it computes."""

    name = "fault:altered-answer"

    def setup(self):
        from stellar_tpu.crypto import ed25519_ref, keys
        n = [0]

        def flipping(pk, msg, sig):
            n[0] += 1
            ok = ed25519_ref.verify(pk, msg, sig)
            return (not ok) if n[0] % 7 == 0 else ok
        keys.set_verifier_backend(flipping)


class _HalfLeftOut(harness.HostOraclePath):
    """Half of the rows never reach the verifier: half of each call's,
    or every second one-envelope call. Their verdicts never come."""

    name = "fault:half-left-out"
    _n = 0

    def call(self, prepared):
        self._n += 1
        if len(prepared) > 1:
            return super().call(prepared[:len(prepared) // 2])
        return super().call(prepared) if self._n % 2 else []


@pytest.mark.parametrize("cell", ["txset-multisig", "scp-envelopes"])
@pytest.mark.parametrize("fault,number", [(_AlteredAnswer, "mismatched_rows"),
                                          (_HalfLeftOut, "unanswered_rows")])
def test_a_broken_timed_path_is_not_correct(capsys, cell, fault, number):
    rc, lines = _run(capsys, cell, path=fault())
    last = lines[-1]
    assert last["correct"] is False
    assert last["checks"]["wrong_rows"]["value"] > 0
    assert lines[0][number] > 0


@pytest.mark.parametrize("cell", ["txset-multisig", "scp-envelopes"])
def test_the_control_is_not_correct(capsys, tmp_path, cell):
    """The reference with libsodium's policy gate dropped, in the
    program's place, accepts the S + L rows both mixes carry (the
    rehearsal's one burst of envelopes is given one in every 4)."""
    root = tmp_path / "repo"
    shutil.copytree(ROOT / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root)
    mix = root / "benchmark/traffic/scp-envelopes.json"
    m = json.loads(mix.read_text())
    m["invalid"]["every"] = 4
    mix.write_text(json.dumps(m))
    rc, lines = _run(capsys, cell, "--control", "no_policy_gate", root=root)
    last = lines[-1]
    assert last["correct"] is False
    assert lines[0]["mismatched_rows"] > 0
    assert lines[0]["unanswered_rows"] == 0


class _Sleepy:
    """A stand-in path whose every call takes 20 ms."""

    on_device = False

    def prepare(self, rows):
        return rows

    def call(self, rows):
        import time
        time.sleep(0.02)
        return [True] * len(rows)

    def readback(self, rows, got):
        return got


def test_a_burst_waits_for_its_arrival_and_queues_its_calls():
    """Open loop: a burst's calls all arrive at its instant, and each
    call's latency runs from there, so it pays for the calls before
    it; the window ends before the first burst due at its close."""
    from benchmark import traffic as tr
    cfg, mix = (json.loads((ROOT / "benchmark" / d / f"{n}.json")
                           .read_text())
                for d, n in (("configs", "scp-storm-16"),
                             ("traffic", "scp-envelopes")))
    mix = dict(mix, arrivals={"law": "bursts", "size": 3, "every_s": 0.1})
    t = tr.Traffic(cfg, mix, SEED)
    w = harness.window(t, _Sleepy(), 0.15, lambda _n: _nullctx())
    calls = w["calls"]
    assert len(calls) == 6 and len(w["groups"]) == 2
    assert [g["calls"] for g in w["groups"]] == [[0, 1, 2], [3, 4, 5]]
    for g in range(2):
        lat = [c["latency_s"] for c in calls[3 * g:3 * g + 3]]
        assert 0.018 < lat[0] < lat[1] - 0.018 < lat[2] - 0.036 + 0.01
        assert calls[3 * g]["t0"] >= w["t_open"] + 0.1 * g
    assert all(c["verdicts"] == [True] for c in calls)


def _nullctx():
    import contextlib
    return contextlib.nullcontext()


def test_the_envelope_entry_refuses_a_row_that_is_no_envelope():
    from benchmark import traffic as tr
    cfg, mix = (json.loads((ROOT / "benchmark" / d / f"{n}.json")
                           .read_text())
                for d, n in (("configs", "scp-storm-16"),
                             ("traffic", "scp-envelopes")))
    t = tr.Traffic(cfg, mix, SEED)
    row = t.call(0)[0]
    path = harness.ProgramPath()
    path.bind("verify_envelope", t.network_id)
    envs = path.prepare([row])
    assert envs[0].signature == row[2]
    assert envs[0].statement.nodeID.value == row[0]
    for bad in ((row[0], row[1][:-1] + b"x", row[2]),
                (row[0], row[1][:40], row[2]),
                (t.signer(1).pk, row[1], row[2])):
        with pytest.raises(harness.SpecError):
            path.prepare([bad])
    with pytest.raises(harness.SpecError):
        path.bind("verify_sig", b"")
