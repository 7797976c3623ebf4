"""Signer-table churn against the plain reference (CPU, small size).

``BatchVerifier`` (the real kernels on JAX's CPU backend) driven by the
benchmark's own generator on the shape of the
``validator-txset-1000-mainnet-zipf`` deployment, cut to 48 txs a call
and a Zipf(1.0) population of 5,000 accounts, behind a 32-table signer
cache: first-sight builds while the budget has room, second-sight
admission once it is full, LRU evictions, and the hot and cold parts of
one call, with every verdict checked against ``benchmark/reference.py``.
Every part stays within one 128-row bucket. A replay of the admission
policy on the full-size cell's key draws (key indices only, no signing)
pins the build rate the policy was sized by.
"""

import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from benchmark import reference
from benchmark import traffic as tr
from stellar_tpu.crypto import batch_verifier as bv
from stellar_tpu.crypto.batch_verifier import BatchVerifier
from stellar_tpu.parallel import signer_tables as st

ROOT = Path(__file__).resolve().parents[1]
SEED = 2 ** 43 + 25
CALLS = 6
BUDGET = 32  # tables


@pytest.fixture
def small_cache():
    bv._reset_dispatch_state_for_testing()
    st.signer_table_cache.configure(max_bytes=BUDGET * st.TABLE_BYTES,
                                    enabled=True)
    yield st.signer_table_cache
    bv._reset_dispatch_state_for_testing()
    st.signer_table_cache.configure(max_bytes=st.DEFAULT_CACHE_BYTES,
                                    enabled=True)


def _traffic():
    cfg = json.loads((ROOT / "benchmark/configs/"
                      "validator-txset-1000-mainnet-zipf.json").read_text())
    mix = json.loads((ROOT / "benchmark/traffic/txset-zipf.json")
                     .read_text())
    mix = dict(mix, call_size={"law": "fixed", "units": 48},
               signers=dict(mix["signers"], accounts=5000))
    return tr.Traffic(cfg, mix, SEED)


def _tape(monkeypatch, cache):
    """Record what the partition asks of the cache, call by call: each
    lookup and whether it hit, installs per call, and for each install
    the key's misses since its last install, split by whether the
    budget was full when it came."""
    tape = {"lookups": [], "installs": [], "misses": Counter(),
            "room": [], "full": []}
    real_lookup, real_install = cache.lookup, cache.install

    def lookup(pk):
        tab = real_lookup(pk)
        tape["lookups"][-1].append((pk, tab is not None))
        if tab is None:
            tape["misses"][pk] += 1
        return tab

    def install(pk, table):
        full = cache.snapshot()["entries"] == BUDGET
        tape["full" if full else "room"].append(tape["misses"].pop(pk))
        tape["installs"][-1] += 1
        return real_install(pk, table)

    monkeypatch.setattr(cache, "lookup", lookup)
    monkeypatch.setattr(cache, "install", install)
    return tape


def _drive(v, tape):
    t = _traffic()
    for i in range(CALLS):
        rows = t.call(i)
        assert len(rows) == 96
        tape["lookups"].append([])
        tape["installs"].append(0)
        got = v.verify_batch(rows)
        want, _gate = reference.verdicts(rows, 0)
        assert [bool(g) for g in got] == want, f"call {i}"
        assert not all(want) and any(want)


def test_zipf_churn_verdicts_match_the_reference(small_cache, monkeypatch):
    tape = _tape(monkeypatch, small_cache)
    v = BatchVerifier(bucket_sizes=(128,))
    _drive(v, tape)

    snap = small_cache.snapshot()
    assert snap["evictions"] > 0 and snap["deferred"] > 0
    assert snap["audit_evictions"] == 0
    assert snap["entries"] == BUDGET
    # a call with a hot part and a cold part
    lookups = tape["lookups"]
    assert any(any(h for _, h in c) and not all(h for _, h in c)
               for c in lookups)
    # while the budget has room a key's install follows its first
    # miss; once it is full, its second (or a later one, when the
    # seen-once set dropped it in between)
    assert len(tape["room"]) == BUDGET and set(tape["room"]) == {1}
    assert tape["full"] and min(tape["full"]) >= 2
    assert 0 < snap["evicted_unused"] <= snap["evictions"]
    assert v.served["host-fallback"] == 0
    assert v.served["device"] > 0

    # the same traffic behind a cache forced to admit on first sight
    # builds more tables in every call once the budget is full
    monkeypatch.undo()
    small_cache._reset_for_testing()
    small_cache.configure(max_bytes=BUDGET * st.TABLE_BYTES, enabled=True)
    forced = _tape(monkeypatch, small_cache)
    monkeypatch.setattr(small_cache, "admit", lambda pk: True)
    _drive(v, forced)
    assert small_cache.snapshot()["deferred"] == 0
    assert all(a < b for a, b in zip(tape["installs"][1:],
                                      forced["installs"][1:]))
    assert sum(tape["installs"]) < sum(forced["installs"])


REPLAY_SEED = 5025000000101  # one of the seeds the policy was sized on
REPLAY_CALLS = 30


def _replay_builds(admit_first_sight: bool) -> list:
    """Builds per window call of the full-size ``txset-zipf`` cell under
    the real admission policy, replayed on the harness's own key draws
    (key indices only, no signing): a 4,369-table cache warmed with the
    4,369 heaviest keys, then the two warm-up calls of the mix, then
    the window's calls. ``admit_first_sight`` replays the rule before
    second-sight admission: every miss builds."""
    cfg = json.loads((ROOT / "benchmark/configs/"
                      "validator-txset-1000-mainnet-zipf.json").read_text())
    mix = json.loads((ROOT / "benchmark/traffic/txset-zipf.json")
                     .read_text())
    t = tr.Traffic(cfg, mix, REPLAY_SEED)
    k = t.keys_per_account
    capacity = mix["warmup"]["signers"]
    cache = st.SignerTableCache(max_bytes=capacity * st.TABLE_BYTES)
    table = np.zeros((st.TABLE_ENTRIES, 3, 20), dtype=np.int16)

    def call(keys) -> int:
        builds = 0
        for key in keys:
            pk = int(key).to_bytes(32, "little")
            if cache.lookup(pk) is None and (
                    admit_first_sight or cache.admit(pk)):
                cache.install(pk, table)
                builds += 1
        return builds

    def drawn(stream, i):
        accounts = t._accounts(tr._rng(REPLAY_SEED, stream, 2, i), 0,
                               t.units)
        return [int(a) * k + j for a in accounts for j in range(k)]

    call(range(capacity))
    assert cache.snapshot()["entries"] == capacity
    for i in range(mix["warmup"]["calls"]):
        call(drawn(t.WARMUP, i))
    return [call(drawn(t.WINDOW, i)) for i in range(REPLAY_CALLS)]


def test_second_sight_admission_replayed_on_the_cells_key_draws():
    second = _replay_builds(admit_first_sight=False)
    first = _replay_builds(admit_first_sight=True)
    assert max(second) < 100
    assert min(first) > 900
