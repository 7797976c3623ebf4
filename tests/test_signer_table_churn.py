"""Signer-table churn against the plain reference (CPU, small size).

``BatchVerifier`` (the real kernels on JAX's CPU backend) driven by the
benchmark's own generator on the shape of the
``validator-txset-1000-mainnet-zipf`` deployment, cut to 48 txs a call
and a Zipf(1.0) population of 5,000 accounts, behind a 32-table signer
cache: first-sight builds, LRU evictions and rebuilds, and the hot and
cold parts of one call, with every verdict checked against
``benchmark/reference.py``. Every part stays within one 128-row bucket.
"""

import json
from collections import Counter
from pathlib import Path

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


def test_zipf_churn_verdicts_match_the_reference(small_cache, monkeypatch):
    lookups, installs = [], Counter()
    real_lookup, real_install = small_cache.lookup, small_cache.install

    def lookup(pk):
        tab = real_lookup(pk)
        lookups[-1].append((pk, tab is not None))
        return tab

    def install(pk, table):
        installs[pk] += 1
        return real_install(pk, table)

    monkeypatch.setattr(small_cache, "lookup", lookup)
    monkeypatch.setattr(small_cache, "install", install)
    t = _traffic()
    v = BatchVerifier(bucket_sizes=(128,))
    seen = set()
    first_sight_cold_then_hot = 0
    for i in range(CALLS):
        rows = t.call(i)
        assert len(rows) == 96
        lookups.append([])
        got = v.verify_batch(rows)
        want, _gate = reference.verdicts(rows, 0)
        assert [bool(g) for g in got] == want, f"call {i}"
        assert not all(want) and any(want)
        first = {}
        for pk, hit in lookups[-1]:
            if pk not in first:
                first[pk] = hit
            elif not first[pk] and hit and pk not in seen:
                first_sight_cold_then_hot += 1
        seen |= set(first)

    snap = small_cache.snapshot()
    assert snap["installs"] > BUDGET and snap["evictions"] > 0
    assert snap["audit_evictions"] == 0
    assert snap["entries"] == BUDGET
    # a call with a hot part and a cold part
    assert any(any(h for _, h in c) and not all(h for _, h in c)
               for c in lookups)
    # installs follow misses only, and nothing but the budget evicts:
    # a key installed twice was evicted and rebuilt
    assert max(installs.values()) >= 2
    assert first_sight_cold_then_hot > 0
    assert 0 < snap["evicted_unused"] <= snap["evictions"]
    assert v.served["host-fallback"] == 0
    assert v.served["device"] > 0
