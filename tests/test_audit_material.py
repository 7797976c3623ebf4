"""The bytes that seed the result-integrity audit's sample
(``Workload.audit_material``, ``docs/robustness.md`` "Sampled
result-integrity audit").

The cold verify and SHA-256 plugins seed from every encoded array, as
the engine always did, so their samples are pinned byte for byte. The
hot verify plugin seeds from ``(R, S, h)`` alone: its per-row signer
table is a function of the public key, which ``h`` already commits to.
The last test runs a hot part through the engine at the default rate
and corrupts exactly the row the plugin's material picks.
"""

import hashlib
import secrets

import numpy as np
import pytest

from stellar_tpu.crypto import audit
from stellar_tpu.crypto import batch_verifier as bv
from stellar_tpu.crypto import ed25519_ref as ref
from stellar_tpu.crypto.batch_hasher import Sha256Workload
from stellar_tpu.crypto.batch_verifier import (
    BatchVerifier, Ed25519HotWorkload, Ed25519Workload)
from stellar_tpu.parallel import batch_engine
from stellar_tpu.parallel import signer_tables as st
from stellar_tpu.utils import tracing


@pytest.fixture
def fresh_dispatch():
    bv._reset_dispatch_state_for_testing()
    st.signer_table_cache.configure(
        max_bytes=st.DEFAULT_CACHE_BYTES, enabled=True)
    yield
    bv._reset_dispatch_state_for_testing()
    st.signer_table_cache.configure(
        max_bytes=st.DEFAULT_CACHE_BYTES, enabled=True)


def _random_triples(n):
    """Well-formed (pk, msg, sig) byte triples; encode needs no valid
    signature, only the lengths."""
    return [(secrets.token_bytes(32), secrets.token_bytes(1 + i % 40),
             secrets.token_bytes(64)) for i in range(n)]


def _signed_triples(n):
    """Valid rows from fixed seeds, so the sample they draw is fixed."""
    items = []
    for i in range(n):
        seed = hashlib.sha256(b"audit material %d" % i).digest()
        msg = b"row %d" % i
        items.append((ref.secret_to_public(seed), msg,
                      ref.sign(seed, msg)))
    return items


def _table(fill):
    return np.full((st.TABLE_ENTRIES, 3, 20), fill, dtype=np.int16)


def _old_material(encoded, lo, hi):
    # the engine's inline seed before plugins chose it
    return b"".join(x[lo:hi].tobytes() for x in encoded)


def test_hot_material_ignores_table_bytes():
    triples = _random_triples(8)
    hot = Ed25519HotWorkload()
    _g1, enc1 = hot.encode([(t, _table(0)) for t in triples])
    _g2, enc2 = hot.encode([(t, _table(7)) for t in triples])
    assert enc1[0].tobytes() != enc2[0].tobytes()
    for lo, hi in ((0, 8), (2, 5)):
        assert hot.audit_material(enc1, lo, hi) == \
            hot.audit_material(enc2, lo, hi)


@pytest.mark.parametrize("which", [1, 2, 3], ids=["r", "s", "h"])
def test_hot_material_binds_r_s_and_h(which):
    hot = Ed25519HotWorkload()
    _g, enc = hot.encode([(t, _table(0)) for t in _random_triples(8)])
    before = hot.audit_material(enc, 0, 8)
    changed = list(enc)
    changed[which] = enc[which].copy()
    changed[which][5, 0] ^= 1
    assert hot.audit_material(tuple(changed), 0, 8) != before
    # a row outside the part leaves the part's material as it was
    assert hot.audit_material(tuple(changed), 0, 5) == \
        hot.audit_material(enc, 0, 5)


@pytest.mark.parametrize("plugin", [Ed25519Workload(), Sha256Workload()],
                         ids=["ed25519", "sha256"])
def test_default_material_is_the_old_join(plugin):
    """The cold verify and hash plugins keep the default: their audit
    samples are the same as before plugins chose the seed."""
    if isinstance(plugin, Sha256Workload):
        items = [secrets.token_bytes(i * 37 % 600) for i in range(12)]
    else:
        items = _random_triples(12)
    _gate, enc = plugin.encode(items)
    for lo, hi in ((0, 12), (3, 7), (11, 12)):
        assert plugin.audit_material(enc, lo, hi) == \
            _old_material(enc, lo, hi)


def test_hot_material_stays_96_bytes_a_row():
    """A full 2,000-row hot part seeds from at most 96 bytes a row, so
    the ~15.4 KB tables cannot creep back into the seed."""
    n = 2000
    table = _table(3)
    hot = Ed25519HotWorkload()
    _g, enc = hot.encode([(t, table) for t in _random_triples(n)])
    material = hot.audit_material(enc, 0, n)
    assert len(material) <= n * 96
    assert enc[0].nbytes == n * st.TABLE_BYTES


def test_flatnonzero_eligible_matches_comprehension():
    rng = np.random.default_rng(0xA0D1)
    gate = rng.random(300) < 0.6
    gate[:3] = [True, False, True]
    for lo, hi in ((0, 300), (0, 1), (1, 2), (17, 140), (299, 300),
                   (50, 50)):
        old = [i for i in range(hi - lo) if gate[lo + i]]
        new = np.flatnonzero(gate[lo:hi]).tolist()
        assert new == old
        assert all(type(i) is int for i in new)


def test_audit_convicts_the_row_the_hot_material_picks(fresh_dispatch,
                                                       monkeypatch):
    """At the default rate a 16-row hot part audits one row: the one
    ``sample_rows(plugin.audit_material(...))`` picks. Corrupting
    another row goes unseen; corrupting that row convicts the device
    and evicts the tables that served the part."""
    assert batch_engine.AUDIT_RATE == 0.02
    n = 16
    items = _signed_triples(n)
    tables = [st.build_signer_table(pk) for pk, _m, _s in items]
    for (pk, _m, _s), tab in zip(items, tables):
        assert st.signer_table_cache.install(pk, tab)
    v = BatchVerifier(bucket_sizes=(n,))
    gate, enc = v._hot.encode(list(zip(items, tables)))
    assert gate.all()
    picks = audit.sample_rows(v._hot.audit_material(enc, 0, n),
                              np.flatnonzero(gate).tolist(),
                              batch_engine.AUDIT_RATE)
    assert len(picks) == 1
    picked = picks[0]
    # the tables would have drawn another row: a seed that still held
    # them would miss the corruption below
    assert audit.sample_rows(_old_material(enc, 0, n), list(range(n)),
                             batch_engine.AUDIT_RATE) != picks

    real_fetch = batch_engine._fetch
    flip = {}

    def corrupting_fetch(dev, dev_idx=None, span_ns="verify",
                         traces=None):
        got = np.array(real_fetch(dev, dev_idx, span_ns, traces))
        got[flip["row"]] = np.logical_not(got[flip["row"]])
        return got

    monkeypatch.setattr(batch_engine, "_fetch", corrupting_fetch)

    # another row corrupted: the audit's one draw misses it, so the
    # wrong verdict surfaces and nothing is convicted
    flip["row"] = (picked + 1) % n
    tracing.flight_recorder.clear()
    got = v.verify_batch(items)
    assert not got[flip["row"]] and got.sum() == n - 1
    assert v.served["device"] == n and v.audit_mismatches == 0
    assert st.signer_table_cache.snapshot()["audit_evictions"] == 0
    sampled = [r for r in tracing.flight_recorder.snapshot(
        limit=4096)["recent"] if r["name"] == "span.verify.audit.sample"]
    assert [r["attrs"]["material_bytes"] for r in sampled] == [n * 96]

    # the picked row corrupted: convicted, host re-computed, evicted
    flip["row"] = picked
    got = v.verify_batch(items)
    assert got.all()
    assert v.audit_mismatches == 1
    assert bv.dispatch_health()["host_only"] is True
    assert st.signer_table_cache.lookup(items[picked][0]) is None
    assert st.signer_table_cache.snapshot()["audit_evictions"] == n
