"""Hot-signer table cache (PR 16): builder-vs-oracle pins, LRU/byte-budget
semantics, the radix-256 signed recode, the partitioning submit, and the
hot-kernel differential.

Layering mirrors the module split: Sections A-B exercise
``stellar_tpu.parallel.signer_tables`` with no jax at all (the module's
own contract — it must stay importable and correct without a backend);
Section C pins the byte-aligned recode the hot kernel consumes; Section D
drives the partition in ``BatchVerifier.submit`` under host-only dispatch
(no kernel compiles — the partition, cache traffic, and index merge are
host-side and identical either way); Section E is the real-device
differential: hot-served verdicts bit-identical to the libsodium-exact
oracle at every bucket size, with an explicit anti-vacuity check that the
cache actually served rows. The 10k repeat-signer sweep is ``-m slow``.
"""

import secrets

import numpy as np
import pytest

from stellar_tpu.crypto import batch_verifier as bv
from stellar_tpu.crypto import ed25519_ref as ref
from stellar_tpu.crypto.batch_verifier import BatchVerifier
from stellar_tpu.parallel import signer_tables as st

from test_verify_differential import (  # noqa: F401  (same-dir import)
    _keypair, check, edge_corpus, make_valid)

RNG = np.random.default_rng(0x516E)


@pytest.fixture
def fresh_dispatch():
    """Process-start dispatch state before AND after: the signer-table
    cache is process-wide, and these tests mutate its knobs."""
    bv._reset_dispatch_state_for_testing()
    st.signer_table_cache.configure(
        max_bytes=st.DEFAULT_CACHE_BYTES, enabled=True)
    yield
    bv._reset_dispatch_state_for_testing()
    st.signer_table_cache.configure(
        max_bytes=st.DEFAULT_CACHE_BYTES, enabled=True)


# --------------- A: fingerprint + table builder vs oracle ---------------


def test_fingerprint_is_content_keyed():
    import hashlib
    _seed, pk = _keypair()
    fp = st.signer_fingerprint(pk)
    assert fp == hashlib.sha256(pk).digest()[:16] and len(fp) == 16
    assert fp == st.signer_fingerprint(pk)
    flipped = bytes([pk[0] ^ 1]) + pk[1:]
    assert st.signer_fingerprint(flipped) != fp


def test_build_table_geometry_and_limb_packing():
    """The builder's rows ARE the oracle's affine rows of -A, packed as
    canonical 13-bit limbs — reconstructing every limb vector must give
    back the oracle integer exactly (the fe.from_int twin pin)."""
    _seed, pk = _keypair()
    table = st.build_signer_table(pk)
    assert table is not None
    assert table.shape == (st.TABLE_ENTRIES, 3, 20)
    assert table.dtype == np.int16
    assert int(table.min()) >= 0 and int(table.max()) <= 8191
    cache = st.SignerTableCache(max_bytes=st.TABLE_BYTES)
    cache.install(pk, table)                   # install freezes aliasing
    assert table.flags.writeable is False
    pt = ref.point_decompress(pk)
    neg = (ref.P - pt[0], pt[1], pt[2], (ref.P - pt[3]) % ref.P)
    rows = ref.affine_table_rows(neg, st.TABLE_ENTRIES)
    for i in (0, 1, 63, st.TABLE_ENTRIES - 1):
        for j in range(3):
            got = sum(int(table[i, j, k]) << (13 * k) for k in range(20))
            assert got == rows[i][j], (i, j)


def test_table_rows_are_multiples_of_negated_point():
    """Independent recomputation: row v-1 must encode v * (-A) in the
    (y+x, y-x, 2dxy) affine form, for multiples derived one point_add at
    a time (not through affine_table_rows' batched-inversion path)."""
    _seed, pk = _keypair()
    table = st.build_signer_table(pk)
    pt = ref.point_decompress(pk)
    neg = (ref.P - pt[0], pt[1], pt[2], (ref.P - pt[3]) % ref.P)
    q = neg
    for v in range(1, st.TABLE_ENTRIES + 1):
        if v in (1, 2, 67, st.TABLE_ENTRIES):
            zinv = pow(q[2], ref.P - 2, ref.P)
            x, y = q[0] * zinv % ref.P, q[1] * zinv % ref.P
            want = ((y + x) % ref.P, (y - x) % ref.P,
                    2 * ref.D * x * y % ref.P)
            for j in range(3):
                got = sum(int(table[v - 1, j, k]) << (13 * k)
                          for k in range(20))
                assert got == want[j], (v, j)
        q = ref.point_add(q, neg)


def test_build_table_rejects_uncacheable_pubkeys():
    _seed, pk = _keypair()
    assert st.build_signer_table(pk[:31]) is None
    assert st.build_signer_table(pk + b"\x00") is None
    assert st.build_signer_table(b"") is None
    # first y with no sqrt — the undecompressable family from the edge
    # corpus; such a signer must never be cached (it never dispatches
    # hot, so the hot kernel's "no decompress stage" stays sound)
    y = 2
    while ref.point_decompress(int(y).to_bytes(32, "little")) is not None:
        y += 1
    assert st.build_signer_table(int(y).to_bytes(32, "little")) is None


# --------------- B: cache semantics (LRU, budget, knobs) ---------------


def _fake_table():
    return np.zeros((st.TABLE_ENTRIES, 3, 20), dtype=np.int16)


def _pk(i):
    return bytes([i]) * 32


def test_lru_recency_and_byte_budget():
    cache = st.SignerTableCache(max_bytes=3 * st.TABLE_BYTES)
    for i in range(3):
        assert cache.install(_pk(i), _fake_table())
    assert cache.lookup(_pk(0)) is not None  # refresh: 0 is now MRU
    cache.install(_pk(3), _fake_table())     # over budget: evict LRU
    snap = cache.snapshot()
    assert snap["entries"] == 3 and snap["evictions"] == 1
    assert snap["bytes"] == 3 * st.TABLE_BYTES
    assert cache.lookup(_pk(1)) is None      # 1 was oldest, not 0
    assert cache.lookup(_pk(0)) is not None
    assert cache.lookup(_pk(3)) is not None


def test_configure_shrink_evicts_and_disable_clears():
    cache = st.SignerTableCache(max_bytes=3 * st.TABLE_BYTES)
    for i in range(3):
        cache.install(_pk(i), _fake_table())
    cache.configure(max_bytes=st.TABLE_BYTES)  # shrink: immediate evict
    snap = cache.snapshot()
    assert snap["entries"] == 1 and snap["evictions"] == 2
    assert cache.lookup(_pk(2)) is not None    # the MRU survives
    cache.configure(enabled=False)             # disable: clears outright
    assert cache.snapshot()["entries"] == 0
    assert cache.lookup(_pk(2)) is None
    assert not cache.install(_pk(4), _fake_table())
    cache.configure(enabled=True)
    assert cache.install(_pk(4), _fake_table())
    assert cache.lookup(_pk(4)) is not None


def test_budget_below_one_table_rejects_install():
    cache = st.SignerTableCache(max_bytes=st.TABLE_BYTES - 1)
    assert not cache.install(_pk(0), _fake_table())
    assert cache.snapshot()["entries"] == 0


def test_audit_evict_drops_exactly_one_signer():
    cache = st.SignerTableCache(max_bytes=4 * st.TABLE_BYTES)
    cache.install(_pk(0), _fake_table())
    cache.install(_pk(1), _fake_table())
    assert cache.evict(_pk(0)) is True
    assert cache.evict(_pk(0)) is False        # already gone
    snap = cache.snapshot()
    assert snap["audit_evictions"] == 1 and snap["entries"] == 1
    assert cache.lookup(_pk(0)) is None
    assert cache.lookup(_pk(1)) is not None


def test_evicted_unused_counts_tables_that_never_served():
    """A (hit) and B (never looked up) fill a 2-table budget; C evicts
    B, the LRU, which counts as evicted unused. An audit eviction of a
    never-served table does not."""
    from stellar_tpu.utils.metrics import registry
    meter = registry.meter("crypto.verify.signer_table.evicted_unused")
    before = meter.count
    cache = st.SignerTableCache(max_bytes=2 * st.TABLE_BYTES)
    a, b, c = _fake_table(), _fake_table(), _fake_table()
    cache.install(_pk(0), a)
    cache.install(_pk(1), b)
    assert cache.lookup(_pk(0)) is a           # the table, not its entry
    cache.install(_pk(2), c)
    snap = cache.snapshot()
    assert snap["evictions"] == 1 and snap["evicted_unused"] == 1
    assert cache.lookup(_pk(1)) is None        # B went, not A
    assert meter.count - before == 1
    assert cache.evict(_pk(2))                 # C never served either
    snap = cache.snapshot()
    assert snap["audit_evictions"] == 1 and snap["evicted_unused"] == 1
    cache.install(_pk(3), _fake_table())
    cache.install(_pk(4), _fake_table())       # evicts A, which served
    snap = cache.snapshot()
    assert snap["evictions"] == 2 and snap["evicted_unused"] == 1
    assert meter.count - before == 1


def _fill(cache, n):
    for i in range(n):
        assert cache.admit(_pk(i))              # room: first sight builds
        assert cache.install(_pk(i), _fake_table())


def test_admit_builds_first_sight_while_the_budget_has_room():
    cache = st.SignerTableCache(max_bytes=3 * st.TABLE_BYTES)
    _fill(cache, 3)
    snap = cache.snapshot()
    assert snap["entries"] == 3 and snap["installs"] == 3
    assert snap["deferred"] == 0 and snap["seen_once"] == 0
    assert snap["evictions"] == 0


def test_full_cache_defers_first_sight_admits_second_then_hits():
    from stellar_tpu.utils.metrics import registry
    meter = registry.meter("crypto.verify.signer_table.deferred")
    before = meter.count
    cache = st.SignerTableCache(max_bytes=2 * st.TABLE_BYTES)
    _fill(cache, 2)
    assert cache.lookup(_pk(9)) is None
    assert not cache.admit(_pk(9))             # first sight: nothing built
    snap = cache.snapshot()
    assert snap["deferred"] == 1 and snap["seen_once"] == 1
    assert snap["entries"] == 2 and snap["evictions"] == 0
    assert meter.count - before == 1
    assert cache.lookup(_pk(9)) is None
    assert cache.admit(_pk(9))                 # second sight: build
    assert cache.snapshot()["seen_once"] == 0
    cache.install(_pk(9), _fake_table())
    assert cache.lookup(_pk(9)) is not None    # third sight: hot
    snap = cache.snapshot()
    assert snap["deferred"] == 1 and snap["evictions"] == 1
    assert snap["entries"] == 2 and cache.lookup(_pk(0)) is None
    assert meter.count - before == 1


def test_seen_once_set_is_bounded_at_the_table_count_oldest_first():
    cache = st.SignerTableCache(max_bytes=2 * st.TABLE_BYTES)
    _fill(cache, 2)
    for i in (10, 11, 12):
        assert not cache.admit(_pk(i))
    snap = cache.snapshot()
    assert snap["seen_once"] == 2 and snap["deferred"] == 3
    assert not cache.admit(_pk(10))            # 10, the oldest, was dropped
    assert cache.admit(_pk(12))                # 12 is still recorded
    assert not cache.admit(_pk(11))            # 10's re-entry dropped 11
    snap = cache.snapshot()
    assert snap["seen_once"] == 2 and snap["deferred"] == 5


def test_configure_shrink_trims_seen_once_and_disable_clears_it():
    cache = st.SignerTableCache(max_bytes=4 * st.TABLE_BYTES)
    _fill(cache, 4)
    for i in (10, 11, 12, 13):
        assert not cache.admit(_pk(i))
    assert cache.snapshot()["seen_once"] == 4
    cache.configure(max_bytes=2 * st.TABLE_BYTES)
    snap = cache.snapshot()
    assert snap["entries"] == 2 and snap["seen_once"] == 2
    assert cache.admit(_pk(13))                # the newest two survive
    assert not cache.admit(_pk(11))            # the oldest went
    cache.configure(enabled=False)
    snap = cache.snapshot()
    assert snap["entries"] == 0 and snap["seen_once"] == 0
    deferred = snap["deferred"]
    assert not cache.admit(_pk(12))            # nothing can be installed
    assert cache.snapshot()["deferred"] == deferred
    cache.configure(enabled=True)
    assert cache.admit(_pk(12))                # empty again: room
    assert not st.SignerTableCache(
        max_bytes=st.TABLE_BYTES - 1).admit(_pk(0))


def test_two_caches_fed_the_same_keys_end_identical():
    """Admission is content-keyed and ordered by the call sequence
    alone: two replicas partition the same traffic identically."""
    rng = np.random.default_rng(0xAD17)
    seq = [int(x) for x in rng.zipf(1.3, size=600) % 200]
    caches = [st.SignerTableCache(max_bytes=8 * st.TABLE_BYTES)
              for _ in range(2)]
    table = _fake_table()
    for cache in caches:
        for k in seq:
            if cache.lookup(_pk(k)) is None and cache.admit(_pk(k)):
                cache.install(_pk(k), table)
    a, b = caches
    assert list(a._entries) == list(b._entries)
    assert list(a._seen_once) == list(b._seen_once)
    assert a.snapshot() == b.snapshot()
    snap = a.snapshot()
    assert snap["deferred"] > 0 and snap["evictions"] > 0
    assert snap["hits"] > 0 and snap["seen_once"] > 0


# --------------- C: byte-aligned signed radix-256 recode ---------------


def test_signed_digits256_exact_for_every_scalar():
    """sum(d_i * 256^i) == s exactly — including non-canonical scalars
    the gates would veto (the recode itself is total); digits below the
    top stay signed bytes, and the top digit of every gate-passable
    scalar (s < L) stays within the 128-entry table range."""
    from stellar_tpu.ops import verify as vk
    scalars = [0, 1, 255, 256, ref.L - 1, ref.L, 2**252, 2**255 - 20,
               2**256 - 1]
    scalars += [int.from_bytes(RNG.bytes(32), "little") for _ in range(7)]
    b = np.stack([np.frombuffer(int(s).to_bytes(32, "little"),
                                dtype=np.uint8) for s in scalars])
    d = np.asarray(vk.signed_digits256_dev(b))
    assert d.shape == (32, len(scalars))
    for i, s in enumerate(scalars):
        got = sum(int(d[w, i]) * 256 ** (31 - w) for w in range(32))
        assert got == s, s
        assert all(-128 <= int(d[w, i]) <= 127 for w in range(1, 32)), s
        if s < ref.L:
            assert 0 <= int(d[0, i]) <= 32, s


# --------------- D: the partitioning submit (host-only) ---------------


def _hot_pool():
    seed, pk = _keypair()
    good = (pk, b"hot partition", ref.sign(seed, b"hot partition"))
    bad = (pk, good[1] + b"!", good[2])
    return pk, good, bad


def test_first_sight_cold_then_repeats_hot(fresh_dispatch):
    """One signer, four rows: the first occurrence installs the table
    and rides cold; rows 2-4 hit the cache IN THE SAME BATCH and ride
    hot. The merged verdict vector keeps original row order (the bad
    row is hot-served and must come back False in place)."""
    bv._enter_host_only("test: partition without kernels")
    v = BatchVerifier(bucket_sizes=(16,))
    pk, good, bad = _hot_pool()
    got = v.verify_batch([good, good, bad, good])
    assert list(got) == [True, True, False, True]
    snap = bv.dispatch_health()["signer_tables"]
    assert snap["installs"] == 1 and snap["entries"] == 1
    assert snap["hits"] == 3 and snap["misses"] == 1
    got2 = v.verify_batch([bad, good])         # all-hot steady state
    assert list(got2) == [False, True]
    snap2 = bv.dispatch_health()["signer_tables"]
    assert snap2["hits"] == 5 and snap2["installs"] == 1


def test_disabled_cache_rides_everything_cold(fresh_dispatch):
    bv._enter_host_only("test: partition without kernels")
    st.signer_table_cache.configure(enabled=False)
    v = BatchVerifier(bucket_sizes=(16,))
    _pk_, good, bad = _hot_pool()
    assert list(v.verify_batch([good, bad, good])) == [True, False, True]
    snap = bv.dispatch_health()["signer_tables"]
    assert snap["entries"] == 0 and snap["installs"] == 0
    assert snap["hits"] == 0 and snap["misses"] == 0


def test_uncacheable_rows_always_ride_cold(fresh_dispatch):
    """Bad-length and undecompressable pubkeys must neither crash the
    partition nor pollute the cache — and a cached signer alongside
    them still serves hot with verdicts merged in order."""
    bv._enter_host_only("test: partition without kernels")
    v = BatchVerifier(bucket_sizes=(16,))
    pk, good, _bad = _hot_pool()
    y = 2
    while ref.point_decompress(int(y).to_bytes(32, "little")) is not None:
        y += 1
    undec = (int(y).to_bytes(32, "little"), b"m", bytes(64))
    rows = [good, (pk[:31], b"m", bytes(64)), undec, good]
    assert list(v.verify_batch(rows)) == [True, False, False, True]
    snap = bv.dispatch_health()["signer_tables"]
    assert snap["entries"] == 1 and snap["installs"] == 1
    assert snap["hits"] == 1                   # only the repeat of pk


def test_failed_build_under_a_full_cache_installs_nothing(fresh_dispatch):
    """An undecompressable key is admitted on its second sight under a
    full budget, its build fails, and the resident table stays: the
    cache neither grows nor evicts."""
    bv._enter_host_only("test: partition without kernels")
    st.signer_table_cache.configure(max_bytes=st.TABLE_BYTES)
    v = BatchVerifier(bucket_sizes=(16,))
    pk, good, _bad = _hot_pool()
    y = 2
    while ref.point_decompress(int(y).to_bytes(32, "little")) is not None:
        y += 1
    undec = (int(y).to_bytes(32, "little"), b"m", bytes(64))
    rows = [good, undec, undec, good]
    assert list(v.verify_batch(rows)) == [True, False, False, True]
    snap = bv.dispatch_health()["signer_tables"]
    assert snap["installs"] == 1 and snap["entries"] == 1
    assert snap["deferred"] == 1 and snap["seen_once"] == 0
    assert snap["evictions"] == 0 and snap["hits"] == 1
    assert st.signer_table_cache.lookup(pk) is not None


def test_audit_conviction_evicts_served_tables(fresh_dispatch):
    """Unit twin of the chaos-mesh scenario: the hot workload's
    conviction hook must evict exactly the signers whose tables served
    the convicted part (end-to-end coverage lives in
    tests/test_chaos_device_domains.py)."""
    bv._enter_host_only("test: partition without kernels")
    v = BatchVerifier(bucket_sizes=(16,))
    pk, good, _bad = _hot_pool()
    v.verify_batch([good, good])
    table = st.signer_table_cache.lookup(pk)
    assert table is not None
    v._hot.on_audit_conviction([(good, table)])
    snap = bv.dispatch_health()["signer_tables"]
    assert snap["audit_evictions"] == 1 and snap["entries"] == 0
    # next sight rebuilds from the pubkey bytes
    v.verify_batch([good, good])
    assert bv.dispatch_health()["signer_tables"]["installs"] == 2


# --------------- E: hot-kernel differential vs the oracle ---------------


@pytest.mark.parametrize("bucket", [4, 16])
def test_hot_differential_every_bucket_size(bucket, fresh_dispatch):
    """The edge corpus reuses ONE control pubkey across most rows, so
    after the first sight the tampered/non-canonical-s/zero-sig rows
    ride the HOT kernel — exactly the adversarial coverage the cold
    differential pins, now against verify_kernel_hot. Two passes: the
    first populates the cache, the second is the hot steady state; both
    must be bit-identical to the oracle AND to each other."""
    v = BatchVerifier(bucket_sizes=(bucket,))
    items = edge_corpus() + make_valid(3)
    got1 = check(v, items)
    snap1 = bv.dispatch_health()["signer_tables"]
    assert snap1["installs"] > 0 and snap1["hits"] > 0
    got2 = check(v, items)                     # repeat: near-all hot
    snap2 = bv.dispatch_health()["signer_tables"]
    assert snap2["hits"] > snap1["hits"]
    assert (got1 == got2).all()
    assert got1[0] and got1[-3:].all()
    # anti-vacuity: rows were KERNEL-served (no silent host fallback),
    # and the hot variant's jit shapes stayed inside the pinned buckets
    assert v.served["host-fallback"] == 0 and v.served["device"] > 0
    hot_shapes = sorted(n for kerns in v._kernels_variants.values()
                        for n in kerns)
    assert hot_shapes and set(hot_shapes) <= {bucket}


def test_hot_and_cold_paths_agree_bit_for_bit(fresh_dispatch):
    """The same workload with the cache disabled (all-cold) and enabled
    (hot steady state) must produce identical verdict vectors — the
    partition is an execution detail, never policy."""
    items = edge_corpus()[:20] + make_valid(3)
    st.signer_table_cache.configure(enabled=False)
    cold = BatchVerifier(bucket_sizes=(16,)).verify_batch(items)
    st.signer_table_cache.configure(enabled=True)
    v = BatchVerifier(bucket_sizes=(16,))
    v.verify_batch(items)                      # populate
    hot = v.verify_batch(items)                # serve hot
    assert (cold == hot).all()
    assert bv.dispatch_health()["signer_tables"]["hits"] > 0


@pytest.mark.slow
def test_hot_differential_10k_repeat_signers(fresh_dispatch):
    """ISSUE 16 acceptance: >= 10k vectors over a small repeat-signer
    set (the consensus traffic shape), chunked through a 2048-bucket
    verifier — most rows ride the hot kernel and every decision is
    bit-identical to the oracle."""
    n = 10_240
    keys = [_keypair() for _ in range(32)]
    items = []
    for i in range(n):
        seed, pk = keys[i % len(keys)]
        msg = RNG.bytes(1 + (i % 96))
        sig = ref.sign(seed, msg)
        if i % 3 == 0:
            b = bytearray(sig)
            b[int(RNG.integers(0, 64))] ^= 1 << int(RNG.integers(0, 8))
            sig = bytes(b)
        items.append((pk, msg, sig))
    v = BatchVerifier(bucket_sizes=(2048,))
    got = check(v, items)
    assert got.any() and not got.all()
    snap = bv.dispatch_health()["signer_tables"]
    assert snap["installs"] == len(keys)
    assert snap["hits"] >= n - 2 * len(keys)   # all but first sights
    assert v.served["host-fallback"] == 0
