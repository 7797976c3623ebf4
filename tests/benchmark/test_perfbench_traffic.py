"""The benchmark's traffic generator and plain reference (CPU, tiny
sizes): seeds, invalid classes, signer laws, arrivals, uniqueness,
SCP payloads, and the reference against libsodium's rules."""

import collections
import json
from pathlib import Path

import pytest

from benchmark import reference as ref
from benchmark import traffic as tr

ROOT = Path(__file__).resolve().parents[2]
SEED = 2 ** 40 + 17  # the driver's seeds exceed 32 bits


def _files(config, mix):
    cfg = json.loads((ROOT / "benchmark/configs" / f"{config}.json")
                     .read_text())
    m = json.loads((ROOT / "benchmark/traffic" / f"{mix}.json").read_text())
    return cfg, m


def _txset(units=60, invalid=True, **signers):
    cfg, mix = _files("validator-txset-1000", "txset-multisig")
    mix = dict(mix, call_size={"law": "fixed", "units": units})
    if signers:
        mix["signers"] = signers
    if not invalid:
        mix.pop("invalid")
    return cfg, mix


def test_same_seed_same_rows_other_seed_other_rows():
    cfg, mix = _txset()
    a, b = tr.Traffic(cfg, mix, SEED), tr.Traffic(cfg, mix, SEED)
    assert a.warmup() == b.warmup()
    assert [a.call(i) for i in range(3)] == [b.call(i) for i in range(3)]
    c = tr.Traffic(cfg, mix, SEED + 1)
    assert c.call(0) != tr.Traffic(cfg, mix, SEED).call(0)


def test_calls_are_made_in_order():
    cfg, mix = _txset()
    t = tr.Traffic(cfg, mix, SEED)
    with pytest.raises(ValueError):
        t.call(1)


def test_invalid_classes_land_as_stated():
    cfg, mix = _txset(units=60)
    clean = tr.Traffic(*_txset(units=60, invalid=False), SEED).call(0)
    rows = tr.Traffic(cfg, mix, SEED).call(0)
    diff = [i for i, (a, b) in enumerate(zip(clean, rows)) if a != b]
    per_class = mix["invalid"]["per_call"]
    assert len(diff) == sum(per_class.values()) == 32
    seen = collections.Counter()
    for i in diff:
        (pk0, m0, s0), (pk, m, s) = clean[i], rows[i]
        s_int = int.from_bytes(s[32:], "little")
        pk_int = int.from_bytes(pk, "little")
        if pk != pk0:
            # p and p + 1 are both non-canonical and of small order
            if pk in tr.NONCANON_A or pk in tr.SMALL_ORDER:
                assert pk_int & ((1 << 255) - 1) >= ref.P or \
                    ref.has_small_order(pk)
                seen["noncanonical_or_small_order_a"] += 1
            else:
                assert pk_int < ref.P and ref.decompress(pk) is None
                seen["a_off_curve"] += 1
        elif m != m0:
            seen["wrong_message"] += 1
        elif s[:32] != s0[:32]:
            if s[:32] == tr.R_OFF_CURVE:
                seen["r_off_curve"] += 1
            else:
                assert ref.has_small_order(s[:32])
                seen["r_small_order"] += 1
        elif s_int >= ref.L:
            assert s_int - ref.L == int.from_bytes(s0[32:], "little")
            seen["noncanonical_s"] += 1
        else:
            seen["tampered_sig"] += 1
        assert not ref.verify(pk, m, s)
    want = dict(per_class)
    want["noncanonical_or_small_order_a"] = \
        want.pop("noncanonical_a") + want.pop("small_order_a")
    assert dict(seen) == want
    assert all(ref.verify(*r) for i, r in enumerate(rows) if i not in diff)


def test_every_k_rows_one_invalid_row_classes_cycle():
    cfg, mix = _files("scp-storm-16", "scp-envelopes")
    t = tr.Traffic(cfg, mix, SEED)
    rows = [r for i in range(450) for r in t.call(i)]
    bad = [i for i, r in enumerate(rows) if not ref.verify(*r)]
    full_blocks = len(rows) // 100
    assert [i // 100 for i in bad][:full_blocks] == list(range(full_blocks))
    # classes alternate: even blocks a flipped S bit, odd blocks S + L
    for i in bad:
        s = int.from_bytes(rows[i][2][32:], "little")
        assert (s >= ref.L) == bool((i // 100) % 2)


def test_round_robin_takes_accounts_in_turn():
    cfg, mix = _txset(units=200, invalid=False, law="round_robin",
                      accounts=64)
    t = tr.Traffic(cfg, mix, SEED)
    rows = t.call(0)
    for u in range(200):
        acct = u % 64
        assert rows[2 * u][0] == t.signer(2 * acct).pk  # master
        assert rows[2 * u + 1][0] == t.signer(2 * acct + 1).pk  # cosigner
        assert rows[2 * u][1] == rows[2 * u + 1][1]  # one tx, one hash


def test_distinct_law_one_tx_per_account_in_a_new_order():
    cfg, mix = _txset(units=50, invalid=False, law="distinct", accounts=50)
    t = tr.Traffic(cfg, mix, SEED)
    orders = []
    for i in range(3):
        rows = t.call(i)
        masters = [rows[2 * u][0] for u in range(50)]
        assert len(set(masters)) == 50  # every account once
        assert {r[0] for r in rows} == {t.signer(k).pk for k in range(100)}
        orders.append(masters)
    assert orders[0] != orders[1] != orders[2]
    with pytest.raises(ValueError):
        tr.Traffic(*_txset(units=51, law="distinct", accounts=50),
                   SEED).call(0)


def test_the_north_star_mix_signs_with_a_ledgers_worth_of_accounts():
    cfg, mix = _files("validator-txset-1000", "txset-multisig")
    assert mix["signers"] == {"law": "distinct", "accounts": 1000}
    assert cfg["call_size"]["units"] == 1000  # every account once a set


def test_bursts_arrive_at_fixed_instants_for_every_seed():
    cfg, mix = _files("scp-storm-16", "scp-envelopes")
    a, b = tr.Traffic(cfg, mix, SEED), tr.Traffic(cfg, mix, SEED + 5)
    k, every = mix["arrivals"]["size"], mix["arrivals"]["every_s"]
    assert k == cfg["validators"]
    assert every * (cfg["ballot_rounds"] + 3) == cfg["slot_seconds"]
    for i in range(3 * k):
        assert a.arrival_s(i) == b.arrival_s(i) == (i // k) * every
    assert list(a.group(0)) == list(range(k))
    assert list(a.group(k + 3)) == list(range(k + 3, 2 * k))
    # one burst is one step of a slot from every validator
    rows = [a.call(i)[0] for i in range(k)]
    assert {r[0] for r in rows} == {a.signer(v).pk for v in range(k)}


def test_a_closed_loop_has_no_arrivals():
    t = tr.Traffic(*_txset(), SEED)
    assert t.arrival_s(7) is None and list(t.group(7)) == [7]


def test_zipf_draws_follow_the_law():
    n, s = 1000, 1.0
    cfg, mix = _txset(units=4000, invalid=False, law="zipf", s=s,
                      accounts=n)
    t = tr.Traffic(cfg, mix, SEED)
    draws = t._accounts(tr._rng(SEED, 9), 0, 200_000)
    counts = collections.Counter(int(a) for a in draws)
    h = sum(k ** -s for k in range(1, n + 1))
    for rank in (1, 2, 10):
        want = rank ** -s / h
        assert abs(counts[rank - 1] / len(draws) - want) < 0.1 * want
    # keys are derived on demand: one call instantiates only what it drew
    rows = t.call(0)
    drawn = {r[0] for r in rows}
    assert len(t._signers) == len(drawn) < 2 * n


def test_zipf_mix_is_a_data_file_away():
    """txset-zipf (Open questions) needs only a traffic file: the
    generator takes the law and warms the top of the population."""
    cfg, _ = _files("validator-txset-1000", "txset-multisig")
    mix = {"signers": {"law": "zipf", "s": 1.0, "accounts": 1_000_000},
           "invalid": {"per_call": {c: 4 for c in tr.CLASSES}},
           "call_size": {"law": "fixed", "units": 50},
           "warmup": {"signers": 64, "calls": 1}}
    t = tr.Traffic(cfg, mix, SEED)
    warm = t.warmup()
    assert [len(c) for c in warm] == [64, 100]
    assert len(t.call(0)) == 100


def test_only_the_fixed_size_law_is_known():
    cfg, mix = _txset()
    with pytest.raises(ValueError):
        tr.Traffic(cfg, dict(mix, call_size={"law": "uniform"}), SEED)
    with pytest.raises(ValueError):
        tr.Traffic(cfg, dict(mix, arrivals={"law": "poisson"}), SEED)


@pytest.mark.parametrize("cell", [("validator-txset-1000", "txset-multisig"),
                                  ("scp-storm-16", "scp-envelopes")])
def test_no_row_repeats_within_a_run(cell):
    cfg, mix = _files(*cell)
    mix = dict(mix)
    if cfg["unit"] == "tx":
        mix["call_size"] = {"law": "fixed", "units": 100}
    t = tr.Traffic(cfg, mix, SEED)
    rows = [r for c in t.warmup() for r in c]
    rows += [r for i in range(40) for r in t.call(i)]
    assert len(set(rows)) == len(rows)
    # nor does the R column of a call repeat (the resident operand
    # cache is keyed by the bytes of whole operands)
    r_cols = [b"".join(r[2][:32] for r in t.call(40 + i))
              for i in range(5)]
    assert len(set(r_cols)) == 5
    if mix["nonces_per_key"] == 0:
        # fresh nonces: a row alone in a device call brings a new R
        assert len({r[2][:32] for r in rows}) == len(rows)


def test_scp_payload_matches_the_herders():
    from stellar_tpu.herder.herder import scp_envelope_sign_payload
    from stellar_tpu.xdr.runtime import from_bytes
    from stellar_tpu.xdr.scp import SCPStatement
    cfg, mix = _files("scp-storm-16", "scp-envelopes")
    t = tr.Traffic(cfg, mix, SEED)
    rows = [r for i in range(128) for r in t.call(i)]
    assert len(rows) >= 128  # a whole slot: 16 validators x 8 steps
    steps = set()
    for pk, msg, _s in rows[:128]:
        net, stmt_bytes = msg[:32], msg[36:]
        stmt = from_bytes(SCPStatement, stmt_bytes)
        assert stmt.nodeID.value == pk
        assert scp_envelope_sign_payload(net, stmt) == msg
        steps.add(int(stmt.pledges.arm))
    assert steps == {0, 1, 2, 3}


def test_reference_blocklist_is_libsodiums():
    from stellar_tpu.crypto import ed25519_ref
    want = {int.from_bytes(e, "little") & ((1 << 255) - 1)
            for e in ed25519_ref.SMALL_ORDER_ENCODINGS}
    assert ref.SMALL_ORDER_Y == want


def test_reference_agrees_with_libsodium_rules_on_every_class():
    """Cross-check (test only): the benchmark's reference, its pure
    Python equation and the program's host oracle on rows of every
    class."""
    from stellar_tpu.crypto import ed25519_ref
    cfg, mix = _txset(units=40)
    rows = tr.Traffic(cfg, mix, SEED).call(0)
    for r in rows:
        want = ed25519_ref.verify(*r)
        assert ref.verify(*r) == want
        if ref.policy_gate(r[0], r[2]):
            assert ref.equation_py(*r) == want


def test_control_breaks_the_gate_only():
    cfg, mix = _txset(units=40)
    rows = tr.Traffic(cfg, mix, SEED).call(0)
    differ = [r for r in rows if ref.verify(*r) != ref.verify_no_gate(*r)]
    # exactly the S + L rows: the equation holds, libsodium refuses S
    assert len(differ) == mix["invalid"]["per_call"]["noncanonical_s"]
    assert all(int.from_bytes(r[2][32:], "little") >= ref.L for r in differ)


def test_reference_pool_matches_inline():
    cfg, mix = _txset(units=30)
    rows = tr.Traffic(cfg, mix, SEED).call(0)
    inline, gate = ref.verdicts(rows, 0)
    pooled, gate2 = ref.verdicts(rows, 2, block=16)
    assert inline == pooled and gate == gate2
