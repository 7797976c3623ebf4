"""Gate for the static-analysis suite (ISSUE 3).

Four layers:

* **interval-domain unit checks** — the abstract interpreter's transfer
  functions on tiny traced jaxprs, including the one-hot exclusivity
  refinement the window selects depend on;
* **the proof itself** — the verify-kernel overflow proof must hold and
  its envelope must match the committed golden ``docs/limb_bounds.json``
  (the golden was written at batch 128; proving at batch 2 here also
  pins batch-invariance of the envelope);
* **mutation tests** — a prover that can't catch a seeded bug is
  vacuous: dropping one carry round from the field multiply must
  produce violations (both on a synthetic chain and through the REAL
  traced dsm stage), and an unlocked mutation in a lock-owning test
  double must trip the lock lint;
* **clean-tree lints** — hotpath/locks/nondet must be clean modulo the
  reviewed allowlists, and allowlists must carry written reasons.

The full bucket sweep (every jit bucket size) runs in tier-1 via
``tools/tier1.sh`` -> ``tools/analyze.py``; see docs/static_analysis.md.
"""

import textwrap

import numpy as np
import pytest

from stellar_tpu.analysis import (
    coverage, hotpath, lockorder, locks, nondet, overflow,
)
from stellar_tpu.analysis.intervals import (
    AbsVal, IntervalInterpreter, Unsupported,
)
from stellar_tpu.analysis.lint_base import (
    Allowlist, finish_report, repo_root,
)


# ---------------- interval-domain units ----------------


def _analyze(fn, *avals, in_ranges):
    import jax
    jx = jax.make_jaxpr(fn)(*avals)
    interp = IntervalInterpreter()
    invals = [AbsVal.from_range(a, lo, hi)
              for a, (lo, hi) in zip(avals, in_ranges)]
    outs = interp.eval_closed(jx, invals, path="unit")
    return interp, outs


def _i32(*shape):
    import jax
    return jax.ShapeDtypeStruct(shape, np.int32)


def test_interval_mul_add_exact():
    interp, (out,) = _analyze(
        lambda a, b: a * b + a, _i32(4), _i32(4),
        in_ranges=[(2, 10), (-3, 5)])
    assert int(out.lo.min()) == 10 * -3 + 2  # mul corner -30, plus a.lo
    assert int(out.hi.max()) == 10 * 5 + 10
    assert not interp.violations


def test_interval_flags_int32_overflow():
    interp, _ = _analyze(
        lambda a, b: a * b, _i32(4), _i32(4),
        in_ranges=[(0, 1 << 20), (0, 1 << 20)])
    assert len(interp.violations) == 1
    v = interp.violations[0]
    assert v.primitive == "mul" and v.dtype == "int32"
    assert v.hi == 1 << 40


def test_interval_carry_step_bound():
    """The field layer's parallel carry round maps loose limbs back
    under MASK + fold headroom — the analyzer must see that."""
    from stellar_tpu.ops import field25519 as fe
    interp, (out,) = _analyze(
        fe._carry_step, _i32(fe.NLIMBS, 3),
        in_ranges=[(0, 20 * fe.LOOSE_MAX ** 2 // 1000)])
    assert not interp.violations
    assert int(out.hi.max()) < 1 << 22


def test_onehot_select_union_bound():
    """The one-hot contraction idiom must get the union bound, not the
    8x sum — the precision the window selects live on."""
    import jax.numpy as jnp

    def select(table, digit):
        idx = jnp.arange(1, 9, dtype=jnp.int32).reshape(8, 1)
        onehot = (idx == digit[None]).astype(jnp.int32)
        return (table * onehot).sum(axis=0)

    interp, (out,) = _analyze(
        select, _i32(8, 5), _i32(5), in_ranges=[(0, 9000), (-8, 8)])
    assert not interp.violations
    assert int(out.hi.max()) == 9000  # union, not 8 * 9000
    assert int(out.lo.min()) == 0


def test_onehot_refinement_sound_against_varying_operand():
    """Soundness regression: eq(iota(8), traced (8,) y) is NOT one-hot
    — y varies per position (it could equal iota everywhere), so the
    sum must be the full 8-fold sum, never the union bound. Uniform
    BOUNDS (stored-size-1) must not be mistaken for uniform VALUES."""
    import jax.numpy as jnp

    def select(table, y):
        idx = jnp.arange(8, dtype=jnp.int32)
        onehot = (idx == y).astype(jnp.int32)  # y varies along axis 0
        return (table[:, None] * onehot[:, None]).sum(axis=0)

    interp, (out,) = _analyze(
        select, _i32(8), _i32(8), in_ranges=[(0, 9000), (0, 7)])
    assert int(out.hi.max()) == 8 * 9000  # all positions can match


def _u32(*shape):
    import jax
    return jax.ShapeDtypeStruct(shape, np.uint32)


def test_or_xor_bitmask_refinement():
    """ISSUE 7: OR/XOR of non-negative operands never set a bit above
    either operand's highest bit — the refinement that keeps the
    SHA-256 schedule/round mixing inside uint32 (the sum bound alone
    would falsely escape on full-range words). The bound must COVER
    the true range (soundness) and be the bit ceiling (precision)."""
    import jax.numpy as jnp
    interp, (o1, o2) = _analyze(
        lambda a, b: (a | b, a ^ b), _u32(4), _u32(4),
        in_ranges=[(0, 5), (0, 9)])
    assert not interp.violations
    # min(sum bound 5+9, bit ceiling of max(5,9)=9 -> 15) = 14, which
    # covers the true max (5|8 = 5^8 = 13)
    assert int(o1.hi.max()) == 14 and int(o2.hi.max()) == 14
    assert int(o1.lo.min()) == 0 and int(o2.lo.min()) == 0
    # full-range uint32 stays uint32 — no violation, no escape
    interp2, (p1, p2) = _analyze(
        lambda a, b: (a | b, a ^ b), _u32(4), _u32(4),
        in_ranges=[(0, 0xFFFFFFFF), (0, 0xFFFFFFFF)])
    assert not interp2.violations
    assert int(p1.hi.max()) == 0xFFFFFFFF
    assert int(p2.hi.max()) == 0xFFFFFFFF
    # signed operands that may be negative fall back to the wide bound
    interp3, (n1,) = _analyze(
        lambda a, b: a ^ b, _i32(4), _i32(4),
        in_ranges=[(-1, 5), (0, 9)])
    assert int(n1.lo.min()) < 0


def test_unsigned_not_transfer():
    """Unsigned bitwise-not is dtype_max - x, not -1 - x (the signed
    form would claim a negative range for a uint32 value)."""
    import jax.numpy as jnp
    interp, (out,) = _analyze(
        lambda a: ~a, _u32(4), in_ranges=[(0, 10)])
    assert not interp.violations
    assert int(out.lo.min()) == 0xFFFFFFFF - 10
    assert int(out.hi.max()) == 0xFFFFFFFF


def test_scan_unroll_exact_counter():
    """fori_loop lowers to scan; the loop counter and carries must stay
    exact under unrolling (no widening overshoot)."""
    from jax import lax

    def f(x):
        return lax.fori_loop(0, 10, lambda i, c: c + i, x)

    interp, (out,) = _analyze(f, _i32(), in_ranges=[(0, 5)])
    assert not interp.violations
    assert int(out.hi.max()) == 5 + sum(range(10))
    assert int(out.lo.min()) == 0 + sum(range(10))


def test_tile_repeats_bounds():
    """jnp.tile lowers to its own primitive on JAX 0.9 (the SHA-256
    kernel uses it): bounds repeat with the values."""
    import jax.numpy as jnp
    interp, (out,) = _analyze(lambda a: jnp.tile(a, (2, 3)) * 2,
                              _i32(2, 4), in_ranges=[(1, 5)])
    assert out.shape == (4, 12)
    assert int(out.lo.min()) == 2 and int(out.hi.max()) == 10


def test_unsupported_primitive_is_loud():
    import jax.numpy as jnp
    import jax
    jx = jax.make_jaxpr(lambda a: jnp.sin(a.astype(jnp.float32)))(
        _i32(3))
    interp = IntervalInterpreter()
    with pytest.raises(Unsupported):
        interp.eval_closed(jx, [AbsVal.from_range(_i32(3), 0, 1)],
                           path="unit")


# ---------------- the proof + golden ----------------


@pytest.fixture(scope="module")
def proof():
    return overflow.prove(batch=2)


def test_overflow_proof_holds(proof):
    assert proof["unsupported"] == []
    assert proof["violations"] == [], proof["violations"][:3]
    assert proof["contract_breaches"] == []
    assert proof["ok"]


def test_headroom_is_the_documented_claim(proof):
    """The binding constraint must be the documented one: the multiply
    accumulator's worst coefficient is exactly NLIMBS * LOOSE_MAX^2,
    proven under int32. If this moves, docs/kernel_design.md §1 moved."""
    from stellar_tpu.ops import field25519 as fe
    worst = proof["envelope"]["stages"]["dsm"]["max_abs"]
    assert worst == fe.NLIMBS * fe.LOOSE_MAX ** 2
    assert worst < 2 ** 31


def test_hot_stage_envelope_pinned(proof):
    """ISSUE 16: the hot-signer kernel is overflow-proven too, and its
    accumulator envelope is strictly TIGHTER than cold's — the cached
    table ships canonical limbs (<= MASK), not loose ones, so the
    worst multiply coefficient drops below the cold dsm's
    NLIMBS * LOOSE_MAX^2 headline."""
    hot = proof["envelope"]["stages"]["dsm_hot"]["max_abs"]
    cold = proof["envelope"]["stages"]["dsm"]["max_abs"]
    assert hot < cold
    assert hot < 2 ** 31
    assert proof["envelope"]["stages"]["kernel_hot_total"]["max_abs"] \
        < 2 ** 31


def test_envelope_matches_golden(proof):
    """The committed golden is the proof artifact kernel PRs diff.
    Golden was written at batch 128; this proof ran at batch 2 — a
    match also pins batch-invariance of the envelope."""
    golden = overflow.load_golden(str(repo_root()))
    assert golden is not None, (
        "docs/limb_bounds.json missing — run tools/analyze.py "
        "--write-golden and review/commit the envelope")
    diff = overflow.diff_golden(proof["envelope"], golden)
    assert not diff, "\n".join(
        ["proven envelope drifted from docs/limb_bounds.json — if the "
         "kernel change is intentional, re-run tools/analyze.py "
         "--write-golden and commit the diff:"] + diff)


def test_stage_outputs_honor_loose_contract(proof):
    from stellar_tpu.ops import field25519 as fe
    for stage, names in overflow.LOOSE_OUTPUTS.items():
        for name in names:
            for lo, hi in proof["envelope"]["stages"][stage][
                    "outputs"][name]:
                assert 0 <= lo and hi <= fe.LOOSE_MAX, (stage, name)


# ---------------- mutation tests (prover vacuity guards) ----------------


def _mul_dropped_carry(a, b):
    """fe.mul with the final carry round removed — the seeded overflow:
    limbs leave a single round around 2^23, so the NEXT multiply's
    accumulator blows through int32."""
    import jax.numpy as jnp
    from stellar_tpu.ops import field25519 as fe
    batch = a.shape[1:]
    pad_rest = ((0, 0),) * len(batch)
    acc = None
    for i in range(fe.NLIMBS):
        row = a[i][None] * b
        shifted = jnp.pad(row, ((i, fe.NLIMBS - 1 - i),) + pad_rest)
        acc = shifted if acc is None else acc + shifted
    lo = acc & fe.MASK
    hi = acc >> fe.BITS
    shifted = jnp.concatenate(
        [jnp.zeros((1,) + batch, jnp.int32), hi[:-1]], axis=0)
    c40_low = lo + shifted
    c39 = hi[-1:]
    high = jnp.concatenate([c40_low[fe.NLIMBS:], c39], axis=0)
    low = c40_low[:fe.NLIMBS] + fe.FOLD * high
    return fe._carry_step(low)  # ONE round; upstream does two


def test_mutant_dropped_carry_caught_synthetic():
    from stellar_tpu.ops import field25519 as fe
    interp, _ = _analyze(
        lambda a, b: _mul_dropped_carry(_mul_dropped_carry(a, b), b),
        _i32(fe.NLIMBS, 2), _i32(fe.NLIMBS, 2),
        in_ranges=[(0, fe.LOOSE_MAX), (0, fe.LOOSE_MAX)])
    assert interp.violations, "dropped carry must overflow the 2nd mul"


def test_mutant_dropped_carry_caught_in_real_dsm(monkeypatch):
    """The strong vacuity guard: seed the dropped carry into the REAL
    field layer and re-trace the REAL dsm stage — the prover must fail
    it. (PR 1 changed exactly these limb magnitudes; this is the test
    that proves the proof would have noticed a bad rework.)"""
    from stellar_tpu.ops import field25519 as fe
    monkeypatch.setattr(fe, "mul", _mul_dropped_carry)
    jaxprs = overflow.trace_stage_jaxprs(batch=2)
    res = overflow.analyze_closed_jaxpr(
        jaxprs["dsm"], overflow._stage_invals("dsm", 2), "dsm-mutant")
    assert res["violations"], (
        "the overflow prover accepted a field multiply with a dropped "
        "carry — the proof is vacuous")


_UNLOCKED_DOUBLE = textwrap.dedent("""
    import threading

    class StatsDouble:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0
            self.events = []

        def record(self, n):
            self.count += n
            self.events.append(n)
""")

_LOCKED_DOUBLE = textwrap.dedent("""
    import threading

    class StatsDouble:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0
            self.events = []

        def record(self, n):
            with self._lock:
                self.count += n
                self.events.append(n)
""")


def test_mutant_unlocked_write_caught():
    findings = locks.lint_source(_UNLOCKED_DOUBLE, "double.py")
    keys = sorted(f.key for f in findings)
    assert keys == ["unlocked-attr:StatsDouble.record.count",
                    "unlocked-attr:StatsDouble.record.events"]
    assert not locks.lint_source(_LOCKED_DOUBLE, "double.py")


def test_lock_lint_catches_indirect_mutations():
    """Tuple unpacking, assigned mutator calls, and nested-attribute
    stores are mutations too — the rule must see through all three."""
    src = textwrap.dedent("""
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self._q = []
                self.a = 0
                self.b = 0

            def bad(self):
                self.a, self.b = 1, 2
                item = self._q.pop(0)
                self.a = item
    """)
    keys = sorted(f.key for f in locks.lint_source(src, "c.py"))
    assert keys == ["unlocked-attr:C.bad._q",
                    "unlocked-attr:C.bad.a",
                    "unlocked-attr:C.bad.a",
                    "unlocked-attr:C.bad.b"]


def test_lock_lint_sees_mutators_in_statement_heads():
    """`if self._q.pop():` / `while ...` / `raise f(self._q.pop())`
    mutate state too — statement heads are expressions, and each call
    must be reported exactly once (no double count via recursion)."""
    src = textwrap.dedent("""
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self._q = []

            def bad(self):
                if self._q.pop(0):
                    return 1
                while self._q.pop():
                    pass
                return 0
    """)
    keys = [f.key for f in locks.lint_source(src, "c.py")]
    assert keys == ["unlocked-attr:C.bad._q",
                    "unlocked-attr:C.bad._q"]


def test_mutant_unlocked_global_caught():
    src = textwrap.dedent("""
        import threading
        _lock = threading.Lock()
        STATE = 0

        def bump():
            global STATE
            STATE += 1

        def bump_guarded():
            global STATE
            with _lock:
                STATE += 1
    """)
    findings = locks.lint_source(src, "mod.py")
    assert [f.key for f in findings] == ["unlocked-global:bump.STATE"]


def test_lock_lint_catches_inplace_global_mutations():
    """Dict/list globals are mutated without any `global` statement —
    the most common shared-state idiom must still be enforced."""
    src = textwrap.dedent("""
        import threading
        _lock = threading.Lock()
        _CACHE = {}
        _EVENTS = []

        def record(k, v):
            _CACHE[k] = v
            _EVENTS.append(v)

        def record_guarded(k, v):
            with _lock:
                _CACHE[k] = v
                _EVENTS.append(v)

        def local_ok(k, v):
            _CACHE = {}        # local shadow, not the module global
            _CACHE[k] = v
    """)
    keys = sorted(f.key for f in locks.lint_source(src, "mod.py"))
    assert keys == ["unlocked-global:record._CACHE",
                    "unlocked-global:record._EVENTS"]


# ---------------- hot-path lint units ----------------


def test_hotpath_flags_sync_and_branch():
    src = textwrap.dedent("""
        import numpy as np

        def kernel(x):
            y = np.asarray(x)       # host sync on traced value
            if x > 0:               # python branch on traced value
                y = x.item()        # another sync
            for _ in range(x):      # data-dependent trip count
                y = y + 1
            return y
    """)
    keys = {f.key for f in hotpath.lint_source(src, "k.py")}
    assert "host-sync:kernel.np.asarray" in keys
    assert "host-sync:kernel.item" in keys
    assert "traced-branch:kernel.x" in keys


def test_hotpath_taint_propagates_through_long_chains():
    """Forward dataflow must cross arbitrarily many assignment links —
    a reversed walk would only propagate one link per pass."""
    src = textwrap.dedent("""
        import numpy as np

        def kernel(x):
            a = x + 1
            b = a + 1
            c = b + 1
            d = c + 1
            if d > 0:
                return np.asarray(d)
            return d
    """)
    keys = {f.key for f in hotpath.lint_source(src, "k.py")}
    assert "traced-branch:kernel.d" in keys
    assert "host-sync:kernel.np.asarray" in keys


def test_hotpath_shape_branches_are_static():
    src = textwrap.dedent("""
        def kernel(x, flag=True):
            if x.ndim > 1:          # shape: static under trace
                x = x + 1
            if flag:                # config default: static
                x = x + 2
            if x is None:           # structural guard
                return None
            n = len(x.shape)
            for i in range(n):      # laundered through len/.shape
                x = x + i
            return x
    """)
    assert hotpath.lint_source(src, "k.py") == []


def test_hotpath_flags_jit_in_func():
    src = textwrap.dedent("""
        import jax

        def dispatch(x):
            f = jax.jit(lambda v: v + 1)
            return f(x)
    """)
    keys = {f.key for f in hotpath.lint_source(src, "d.py",
                                               device_file=False)}
    assert "jit-in-func:dispatch.jax.jit" in keys


def test_hotpath_flags_jit_decorator_and_import_forms():
    """The decorator spelling and `from jax import jit` build the same
    fresh-wrapper-per-call hazard and must not slip through."""
    src = textwrap.dedent("""
        import functools
        import jax
        from jax import jit

        def dispatch(x):
            @jax.jit
            def f(v):
                return v + 1
            g = jit(lambda v: v - 1)
            h = functools.partial(jax.jit, donate_argnums=0)
            return f(x), g(x), h
    """)
    keys = {f.key for f in hotpath.lint_source(src, "d.py",
                                               device_file=False)}
    assert "jit-in-func:dispatch.f.jax.jit" in keys   # decorator
    assert "jit-in-func:dispatch.jax.jit" in keys     # bare jit + partial

    # module-level decoration is the normal, cached pattern: clean
    top = textwrap.dedent("""
        import jax

        @jax.jit
        def kernel(v):
            return v + 1
    """)
    assert hotpath.lint_source(top, "k.py", device_file=False) == []


# ---------------- clean tree + allowlist hygiene ----------------


def test_hotpath_clean_on_tree():
    rep = hotpath.run()
    assert rep.ok, "\n" + rep.describe()


def test_locks_clean_on_tree():
    rep = locks.run()
    assert rep.ok, "\n" + rep.describe()


def test_nondet_clean_on_tree():
    rep = nondet.run()
    assert rep.ok, "\n" + rep.describe()


def test_allowlist_requires_written_reason():
    with pytest.raises(ValueError):
        Allowlist({"f.py": {"rule:sym": ""}})
    with pytest.raises(ValueError):
        Allowlist({"f.py": {"rule:sym": "ok"}})  # too short to argue


def test_lock_lint_scope_covers_threaded_modules():
    scope = set(locks.SCOPE)
    assert "stellar_tpu/crypto/batch_verifier.py" in scope
    assert "stellar_tpu/utils/resilience.py" in scope
    assert "stellar_tpu/utils/metrics.py" in scope
    assert "stellar_tpu/parallel/batch_engine.py" in scope
    # ISSUE 4: the per-device quarantine registry mutates shared state
    # from dispatch threads and breaker callbacks — it must stay under
    # lock-discipline enforcement
    assert "stellar_tpu/parallel/device_health.py" in scope


def test_nondet_lint_scope_covers_audit_sampler():
    """ISSUE 4: the audit sampler and the quarantine registry gate
    WHICH backend serves a consensus verdict — both must stay inside
    the nondeterminism lint's scope so a clock/RNG can never sneak
    into what replicas re-verify."""
    scope = set(nondet.HOST_ORACLE_FILES)
    assert "stellar_tpu/crypto/audit.py" in scope
    assert "stellar_tpu/parallel/device_health.py" in scope


def test_lint_scopes_cover_verify_service():
    """ISSUE 6: the resident verify service mutates lane queues and
    conservation counters from caller + dispatcher threads (lock
    lint), and decides WHICH work verifies vs sheds under overload —
    the shed rule must stay content-seeded and the scheduler
    clock-free (nondet lint; its only clock use is the allowlisted
    latency stamps, which must keep a written safety argument)."""
    assert "stellar_tpu/crypto/verify_service.py" in set(locks.SCOPE)
    assert "stellar_tpu/crypto/verify_service.py" in \
        set(nondet.HOST_ORACLE_FILES)
    entry = nondet.ALLOWLIST._entries.get(
        "stellar_tpu/crypto/verify_service.py", {})
    assert set(entry) == {"nondet:clock"}
    assert "never" in entry["nondet:clock"] or \
        "only" in entry["nondet:clock"]  # a real safety argument
    # the shed rule itself lives in the audit module — already scoped
    assert "stellar_tpu/crypto/audit.py" in set(nondet.HOST_ORACLE_FILES)


def test_lint_scopes_cover_tenant_scheduler():
    """ISSUE 14: the tenant QoS layer decides WHICH tenant's work
    dispatches (weighted-fair virtual time) and WHICH rows shed
    (tenant-keyed fractions + draws) — it joins the nondet scope with
    ZERO allowlist entries (the scheduler path reads no clock at
    all), and its policy/SLO state joins the lock-lint scope. The
    verify service's pre-existing clock allowlist (latency stamps)
    must NOT have grown new keys for the scheduler."""
    t = "stellar_tpu/crypto/tenant.py"
    assert t in set(nondet.HOST_ORACLE_FILES)
    assert t in set(locks.SCOPE)
    assert t not in nondet.ALLOWLIST._entries
    # the service surgery added no new nondet allowlist keys: still
    # exactly the latency-stamp clock entry
    entry = nondet.ALLOWLIST._entries.get(
        "stellar_tpu/crypto/verify_service.py", {})
    assert set(entry) == {"nondet:clock"}


def test_lint_scopes_cover_controller():
    """ISSUE 15: the closed-loop controller moves the service's
    scheduling knobs (batch size, pipeline depth, shed highwater), so
    its decisions must be a pure function of the telemetry window —
    it joins the nondet scope with ZERO allowlist entries (no clock
    read anywhere in a decision) and the lock-lint scope (trajectory
    log + knob state mutate from the dispatcher thread while admin
    routes read snapshots). The verify service's pre-existing clock
    allowlist must NOT have grown new keys for the control hook."""
    c = "stellar_tpu/crypto/controller.py"
    assert c in set(nondet.HOST_ORACLE_FILES)
    assert c in set(locks.SCOPE)
    assert c not in nondet.ALLOWLIST._entries
    assert c not in locks.ALLOWLIST._entries
    # the control surgery added no new nondet allowlist keys to the
    # service: still exactly the latency-stamp clock entry
    entry = nondet.ALLOWLIST._entries.get(
        "stellar_tpu/crypto/verify_service.py", {})
    assert set(entry) == {"nondet:clock"}


def test_lint_scopes_cover_fleet():
    """ISSUE 17: the fleet router decides WHICH replica serves every
    (lane, tenant) key and WHO gets convicted of divergence — both
    must be pure functions of the submission history (SHA-256
    rendezvous draws + event-count probation, zero clock/RNG), so
    fleet.py joins the nondet scope with ZERO allowlist entries; its
    routing tables, conviction log and conservation counters mutate
    from submitter threads while admin routes read snapshots, so it
    joins the lock-lint scope with ZERO allowlist entries too. The
    fleet surgery (replica stamps, handoff terminal, trace_lo
    re-submission) must NOT have grown the verify service's
    pre-existing clock allowlist."""
    f = "stellar_tpu/crypto/fleet.py"
    assert f in set(nondet.HOST_ORACLE_FILES)
    assert f in set(locks.SCOPE)
    assert f not in nondet.ALLOWLIST._entries
    assert f not in locks.ALLOWLIST._entries
    entry = nondet.ALLOWLIST._entries.get(
        "stellar_tpu/crypto/verify_service.py", {})
    assert set(entry) == {"nondet:clock"}


def test_lint_scopes_cover_ingress():
    """ISSUE 19: two nodes decoding the same bytes must always agree
    on what arrived, so the frame codec and the ingress server join
    the nondet scope with ZERO allowlist entries (no clock, no RNG —
    read deadlines are poll-counted, pack timing is measured by the
    unscoped soak harness); the server's conservation counters mutate
    from accept/reader/responder threads under one cv while socket
    ops run lock-free, so both files join the lock scope — and the
    lock-order prover's allowlist must NOT have grown for them (no
    blocking call under a lock gets excused on the wire path)."""
    from stellar_tpu.analysis import lockorder
    for mod in ("stellar_tpu/crypto/ingress.py",
                "stellar_tpu/utils/wire.py"):
        assert mod in set(nondet.HOST_ORACLE_FILES), mod
        assert mod in set(locks.SCOPE), mod
        assert mod not in nondet.ALLOWLIST._entries, mod
        assert mod not in locks.ALLOWLIST._entries, mod
        assert mod not in lockorder.ALLOWLIST._entries, mod
    # the reusable lease pool rides the lock scope too (refcounts
    # mutate from reader + responder threads)
    assert "stellar_tpu/parallel/hostbuf.py" in set(locks.SCOPE)
    assert "stellar_tpu/parallel/hostbuf.py" not in \
        locks.ALLOWLIST._entries


def test_lint_scopes_cover_batch_engine():
    """ISSUE 7: the workload-agnostic engine owns the jit-bucket cache,
    device-health registry and served-counter RMWs from resolver/pool/
    breaker threads (lock lint), and decides WHICH backend serves every
    workload's rows (nondet lint — its clock use and tracing ownership
    must keep written safety arguments); the SHA-256 workload's host
    helpers and plugin produce CONSENSUS state (header/bucket/TxSet
    identities), so they join the nondet scope, and the kernel module
    joins the hot-path scope."""
    eng = "stellar_tpu/parallel/batch_engine.py"
    for mod in (eng, "stellar_tpu/crypto/batch_hasher.py"):
        assert mod in set(locks.SCOPE), mod
    for mod in (eng, "stellar_tpu/ops/sha256.py",
                "stellar_tpu/crypto/batch_hasher.py"):
        assert mod in set(nondet.HOST_ORACLE_FILES), mod
    assert eng in set(hotpath.SCOPE_HOST)
    entry = nondet.ALLOWLIST._entries.get(eng, {})
    assert set(entry) == {"nondet:clock", "nondet:tracing-import"}
    for key in entry:  # real safety arguments, not rubber stamps
        assert "never" in entry[key] or "only" in entry[key], key
    # the plugin modules carry NO nondet allowlist — clock/RNG-free
    # by design, like audit.py and device_health.py before them
    for mod in ("stellar_tpu/ops/sha256.py",
                "stellar_tpu/crypto/batch_hasher.py"):
        assert mod not in nondet.ALLOWLIST._entries, mod


def test_lint_scopes_cover_transfer_ledger_and_sentinel():
    """ISSUE 8: the transfer ledger mutates per-resolve accounting
    and the fingerprint LRU from resolver + pool threads (lock lint),
    and both it and the perf sentinel gate tier-1 verdicts — their
    fingerprints/drift decisions must stay content-derived, no clocks
    or RNG (nondet lint). Neither carries an allowlist entry:
    clock/RNG-free by design, like audit.py."""
    led = "stellar_tpu/utils/transfer_ledger.py"
    assert led in set(locks.SCOPE)
    assert led in set(nondet.HOST_ORACLE_FILES)
    assert "tools/perf_sentinel.py" in set(nondet.HOST_ORACLE_FILES)
    for mod in (led, "tools/perf_sentinel.py"):
        assert mod not in nondet.ALLOWLIST._entries, mod


def test_lint_scopes_cover_signer_tables():
    """ISSUE 16: the per-pubkey table cache's LRU mutates from every
    partitioning submit thread (lock lint), and it decides which rows
    ride the hot kernel — fingerprints must stay content-derived and
    eviction clock/RNG-free (nondet lint), or replicas diverge on
    which kernel variant served a row. No allowlist entry: clock/
    RNG-free by design, like the transfer ledger."""
    st = "stellar_tpu/parallel/signer_tables.py"
    assert st in set(locks.SCOPE)
    assert st in set(nondet.HOST_ORACLE_FILES)
    assert st not in nondet.ALLOWLIST._entries


def test_lint_scopes_cover_journal():
    """ISSUE 20: the unified journal is the fleet's determinism
    surface — two replicas' journals must merge bit-identically, so
    journal.py must stay clock/RNG-free (nondet scope) and, being a
    pure function of the logs it is handed, lock-free (lock scope
    proves it grows no unordered lock). ZERO allowlist entries in
    either lint: an excused journal is no determinism surface at
    all."""
    mod = "stellar_tpu/utils/journal.py"
    assert mod in set(nondet.HOST_ORACLE_FILES)
    assert mod in set(locks.SCOPE)
    assert mod not in nondet.ALLOWLIST._entries
    assert mod not in locks.ALLOWLIST._entries


def test_lint_scopes_cover_pipeline_timeline():
    """ISSUE 10: the pipeline-bubble profiler's tokens and ring
    mutate from submitter + resolver + service-dispatcher threads —
    lock-lint scoped. It is deliberately NOT in the nondet scope: it
    is clock-bearing observability BY DESIGN (like tracing), and the
    engine reaches it only through the duration-blind token API, so
    no clock value ever flows back into a scoped module."""
    assert "stellar_tpu/utils/timeline.py" in set(locks.SCOPE)
    assert "stellar_tpu/utils/timeline.py" not in \
        set(nondet.HOST_ORACLE_FILES)
    # the time-series ring lives inside metrics.py — already scoped
    assert "stellar_tpu/utils/metrics.py" in set(locks.SCOPE)


def test_sha256_overflow_golden_committed():
    """ISSUE 7: the hash workload gets the verify kernel's discipline —
    a committed proven envelope, diffed (not pass/failed) by
    tools/analyze.py, in its OWN golden file so the ed25519 envelope
    (docs/limb_bounds.json) diffs independently."""
    golden = overflow.load_sha_golden(str(repo_root()))
    assert golden is not None, (
        f"{overflow.SHA_GOLDEN_PATH} missing — run tools/analyze.py "
        "--write-golden and review the envelope")
    assert golden["stages"]["sha256_kernel"]["outputs"]["digest"] == \
        [[0, 0xFFFFFFFF]]  # digest words span exactly uint32
    assert golden["word_layout"]["rounds"] == 64


def test_lock_lint_scope_covers_tracing_ring():
    """ISSUE 5: the flight-recorder ring + active-span map mutate from
    resolver, pool-worker and breaker-callback threads; the reservoir
    RMW lives in metrics. Both must stay under lock enforcement."""
    scope = set(locks.SCOPE)
    assert "stellar_tpu/utils/tracing.py" in scope
    assert "stellar_tpu/utils/metrics.py" in scope


def test_nondet_lint_fences_tracing_out_of_consensus():
    """ISSUE 5: tracing is clock-bearing BY DESIGN — consensus modules
    may import only its duration-blind context managers. Anything that
    exposes readable clock state (the module itself, the flight
    recorder, span_totals) is a finding."""
    flagged = nondet.lint_source(
        "from stellar_tpu.utils import tracing\n", "x.py")
    assert any(f.symbol == "tracing-import" for f in flagged)
    flagged = nondet.lint_source(
        "import stellar_tpu.utils.tracing\n", "x.py")
    assert any(f.symbol == "tracing-import" for f in flagged)
    flagged = nondet.lint_source(
        "from stellar_tpu.utils.tracing import flight_recorder\n",
        "x.py")
    assert any(f.symbol == "tracing-import" for f in flagged)
    flagged = nondet.lint_source(
        "from stellar_tpu.utils.tracing import span_totals\n", "x.py")
    assert any(f.symbol == "tracing-import" for f in flagged)
    # the parenthesized utils-import spelling can't slip the module in
    flagged = nondet.lint_source(
        "from stellar_tpu.utils import (\n    faults,\n"
        "    tracing,\n)\n", "x.py")
    assert any(f.symbol == "tracing-import" for f in flagged)
    clean = nondet.lint_source(
        "from stellar_tpu.utils import (\n    faults,\n)\n", "x.py")
    assert not [f for f in clean if f.symbol == "tracing-import"]
    # ...and neither can backslash continuations, in either spelling
    flagged = nondet.lint_source(
        "from stellar_tpu.utils.tracing import zone, \\\n"
        "    span_totals\n", "x.py")
    assert any(f.symbol == "tracing-import" for f in flagged)
    flagged = nondet.lint_source(
        "from stellar_tpu.utils import faults, \\\n    tracing\n",
        "x.py")
    assert any(f.symbol == "tracing-import" for f in flagged)
    # the sanctioned names pass, including the ledger_manager's
    # parenthesized multi-line spelling
    clean = nondet.lint_source(
        "from stellar_tpu.utils.tracing import (\n"
        "    LogSlowExecution, frame_mark, zone,\n"
        ")\n", "x.py")
    assert not [f for f in clean if f.symbol == "tracing-import"]
    clean = nondet.lint_source(
        "from stellar_tpu.utils.tracing import zone\n", "x.py")
    assert not [f for f in clean if f.symbol == "tracing-import"]
    # the tracing module itself must never enter the nondet scope —
    # its clock reads are the sanctioned implementation, fenced by
    # this import rule instead
    scoped = set(nondet.HOST_ORACLE_FILES)
    assert "stellar_tpu/utils/tracing.py" not in scoped
    assert "stellar_tpu/utils/metrics.py" not in scoped


def test_nondet_bans_perf_counter_in_consensus():
    """ISSUE 5: perf_counter joined the clock ban — before the fence,
    consensus code could read the one clock tracing uses."""
    flagged = nondet.lint_source("t0 = time.perf_counter()\n", "x.py")
    assert any(f.symbol == "clock" for f in flagged)


# ---------------- lock-order prover (ISSUE 18) ----------------


_CYCLE_A = textwrap.dedent("""
    import threading
    import modb
    _la = threading.Lock()

    def fa():
        with _la:
            modb.fb()

    def fa2():
        with _la:
            pass
""")

_CYCLE_B = textwrap.dedent("""
    import threading
    import moda
    _lb = threading.Lock()

    def fb():
        with _lb:
            pass

    def fb2():
        with _lb:
            moda.fa2()
""")


def test_lockorder_synthetic_two_module_cycle_caught():
    """The acceptance fixture: moda holds _la and calls into modb
    (acquiring _lb); modb holds _lb and calls back into moda
    (acquiring _la). Both acquisition paths must be printed, and a
    report built from the findings must fail (exit nonzero through
    tools/analyze.py)."""
    findings, graph = lockorder.run_sources(
        {"moda.py": _CYCLE_A, "modb.py": _CYCLE_B})
    cycles = [f for f in findings if f.rule == "lock-cycle"]
    assert len(cycles) == 1, [f.key for f in findings]
    msg = cycles[0].message
    assert "moda._la -> modb._lb" in msg  # path one
    assert "modb._lb -> moda._la" in msg  # path two
    assert "calls fb" in msg and "calls fa2" in msg
    assert graph["edges"]["moda._la"] == ["modb._lb"]
    assert graph["edges"]["modb._lb"] == ["moda._la"]
    rep = finish_report("lockorder", 2, findings, Allowlist({}))
    assert not rep.ok  # what makes analyze.py exit nonzero


def test_lockorder_cycle_free_graph_passes():
    """Same two modules, one acquisition direction only: edges exist,
    no cycle, no findings."""
    b_one_way = _CYCLE_B.replace("moda.fa2()", "pass")
    findings, graph = lockorder.run_sources(
        {"moda.py": _CYCLE_A, "modb.py": b_one_way})
    assert findings == []
    assert graph["edges"]["moda._la"] == ["modb._lb"]
    assert "modb._lb" not in graph["edges"]


def test_lockorder_hold_and_block_through_helper_hop():
    """A blocking op reached through a helper-function hop while a
    lock is held must be attributed to the lock-holding caller, with
    the call path in the message."""
    src = textwrap.dedent("""
        import threading
        import time
        _l = threading.Lock()

        def helper():
            time.sleep(1.0)

        def outer():
            with _l:
                helper()
    """)
    findings, _ = lockorder.run_sources({"mod.py": src})
    assert [f.key for f in findings] == \
        ["hold-and-block:outer.helper.sleep"]
    assert "mod.py:helper" in findings[0].message
    # the helper alone (no lock held anywhere) is clean
    clean, _ = lockorder.run_sources({"mod.py": src.replace(
        "with _l:\n        helper()", "helper()")})
    assert clean == []


def test_lockorder_inverted_order_mutation_caught():
    """Mutation test against a vacuous pass: a test double acquiring
    A->B in one method and B->A in another must produce a lock-cycle
    finding — if this double ever passes, the prover is broken."""
    src = textwrap.dedent("""
        import threading

        class Pair:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def fwd(self):
                with self._a:
                    with self._b:
                        pass

            def rev(self):
                with self._b:
                    with self._a:
                        pass
    """)
    findings, _ = lockorder.run_sources({"pair.py": src})
    cycles = [f for f in findings if f.rule == "lock-cycle"]
    assert len(cycles) == 1
    assert "pair.Pair._a" in cycles[0].message
    assert "pair.Pair._b" in cycles[0].message
    rep = finish_report("lockorder", 1, findings, Allowlist({}))
    assert not rep.ok
    # fixing one direction clears it
    fixed = src.replace("with self._b:\n            with self._a:",
                        "with self._a:\n            with self._b:")
    clean, _ = lockorder.run_sources({"pair.py": fixed})
    assert not [f for f in clean if f.rule == "lock-cycle"]


def test_lockorder_untimed_wait_flagged_timed_ok():
    """cv.wait() without a timeout is an unbounded park (the
    WatchdogPool allowlist entry's exact shape); cv.wait(0.05) is
    bounded and clean — and untimed join/Queue.get follow suit."""
    src = textwrap.dedent("""
        import threading

        class W:
            def __init__(self):
                self._cv = threading.Condition()

            def park(self):
                with self._cv:
                    self._cv.wait()

            def poll(self):
                with self._cv:
                    self._cv.wait(0.05)
    """)
    findings, _ = lockorder.run_sources({"w.py": src})
    assert [f.key for f in findings] == \
        ["hold-and-block:W.park.wait-untimed"]
    src2 = textwrap.dedent("""
        import threading
        _l = threading.Lock()

        def drain(q, t):
            with _l:
                q.get()
                t.join()

        def drain_bounded(q, t):
            with _l:
                q.get(timeout=1.0)
                t.join(1.0)
    """)
    findings2, _ = lockorder.run_sources({"m.py": src2})
    assert sorted(f.key for f in findings2) == [
        "hold-and-block:drain.join-untimed",
        "hold-and-block:drain.queue-get"]


def test_lockorder_deferred_closures_not_attributed():
    """A closure defined under a lock runs later, possibly outside
    it — its body must not be charged to the lock holder (the same
    lexical convention the locks lint encodes)."""
    src = textwrap.dedent("""
        import threading
        import time
        _l = threading.Lock()

        def schedule(pool):
            with _l:
                def later():
                    time.sleep(1.0)
                pool.submit(later)
    """)
    findings, _ = lockorder.run_sources({"m.py": src})
    assert findings == []


def test_lockorder_clean_on_tree():
    rep = lockorder.run()
    assert rep.ok, "\n" + rep.describe()


def test_lockorder_graph_covers_scope():
    """Acceptance: the acquisition graph covers every module in
    locks.SCOPE — a SCOPE entry the prover cannot parse would
    silently shrink coverage."""
    graph = lockorder.build_graph()
    assert set(graph["modules"]) == set(locks.SCOPE)
    # the known seams are live: the fleet router reaches the service
    # cv, and the service cv reaches the SLO/tenant/metrics tier
    edges = graph["edges"]
    assert "verify_service.VerifyService._cv" in \
        edges["fleet.FleetRouter._lock"]
    assert "metrics.MetricsRegistry._lock" in \
        edges["verify_service.VerifyService._cv"]


def test_lockorder_allowlist_pinned():
    """Every hold-and-block allowlist entry is a written safety
    argument over exactly the expected parks: the watchdog pool's
    idle wait and the four one-shot native compile locks. Anything
    new must argue its case here."""
    entries = {rel: sorted(keys)
               for rel, keys in lockorder.ALLOWLIST._entries.items()}
    assert entries == {
        "stellar_tpu/utils/resilience.py":
            ["hold-and-block:WatchdogPool._loop.wait-untimed"],
        "stellar_tpu/utils/native.py":
            ["hold-and-block:_load.build_lib.subprocess"],
        "stellar_tpu/crypto/native_prep.py":
            ["hold-and-block:_load.build_lib.subprocess"],
        "stellar_tpu/crypto/native_verify.py":
            ["hold-and-block:_load.build_lib.subprocess"],
        "stellar_tpu/soroban/native_wasm.py":
            ["hold-and-block:_load.build_lib.subprocess",
             "hold-and-block:_load_ext.build_lib.subprocess"],
    }


def test_workers_shutdown_regression():
    """The real finding ISSUE 18's prover surfaced: workers.shutdown()
    used to run pool.shutdown(wait=True) UNDER the submission lock
    (wedging any concurrent run_async), and set_background stored its
    global without the lock. The old spellings must trip the lints;
    the shipped module must be clean."""
    old = textwrap.dedent("""
        import threading
        _pool = None
        _lock = threading.Lock()
        _background = True

        def set_background(enabled):
            global _background
            _background = enabled

        def shutdown():
            global _pool
            with _lock:
                if _pool is not None:
                    _pool.shutdown(wait=True)
                    _pool = None
    """)
    held, _ = lockorder.run_sources({"workers.py": old})
    assert "hold-and-block:shutdown.executor-shutdown" in \
        [f.key for f in held]
    assert "unlocked-global:set_background._background" in \
        [f.key for f in locks.lint_source(old, "workers.py")]
    rel = "stellar_tpu/utils/workers.py"
    shipped = (repo_root() / rel).read_text()
    assert locks.lint_source(shipped, rel) == []
    fixed, _ = lockorder.run_sources({rel: shipped})
    assert fixed == []


# ---------------- scope-drift meta-lint (ISSUE 18) ----------------


def test_scope_drift_catches_unscoped_lock_owner():
    """Removing a lock-owning module from locks.SCOPE must produce a
    scope-drift finding — new threaded files can no longer silently
    escape the mutation lint and the lock-order prover."""
    pruned = [s for s in locks.SCOPE
              if not s.endswith("workers.py")]
    hits = [f for f in locks.drift_findings(scope=pruned)
            if f.file == "stellar_tpu/utils/workers.py"]
    assert len(hits) == 1
    assert hits[0].key == "scope-drift:lock-ctor"
    # the real tree's only unscoped lock owners are the two argued
    # allowlist entries (crank-disciplined VirtualClock, the query
    # throttle semaphore)
    assert sorted({f.file for f in locks.drift_findings()}) == [
        "stellar_tpu/main/command_handler.py",
        "stellar_tpu/utils/timer.py"]


def test_nondet_scope_drift_catches_oracle_composition():
    """A crypto module importing host-oracle modules while absent
    from HOST_ORACLE_FILES is a finding (batch_verifier.py is the
    module that made this rule necessary); the shipped tree is
    drift-free."""
    pruned = [s for s in nondet.HOST_ORACLE_FILES
              if not s.endswith("batch_verifier.py")]
    hits = [f for f in nondet.drift_findings(scope=pruned)
            if f.file == "stellar_tpu/crypto/batch_verifier.py"]
    assert len(hits) == 1
    assert hits[0].key == "scope-drift:host-oracle-import"
    assert nondet.drift_findings() == []


def test_scope_sets_pinned():
    """The ISSUE 18 pin: both scope sets, exactly. Growing either is
    routine (add the file + this pin moves with it); SHRINKING either
    must be a loud, reviewed act — scope removal is how a lint dies
    in place."""
    assert sorted(locks.SCOPE) == sorted([
        "stellar_tpu/crypto/batch_verifier.py",
        "stellar_tpu/crypto/batch_hasher.py",
        "stellar_tpu/crypto/verify_service.py",
        "stellar_tpu/crypto/tenant.py",
        "stellar_tpu/crypto/controller.py",
        "stellar_tpu/crypto/fleet.py",
        "stellar_tpu/crypto/ingress.py",
        "stellar_tpu/crypto/keys.py",
        "stellar_tpu/crypto/native_prep.py",
        "stellar_tpu/crypto/native_verify.py",
        "stellar_tpu/parallel/batch_engine.py",
        "stellar_tpu/parallel/device_health.py",
        "stellar_tpu/parallel/hostbuf.py",
        "stellar_tpu/parallel/signer_tables.py",
        "stellar_tpu/soroban/native_wasm.py",
        "stellar_tpu/utils/faults.py",
        "stellar_tpu/utils/journal.py",
        "stellar_tpu/utils/metrics.py",
        "stellar_tpu/utils/wire.py",
        "stellar_tpu/utils/native.py",
        "stellar_tpu/utils/native_build.py",
        "stellar_tpu/utils/resilience.py",
        "stellar_tpu/utils/tracing.py",
        "stellar_tpu/utils/transfer_ledger.py",
        "stellar_tpu/utils/timeline.py",
        "stellar_tpu/utils/workers.py",
        "stellar_tpu/xdr/runtime.py",
    ])
    crypto_scope = {f for f in nondet.HOST_ORACLE_FILES
                    if f.startswith("stellar_tpu/crypto/")}
    assert crypto_scope == {
        "stellar_tpu/crypto/audit.py",
        "stellar_tpu/crypto/batch_hasher.py",
        "stellar_tpu/crypto/batch_verifier.py",
        "stellar_tpu/crypto/bls12_381.py",
        "stellar_tpu/crypto/controller.py",
        "stellar_tpu/crypto/curve25519.py",
        "stellar_tpu/crypto/ed25519_ref.py",
        "stellar_tpu/crypto/fleet.py",
        "stellar_tpu/crypto/h2c.py",
        "stellar_tpu/crypto/ingress.py",
        "stellar_tpu/crypto/keccak.py",
        "stellar_tpu/crypto/keys.py",
        "stellar_tpu/crypto/nacl_box.py",
        "stellar_tpu/crypto/native_prep.py",
        "stellar_tpu/crypto/native_verify.py",
        "stellar_tpu/crypto/secp256.py",
        "stellar_tpu/crypto/sha.py",
        "stellar_tpu/crypto/shorthash.py",
        "stellar_tpu/crypto/strkey.py",
        "stellar_tpu/crypto/verify_service.py",
        "stellar_tpu/crypto/tenant.py",
    }
    # nacl_box composes curve25519 with zero clock/RNG of its own:
    # scoped, NO allowlist entry
    assert "stellar_tpu/crypto/nacl_box.py" not in \
        nondet.ALLOWLIST._entries


# ---------------- proof-coverage gate (ISSUE 18) ----------------


def test_proof_coverage_clean_on_tree():
    """Every registered kernel variant (cold, hot, sha256) maps to a
    proven envelope stage in a committed golden."""
    cov = coverage.run()
    assert cov["ok"], cov
    assert cov["proven"] == 3
    assert {k["class"] for k in cov["kernels"]} >= {
        "Ed25519Workload", "Ed25519HotWorkload", "Sha256Workload"}
    assert all(k["proven"] for k in cov["kernels"])


def test_proof_coverage_ignores_test_fixture_workloads():
    """Workload subclasses defined outside the stellar_tpu package
    (test fixtures, scratch scripts) are not dispatchable variants and
    must not leak into the gate via ``__subclasses__()``."""
    from stellar_tpu.parallel import batch_engine

    class _FixtureWorkload(batch_engine.Workload):  # noqa: unused
        metrics_ns = "test.fixture"
        variant_name = None

    names = {c for _ns, _v, c in coverage.enumerate_kernels()}
    assert "_FixtureWorkload" not in names
    assert coverage.run()["ok"]


def test_proof_coverage_unmapped_variant_fails():
    """A future Workload plugin with no PROOF_STAGES mapping (the
    ROADMAP's BLS/MSM shape) must fail the gate."""
    findings, rows = coverage.check(
        [("crypto.bls", "msm", "BlsMsmWorkload")], {})
    assert [f.key for f in findings] == \
        ["proof-coverage:crypto.bls:msm"]
    assert rows[0]["proven"] is False
    rep = finish_report("proof_coverage", 1, findings, Allowlist({}))
    assert not rep.ok


def test_proof_coverage_missing_stage_fails():
    """A mapped variant whose committed golden lacks the proven stage
    (the forgot-to-rerun---write-golden shape) must fail too."""
    stages = {("crypto.verify", None):
              ("docs/limb_bounds.json", "kernel_total")}
    goldens = {"docs/limb_bounds.json": {"stages": {}}}
    findings, rows = coverage.check(
        [("crypto.verify", None, "Ed25519Workload")], goldens,
        proof_stages=stages)
    assert [f.key for f in findings] == \
        ["proof-coverage:crypto.verify:cold"]
    assert not rows[0]["proven"]
    # with the stage present and enveloped, it proves
    goldens = {"docs/limb_bounds.json":
               {"stages": {"kernel_total": {"max_abs": 7}}}}
    findings, rows = coverage.check(
        [("crypto.verify", None, "Ed25519Workload")], goldens,
        proof_stages=stages)
    assert findings == [] and rows[0]["proven"]


def test_stale_allowlist_fails_every_family():
    """The ISSUE 18 sweep: a stale allowlist entry FAILS a report
    (rep.ok False -> analyze.py exits nonzero) for every lint family,
    not just warns."""
    stale = Allowlist({"ghost.py": {"rule:gone": "a written reason "
                                    "for code that no longer exists"}})
    rep = finish_report("locks", 1, [], stale)
    assert rep.stale_allowlist == ["ghost.py:rule:gone"]
    assert not rep.ok
