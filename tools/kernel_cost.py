"""Static cost accounting for the TPU verify kernel, from traced jaxprs.

Kernel optimizations need a hardware-independent scoreboard next to the
chip's timings (``chip_smoke.py``, ``bench.py``). This tool traces
the jitted verify kernel's three stages —

  * ``decompress``       — ``ops.edwards.decompress`` (A frombytes),
  * ``dsm``              — scalar recode + table build + the Strauss-Shamir
                           double-scalarmult loop (the hot loop), and
  * ``compress_compare`` — ``ops.edwards.compress_equals`` (one field inverse
                           + canonical compare)

— and counts multiply work two ways from the jaxpr:

  * **static**   — multiply *ops* (HLO ``mul``/``dot_general`` equations) with
    every ``scan``/``while`` body counted ONCE: the size of the compiled
    program, the cost model for a launch-overhead-bound kernel (the repo's
    measured regime on small batches — see ``ops.edwards._mulstack``'s
    note).
  * **weighted** — the same traversal with ``scan`` bodies multiplied by their
    static trip counts: total multiply ops *executed* per kernel call.  The
    element variant (``*_elems``) additionally weights each op by its output
    element count, i.e. scalar multiply (MAC) volume per call.

``select_macs_per_verify`` is the analytic one-hot-contraction volume of the
window selects (2 tables x 64 windows x entries x 4 coords x 20 limbs): the
quantity the signed-window rework (PR 1) halves.

``select_macs_per_verify`` is the analytic one-hot-contraction volume of the
window selects (2 tables x windows x entries x coords x 20 limbs). Since the
PR 13 batched-affine rework the landed kernel selects by a multiply-free
cmov tree (ref10 ge25519_select), so the landed value is ZERO and the select
work is carried as ``select_logic_elems_per_verify`` instead — reclassified,
not hidden (the §3 ledger shows both columns).

Run as a script for one JSON line (``tests/test_kernel_cost.py`` uses it as
a regression gate):

    python tools/kernel_cost.py                    # pretty
    python tools/kernel_cost.py --json             # one JSON line
    python tools/kernel_cost.py --json --workload=record  # slim consumer
    python tools/kernel_cost.py --sweep            # radix-window sweep
"""

from __future__ import annotations

import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

BATCH_DEFAULT = 128

# Bumped when the window scheme / cost-shape of the kernel is REWORKED
# deliberately (with its docs/kernel_design.md §3 ledger): consumers
# comparing records across versions (tools/perf_sentinel.py) treat the
# kernel-cost family as re-baselined instead of as drift.
#   1 — PR 1 signed radix-16, projective A-tables, one-hot selects
#   2 — PR 13 signed radix-32, batched-affine tables (fe.batch_inv),
#       cmov-tree selects, strength-reduced carry fold
#   3 — PR 16 hot-signer split: the ledger grows the radix-256
#       cached-table arm (``dsm.hot``; the radix-32 live-build arm is
#       now ``dsm.cold`` and keeps the headline names) plus the
#       ``signer_table`` geometry section
LEDGER_VERSION = 3

# The enforced ledger rows (tier-1 echoes KERNEL_COST_OK=<count>): slim
# record path -> (ceiling, why). Enforced by tests/test_kernel_cost.py;
# tools/perf_sentinel.py additionally trend-gates the same paths at +2%
# between consecutive bench records of the same ledger version.
ENFORCED_LEDGER_ROWS = {
    "dsm.executed_macs_per_call": (
        123_952_089, "acceptance: >= 10% below the PR 1 executed ledger"
        " (137 724 544)"),
    "dsm.static_mul_ops": (
        1076, "PR 1 acceptance held: >= 30% below the unsigned 1538"),
    "kernel_static_mul_ops": (
        2818, "whole-kernel program size never above the PR 1 point"),
    "select_macs_per_verify": (
        0, "window selects stay off the multiply units entirely"),
    "affine_table.batch_inv_weighted_mul_elems": (
        6_000_000, "the Montgomery chain stays ~1 inversion per call"
        " (a per-lane inv would cost ~8.2M elems at batch 128)"),
    "dsm.hot.executed_macs_per_call": (
        92_099_632, "ISSUE 16 acceptance: hot-signer dsm >= 20% below"
        " the landed cold executed ledger (0.80 x 115 124 540; landed"
        " hot arm is 87 439 360 = -24.05%)"),
    "signer_table.bytes_per_signer": (
        15_360, "128-entry int16 affine table stays 15 KiB/signer —"
        " the cache-budget unit every knob doc quotes"),
}


def force_cpu():
    """Pin jax to CPU: tracing needs a backend for constants, and the
    counts are hardware-independent. Must run before jax initializes a
    backend."""
    from stellar_tpu.utils.jax_setup import force_cpu as _force_cpu
    _force_cpu()


# Multiply-like primitives. ``mul`` is elementwise; ``dot_general`` (none in
# the current kernel, but counted defensively) weights by contraction size.
_MUL_PRIMS = ("mul", "dot_general")


def _out_elems(eqn) -> int:
    import numpy as np
    n = 0
    for v in eqn.outvars:
        aval = v.aval
        n += int(np.prod(aval.shape)) if aval.shape else 1
    if eqn.primitive.name == "dot_general":
        dims = eqn.params["dimension_numbers"][0][0]
        lhs = eqn.invars[0].aval.shape
        for d in dims:
            n *= int(lhs[d])
    return n


def _sub_jaxprs(eqn):
    """Yield (sub_jaxpr, trip_count) pairs for an equation's nested bodies.
    trip_count is None when unknown (while bodies, cond branches)."""
    from jax.extend import core
    name = eqn.primitive.name
    if name == "scan":
        yield eqn.params["jaxpr"], int(eqn.params["length"])
        return
    if name == "while":
        yield eqn.params["cond_jaxpr"], None
        yield eqn.params["body_jaxpr"], None
        return
    if name == "cond":
        for br in eqn.params["branches"]:
            yield br, None
        return
    for val in eqn.params.values():
        vals = val if isinstance(val, (list, tuple)) else (val,)
        for v in vals:
            if isinstance(v, core.ClosedJaxpr):
                yield v, 1
            elif isinstance(v, core.Jaxpr):
                yield v, 1


def count_prims(jaxpr, prims) -> dict:
    """Count ops/elements of ``prims`` (or EVERY primitive when None)
    in a (Closed)Jaxpr.

    Returns dict with ``static_ops``/``static_elems`` (loop bodies
    once) and ``weighted_ops``/``weighted_elems`` (scan bodies times
    their trip counts; unknown-trip bodies count once and set
    ``has_unbounded_loop``).
    """
    from jax.extend import core
    if isinstance(jaxpr, core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    out = {"static_ops": 0, "static_elems": 0,
           "weighted_ops": 0, "weighted_elems": 0,
           "has_unbounded_loop": False}
    for eqn in jaxpr.eqns:
        if prims is None or eqn.primitive.name in prims:
            elems = _out_elems(eqn)
            out["static_ops"] += 1
            out["static_elems"] += elems
            out["weighted_ops"] += 1
            out["weighted_elems"] += elems
        for sub, trips in _sub_jaxprs(eqn):
            c = count_prims(sub, prims)
            out["static_ops"] += c["static_ops"]
            out["static_elems"] += c["static_elems"]
            w = 1 if trips is None else trips
            out["weighted_ops"] += w * c["weighted_ops"]
            out["weighted_elems"] += w * c["weighted_elems"]
            out["has_unbounded_loop"] |= (
                trips is None or c["has_unbounded_loop"])
    return out


def count_jaxpr(jaxpr) -> dict:
    """Multiply-op counts (the verify kernel's scoreboard metric),
    under the historical ``*_mul_*`` key names."""
    c = count_prims(jaxpr, _MUL_PRIMS)
    return {"static_mul_ops": c["static_ops"],
            "static_mul_elems": c["static_elems"],
            "weighted_mul_ops": c["weighted_ops"],
            "weighted_mul_elems": c["weighted_elems"],
            "has_unbounded_loop": c["has_unbounded_loop"]}


def _abstract_inputs(batch: int):
    import jax
    import numpy as np
    bytes32 = jax.ShapeDtypeStruct((batch, 32), np.uint8)
    from stellar_tpu.ops import field25519 as fe
    limb = jax.ShapeDtypeStruct((fe.NLIMBS, batch), np.int32)
    return bytes32, (limb, limb, limb, limb)


def _abstract_hot_table(batch: int):
    """The hot-path cached-table operand exactly as the verifier ships
    it: batch-leading (batch, 128, 3, 20) int16 canonical limbs."""
    import jax
    import numpy as np
    from stellar_tpu.ops import edwards as ed
    from stellar_tpu.ops import field25519 as fe
    return jax.ShapeDtypeStruct(
        (batch, ed.TABLE_ENTRIES256, ed.AFFINE_COORDS, fe.NLIMBS),
        np.int16)


def analytic_window_costs(radix: int) -> dict:
    """Closed-form window-scheme quantities for one sweep arm (the
    numbers a change to WINDOWS/TABLE_ENTRIES moves even before
    tracing). ``select_macs``: one-hot contraction multiply volume per
    verify (zero for the cmov-tree arm); ``select_logic_elems``: cmov
    tree select/compare element volume per verify (zero for the
    one-hot arm) — the same work carried on the other unit class."""
    from stellar_tpu.ops import edwards as ed
    if radix == 16:
        windows, entries, coords = ed.WINDOWS, ed.TABLE_ENTRIES, 4
        return {
            "radix": 16, "windows": windows, "table_entries": entries,
            "doublings": 4 * windows, "cached_adds": 2 * windows,
            "affine_a_table": False,
            "select_macs": 2 * windows * entries * coords * 20,
            "select_logic_elems": 0,
        }
    if radix == 32:
        windows, entries = ed.WINDOWS32, ed.TABLE_ENTRIES32
        coords = ed.AFFINE_COORDS
        return {
            "radix": 32, "windows": windows, "table_entries": entries,
            # the top window skips its doubling chain (accumulator
            # seeded from the selected B-entry)
            "doublings": 5 * (windows - 1),
            "cached_adds": 2 * windows - 1,
            "affine_a_table": True,
            "select_macs": 0,
            "select_logic_elems":
                2 * windows * (entries - 1) * coords * 20,
        }
    if radix == 256:
        # the hot-signer cached-table arm (ISSUE 16): byte-aligned
        # windows, 128-entry tables shipped as operands (no in-kernel
        # build at all), cmov-tree selects like the radix-32 arm
        windows, entries = ed.WINDOWS256, ed.TABLE_ENTRIES256
        coords = ed.AFFINE_COORDS
        return {
            "radix": 256, "windows": windows, "table_entries": entries,
            "doublings": 8 * (windows - 1),
            "cached_adds": 2 * windows - 1,
            "affine_a_table": True,
            "cached_table_operand": True,
            "select_macs": 0,
            "select_logic_elems":
                2 * windows * (entries - 1) * coords * 20,
        }
    raise ValueError(f"unknown radix {radix}")


def trace_dsm_variant(radix: int, batch: int = BATCH_DEFAULT) -> dict:
    """Traced multiply counts for ONE radix arm of the sweep (recode +
    table build + Strauss-Shamir loop, the dsm stage shape), regardless
    of which arm the kernel currently defaults to — both loops stay
    traceable exactly so the sweep is measured, not remembered."""
    import jax
    from stellar_tpu.ops import verify as vk

    bytes32, point = _abstract_inputs(batch)
    recode = {16: vk.signed_digits16_dev,
              32: vk.signed_digits32_dev}[radix]

    def dsm(s_bytes, h_bytes, x, y, z, t):
        from stellar_tpu.ops import edwards as ed
        return ed.double_scalarmult(recode(s_bytes), recode(h_bytes),
                                    (x, y, z, t))

    jx = jax.make_jaxpr(dsm)(bytes32, bytes32, *point)
    out = count_jaxpr(jx)
    out.update(analytic_window_costs(radix))
    return out


def radix_sweep(batch: int = BATCH_DEFAULT) -> dict:
    """The radix-window sweep (PR 13): analytic + traced cost for the
    signed radix-16 arm (PR 1: projective A-tables, one-hot selects)
    vs the signed radix-32 arm (batched-affine tables via fe.batch_inv,
    cmov-tree selects), decided on the EXECUTED MAC ledger. The winner
    is what ``verify.dsm_stage`` runs; the §3 decision record in
    docs/kernel_design.md carries this table."""
    arms = {f"radix{r}": trace_dsm_variant(r, batch) for r in (16, 32)}
    decision = min(arms, key=lambda k: arms[k]["weighted_mul_elems"])
    return {"batch": batch, "arms": arms, "decision": decision,
            "criterion": "min dsm weighted_mul_elems (executed MACs "
                         "per call)"}


def trace_affine_table(batch: int = BATCH_DEFAULT) -> dict:
    """Stage rows for the batched-affine table build: the full
    ``build_point_table_affine`` (ladder + normalization) and the
    ``fe.batch_inv`` chain alone — the rows the perf sentinel pins so
    the Montgomery trick can't silently decay into per-lane
    inversions."""
    import jax
    from stellar_tpu.ops import edwards as ed
    from stellar_tpu.ops import field25519 as fe
    import numpy as np
    _, point = _abstract_inputs(batch)
    build = count_jaxpr(jax.make_jaxpr(
        lambda x, y, z, t: ed.build_point_table_affine(
            (x, y, z, t), ed.TABLE_ENTRIES32))(*point))
    zstack = jax.ShapeDtypeStruct(
        (fe.NLIMBS, ed.TABLE_ENTRIES32, batch), np.int32)
    inv = count_jaxpr(jax.make_jaxpr(fe.batch_inv)(zstack))
    return {
        "entries": ed.TABLE_ENTRIES32,
        "build_static_mul_ops": build["static_mul_ops"],
        "build_weighted_mul_elems": build["weighted_mul_elems"],
        "batch_inv_static_mul_ops": inv["static_mul_ops"],
        "batch_inv_weighted_mul_elems": inv["weighted_mul_elems"],
    }


def trace_stages(batch: int = BATCH_DEFAULT) -> dict:
    """Trace each verify-kernel stage and the whole kernel; return
    per-stage counts plus the analytic select volumes and the nested
    ``dsm``/``affine_table`` consumer rows."""
    import jax
    from stellar_tpu.ops import edwards as ed
    from stellar_tpu.ops import verify as vk

    bytes32, point = _abstract_inputs(batch)
    hot_table = _abstract_hot_table(batch)

    def dsm(s_bytes, h_bytes, x, y, z, t):
        return vk.dsm_stage(s_bytes, h_bytes, (x, y, z, t))

    stages = {
        "decompress": jax.make_jaxpr(ed.decompress)(bytes32),
        "dsm": jax.make_jaxpr(dsm)(bytes32, bytes32, *point),
        "dsm_hot": jax.make_jaxpr(vk.dsm_stage_hot)(
            bytes32, bytes32, hot_table),
        "compress_compare": jax.make_jaxpr(
            lambda x, y, z, t, r: ed.compress_equals((x, y, z, t), r))(
                *point, bytes32),
        "kernel_total": jax.make_jaxpr(vk.verify_kernel)(
            bytes32, bytes32, bytes32, bytes32),
        "kernel_hot_total": jax.make_jaxpr(vk.verify_kernel_hot)(
            hot_table, bytes32, bytes32, bytes32),
    }
    out = {"batch": batch, "ledger_version": LEDGER_VERSION,
           "stages": {}}
    for name, jx in stages.items():
        out["stages"][name] = count_jaxpr(jx)
    landed = analytic_window_costs(32)  # the dsm_stage default
    out["radix"] = landed["radix"]
    out["table_entries"] = landed["table_entries"]
    out["windows"] = landed["windows"]
    out["select_macs_per_verify"] = landed["select_macs"]
    out["select_logic_elems_per_verify"] = landed["select_logic_elems"]
    for k in ("static_mul_ops", "weighted_mul_ops",
              "static_mul_elems", "weighted_mul_elems"):
        out["dsm_" + k] = out["stages"]["dsm"][k]
    out["kernel_static_mul_ops"] = \
        out["stages"]["kernel_total"]["static_mul_ops"]
    # nested consumer rows (bench records / perf sentinel): the
    # executed-MAC headline under its enforced name, plus the
    # affine-table stage rows. Since ledger v3 the headline keys are
    # the COLD (live-build radix-32) arm — the path every first-sight
    # signer still runs — and the hot/cold split is carried explicitly
    # under ``dsm.hot`` / ``dsm.cold``.
    hot = out["stages"]["dsm_hot"]
    cold_macs = out["dsm_weighted_mul_elems"]
    hot_macs = hot["weighted_mul_elems"]
    out["dsm"] = {
        "executed_macs_per_call": cold_macs,
        "executed_mul_ops_per_call": out["dsm_weighted_mul_ops"],
        "static_mul_ops": out["dsm_static_mul_ops"],
        "cold": {
            "executed_macs_per_call": cold_macs,
            "static_mul_ops": out["dsm_static_mul_ops"],
        },
        "hot": {
            "executed_macs_per_call": hot_macs,
            "static_mul_ops": hot["static_mul_ops"],
            # the ISSUE 16 acceptance quantity: executed dsm MACs of a
            # hot (cached-table) call as a fraction of a cold call at
            # the same batch — must stay <= 0.80
            "vs_cold_frac": round(hot_macs / cold_macs, 4),
        },
    }
    out["affine_table"] = trace_affine_table(batch)
    hot_geom = analytic_window_costs(256)
    from stellar_tpu.parallel import signer_tables
    out["signer_table"] = {
        "radix": hot_geom["radix"],
        "windows": hot_geom["windows"],
        "entries": hot_geom["table_entries"],
        "table_dtype": "int16",
        "bytes_per_signer": signer_tables.TABLE_BYTES,
        "doublings": hot_geom["doublings"],
        "cached_adds": hot_geom["cached_adds"],
        "select_logic_elems_per_verify":
            hot_geom["select_logic_elems"],
        "hot_savings_frac": round(1.0 - hot_macs / cold_macs, 4),
    }
    return out


def slim_record(batch: int = BATCH_DEFAULT) -> dict:
    """The ONE consumer shape for bench records and the perf sentinel
    (``--json --workload=record``): verify + sha256 ledgers in a single
    subprocess-friendly JSON line, replacing the two slightly-divergent
    ad-hoc parsers bench.py used to build its slim dict with."""
    rec = trace_stages(batch)
    out = {
        "ledger_version": rec["ledger_version"],
        "batch": rec["batch"],
        "radix": rec["radix"],
        "windows": rec["windows"],
        "table_entries": rec["table_entries"],
        "select_macs_per_verify": rec["select_macs_per_verify"],
        "select_logic_elems_per_verify":
            rec["select_logic_elems_per_verify"],
        "dsm_static_mul_ops": rec["dsm_static_mul_ops"],
        "dsm_weighted_mul_elems": rec["dsm_weighted_mul_elems"],
        "kernel_static_mul_ops":
            rec["stages"]["kernel_total"]["static_mul_ops"],
        "dsm": rec["dsm"],
        "affine_table": rec["affine_table"],
        "signer_table": rec["signer_table"],
    }
    # sha256 failure isolation: workload #2's trace breaking (or being
    # absent) must not cost the record its verify ledger — the sentinel
    # skips missing sha rows but still trends the verify family.
    try:
        sha = trace_sha256(batch)
        out["sha256"] = {
            "static_ops": sha["static_ops"],
            "weighted_ops": sha["weighted_ops"],
            "add_weighted_elems": sha["add_weighted_elems"],
            "max_blocks": sha["max_blocks"],
            "batch": sha["batch"],
        }
    except Exception as e:  # pragma: no cover - defensive
        out["sha256"] = {"error": f"sha256 cost failed: {e!r}"[:200]}
    return out


# Primitives that do the SHA-256 kernel's arithmetic work: the masked
# half-word adds (`add`), the rotate/shift lanes, and the boolean
# mixing (Ch/Maj/sigma xor-and-or). Multiply counts are ~0 for a hash
# kernel, so its scoreboard is add volume + logical volume + program
# size — the quantities the scan-based design keeps flat in max_blocks.
_SHA_ADD_PRIMS = ("add",)
_SHA_LOGIC_PRIMS = ("xor", "and", "or", "shift_right_logical",
                    "shift_left")


def trace_sha256(batch: int = BATCH_DEFAULT,
                 max_blocks: int = None) -> dict:
    """Static cost record for the SHA-256 workload kernel
    (``stellar_tpu.ops.sha256``): program size (static ops) and
    executed volume (scan-weighted) overall, for the masked adds, and
    for the logical mixing — the hash kernel's hardware-independent
    cost trajectory, like the verify kernel's multiply ledger."""
    import jax
    import numpy as np
    from stellar_tpu.ops import sha256 as sk
    if max_blocks is None:
        from stellar_tpu.crypto.batch_hasher import MAX_BLOCKS
        max_blocks = MAX_BLOCKS
    words = jax.ShapeDtypeStruct((batch, max_blocks, 16), np.uint32)
    active = jax.ShapeDtypeStruct((batch, max_blocks), np.bool_)
    jx = jax.make_jaxpr(sk.sha256_kernel)(words, active)
    total = count_prims(jx, None)
    adds = count_prims(jx, _SHA_ADD_PRIMS)
    logic = count_prims(jx, _SHA_LOGIC_PRIMS)
    return {
        "workload": "sha256",
        "batch": batch,
        "max_blocks": int(max_blocks),
        "rounds": 64,
        "static_ops": total["static_ops"],
        "weighted_ops": total["weighted_ops"],
        "weighted_elems": total["weighted_elems"],
        "add_static_ops": adds["static_ops"],
        "add_weighted_ops": adds["weighted_ops"],
        "add_weighted_elems": adds["weighted_elems"],
        "logic_static_ops": logic["static_ops"],
        "logic_weighted_ops": logic["weighted_ops"],
        "logic_weighted_elems": logic["weighted_elems"],
        "has_unbounded_loop": total["has_unbounded_loop"],
    }


def main(argv):
    as_json = "--json" in argv
    batch = BATCH_DEFAULT
    workload = "verify"
    for a in argv:
        if a.startswith("--batch="):
            batch = int(a.split("=", 1)[1])
        if a.startswith("--workload="):
            workload = a.split("=", 1)[1]
    force_cpu()
    if "--sweep" in argv:
        rec = radix_sweep(batch)
    elif workload == "sha256":
        rec = trace_sha256(batch)
    elif workload == "record":
        rec = slim_record(batch)
    elif workload == "all":
        rec = {"verify": trace_stages(batch), "sha256": trace_sha256(batch)}
    else:
        rec = trace_stages(batch)
    if as_json:
        print(json.dumps(rec))
    else:
        print(json.dumps(rec, indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
