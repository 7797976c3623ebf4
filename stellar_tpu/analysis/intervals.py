"""Interval-domain abstract interpretation over jaxprs.

The verify kernel's correctness rests on a range claim: with 13-bit limbs
bounded by ``LOOSE_MAX``, every schoolbook-product coefficient stays below
2^31 (``ops/field25519.py``). That claim was informal — a comment plus an
empirical spot check — and every kernel rework (signed windows in PR 1,
the batched-affine tables + Montgomery inversion chain + strength-reduced
carry fold in PR 13) re-perturbs exactly the limb magnitudes it covers.
This module makes it machine-checked, in the spirit of "Efficient
Verification of Optimized Code: Correct High-speed X25519" (PAPERS.md):
abstract-interpret the traced jaxpr with per-element ``[lo, hi]``
intervals in exact integer arithmetic and flag every equation whose
output interval escapes its dtype.

Design notes:

* **Exact integer intervals, saturated at 2^61.** All bounds are int64;
  products/shifts/sums are float64-guarded and saturate at ``SAT`` rather
  than wrap, so an already-overflowed bound can never launder itself back
  into range through int64 wraparound. Saturation only ever *keeps* a
  bound out of dtype range, and every equation is checked at its own
  site, so a violation is reported where it happens even though
  downstream bounds are then clamped (wrap semantics: a wrapped int32 can
  be anything in int32 range — that IS the clamp).
* **Batch-collapsed storage.** Verify batches are data-parallel: bounds
  are uniform along the batch axis, so abstract arrays store size-1 dims
  wherever the interval is uniform (numpy broadcasting does the rest).
  Analysis cost is near batch-size-independent — the 16384 bucket costs
  what the 128 bucket costs — while the limb axis keeps full per-limb
  resolution (the whole point: limb 0 carries the 608x fold, limb 19 the
  top digit).
* **Loops.** ``scan`` (every ``fori_loop`` in the kernel lowers to it)
  is UNROLLED EXACTLY when its static trip count is at most
  ``max_unroll`` (256; every kernel loop is <= 100) — per-iteration
  bounds, no over-approximation, made cheap by the incremental body
  evaluator (see :class:`_IncrementalBody`). Longer scans fall back to
  a join fixed point with threshold widening on the carry, whose final
  recorded pass checks every body equation under the (dtype-clamped)
  invariant — sound but possibly imprecise, and loud if the
  imprecision reaches a violation. A join fixed point can never close
  over an incrementing loop counter (``f([0,n]) = [1,n+1]``), which is
  exactly why bounded unrolling is the primary strategy.
* **Loud by construction.** Any primitive, padding mode, or scatter shape
  outside the verified subset raises :class:`Unsupported` — the prover
  refuses to claim a proof over code it did not model.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "AbsVal", "Violation", "Unsupported", "IntervalInterpreter",
    "interval_for_dtype", "SAT",
]

# Saturation bound for abstract values: far above any dtype the kernel
# uses, far below int64 wraparound even after one addition.
SAT = np.int64(1) << np.int64(61)


class Unsupported(Exception):
    """The jaxpr uses a primitive/feature outside the verified subset."""


@dataclasses.dataclass
class Violation:
    """One equation whose output interval escapes its dtype."""
    path: str          # nesting path, e.g. "dsm/pjit:mul/scan@41"
    eqn_index: int     # equation index within that (sub)jaxpr
    primitive: str
    dtype: str
    lo: int
    hi: int
    dtype_min: int
    dtype_max: int
    where: str         # user source location from jax source_info

    def describe(self) -> str:
        return (f"{self.path}[{self.eqn_index}] {self.primitive} -> "
                f"[{self.lo}, {self.hi}] escapes {self.dtype} "
                f"[{self.dtype_min}, {self.dtype_max}] at {self.where}")

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def interval_for_dtype(dtype) -> Tuple[int, int]:
    dtype = np.dtype(dtype)
    if dtype == np.bool_:
        return 0, 1
    if dtype.kind in "iu":
        info = np.iinfo(dtype)
        return int(info.min), int(info.max)
    raise Unsupported(f"non-integer dtype {dtype} in checked jaxpr")


def _clamp(a: np.ndarray) -> np.ndarray:
    return np.clip(a, -SAT, SAT)


def _safe_mul(x: np.ndarray, y: np.ndarray) -> np.ndarray:
    """Exact int64 product, saturated where float64 says it would leave
    [-SAT, SAT] (float magnitude error is negligible at the 2^61 scale)."""
    fx = x.astype(np.float64) * y.astype(np.float64)
    big = np.abs(fx) >= float(SAT)
    with np.errstate(over="ignore"):
        exact = x * y
    if not big.any():
        return exact
    return np.where(big, np.where(fx > 0, SAT, -SAT), exact)


def _safe_sum(a: np.ndarray, axis: int) -> np.ndarray:
    f = a.astype(np.float64).sum(axis=axis)
    big = np.abs(f) >= float(SAT)
    exact = a.sum(axis=axis)
    if not big.any():
        return exact
    return np.where(big, np.where(f > 0, SAT, -SAT), exact)


class AbsVal:
    """Interval abstraction of one traced array.

    ``lo``/``hi`` are int64 arrays broadcast-compatible with the concrete
    ``shape``: any dim may be stored with size 1 when the bound is
    uniform along it (the batch axis always is).

    ``excl`` is a relational refinement: the set of axes along which AT
    MOST ONE element is nonzero (for every fixed index of the other
    axes). It is born at ``eq(pairwise-distinct constant, uniform)`` —
    the one-hot idiom — survives convert/broadcast/reshape/multiply, and
    is consumed by ``reduce_sum``, which then takes the union bound
    instead of the sum. Without it, the kernel's 8-entry window selects
    would inflate 8x and falsely 'overflow' the downstream multiplies.

    ``vuni`` is the companion refinement that makes ``excl``'s birth
    sound: the set of axes along which the runtime VALUE is provably
    the same at every position. Only a broadcast (size-1 -> N), a
    size-1 concrete extent, or a uniform constant establishes it —
    uniform *bounds* (stored-size-1) never do, because a traced input
    can vary within uniform bounds."""

    __slots__ = ("lo", "hi", "shape", "dtype", "excl", "vuni")

    def __init__(self, lo, hi, shape, dtype, excl=frozenset(),
                 vuni=frozenset()):
        self.lo = np.asarray(lo, dtype=np.int64)
        self.hi = np.asarray(hi, dtype=np.int64)
        self.shape = tuple(int(s) for s in shape)
        self.dtype = np.dtype(dtype)
        self.excl = frozenset(excl)
        self.vuni = frozenset(vuni)
        if self.lo.shape != self.hi.shape:
            raise AssertionError("lo/hi shape mismatch")
        if self.lo.ndim != len(self.shape):
            # scalars may arrive rank-0 against a rank-0 aval only
            raise AssertionError(
                f"stored rank {self.lo.ndim} vs concrete {self.shape}")
        for stored, concrete in zip(self.lo.shape, self.shape):
            if stored not in (1, concrete):
                raise AssertionError(
                    f"stored {self.lo.shape} vs concrete {self.shape}")

    # ---------------- constructors ----------------

    @classmethod
    def from_concrete(cls, value) -> "AbsVal":
        a = np.asarray(value)
        if a.dtype.kind in "biu":
            ai = a.astype(np.int64)
        else:
            raise Unsupported(f"non-integer constant dtype {a.dtype}")
        out = cls(ai, ai, a.shape, a.dtype).collapsed()
        # a constant's collapsed axes really are value-uniform (we know
        # the exact values — lo == hi)
        out.vuni = frozenset(ax for ax in range(out.lo.ndim)
                             if out.lo.shape[ax] == 1)
        return out

    @classmethod
    def from_range(cls, aval, lo: int, hi: int) -> "AbsVal":
        shape = tuple(aval.shape)
        one = (1,) * len(shape)
        # only size-1 concrete extents are value-uniform: a traced
        # input varies freely within its (uniform) bounds
        return cls(np.full(one, lo, np.int64), np.full(one, hi, np.int64),
                   shape, aval.dtype,
                   vuni=frozenset(ax for ax, s in enumerate(shape)
                                  if s == 1))

    @classmethod
    def top(cls, aval) -> "AbsVal":
        lo, hi = interval_for_dtype(aval.dtype)
        return cls.from_range(aval, lo, hi)

    # ---------------- views ----------------

    def materialize(self, axes: Sequence[int]) -> Tuple[np.ndarray, np.ndarray]:
        """lo/hi broadcast to concrete size along the given axes."""
        tgt = list(self.lo.shape)
        for ax in axes:
            tgt[ax] = self.shape[ax]
        return (np.broadcast_to(self.lo, tgt), np.broadcast_to(self.hi, tgt))

    def full(self) -> Tuple[np.ndarray, np.ndarray]:
        return (np.broadcast_to(self.lo, self.shape),
                np.broadcast_to(self.hi, self.shape))

    def collapsed(self) -> "AbsVal":
        """Shrink every axis along which both bounds are uniform to 1."""
        lo, hi = self.lo, self.hi
        for ax in range(lo.ndim):
            if lo.shape[ax] > 1:
                l0 = np.take(lo, [0], axis=ax)
                h0 = np.take(hi, [0], axis=ax)
                if (lo == l0).all() and (hi == h0).all():
                    lo, hi = l0, h0
        return AbsVal(lo, hi, self.shape, self.dtype, self.excl,
                      self.vuni)

    def max_abs(self) -> int:
        if self.lo.size == 0:
            return 0
        return int(max(abs(int(self.lo.min())), abs(int(self.hi.max()))))

    def join(self, other: "AbsVal") -> "AbsVal":
        lo = np.minimum(self.lo, other.lo)
        hi = np.maximum(self.hi, other.hi)
        # a property must hold on both sides to hold on the union
        return AbsVal(lo, hi, self.shape, self.dtype,
                      self.excl & other.excl, self.vuni & other.vuni)

    def contains(self, other: "AbsVal") -> bool:
        return bool((self.lo <= other.lo).all() and
                    (other.hi <= self.hi).all())

    def equals(self, other: "AbsVal") -> bool:
        return bool(np.array_equal(self.lo, other.lo) and
                    np.array_equal(self.hi, other.hi))

    def same(self, other: "AbsVal") -> bool:
        """Full abstract-state equality (bounds + refinements) — the
        reuse criterion for incremental evaluation."""
        return (self.equals(other) and self.excl == other.excl and
                self.vuni == other.vuni)

    def __repr__(self):
        return (f"AbsVal([{int(self.lo.min()) if self.lo.size else 0}, "
                f"{int(self.hi.max()) if self.hi.size else 0}] "
                f"{self.dtype}{self.shape})")


def _binop_arrays(a: AbsVal, b: AbsVal):
    """Broadcast-aligned stored arrays for an elementwise binary op."""
    nd = max(a.lo.ndim, b.lo.ndim)

    def lift(x):
        return x.reshape((1,) * (nd - x.ndim) + x.shape)
    return (lift(a.lo), lift(a.hi), lift(b.lo), lift(b.hi))


def _bitmask_bound(hi: np.ndarray) -> np.ndarray:
    """Smallest all-ones mask covering ``hi`` (elementwise, hi >= 0):
    7 -> 7, 8 -> 15, 2^32-1 -> 2^32-1. The sound upper bound for
    OR/XOR of non-negative values — neither can set a bit above the
    highest bit of either operand."""
    h = np.maximum(np.asarray(hi, dtype=np.int64), 0)
    out = np.zeros_like(h)
    nz = h > 0
    if nz.any():
        bits = np.ceil(np.log2(h[nz].astype(np.float64) + 1.0))
        cand = (np.int64(1) << bits.astype(np.int64)) - 1
        # float rounding safety: the mask must COVER hi
        short = cand < h[nz]
        cand = np.where(short, (cand << 1) | 1, cand)
        out[nz] = cand
    return _clamp(out)


def _corner_minmax(fn, alo, ahi, blo, bhi):
    c1, c2, c3, c4 = fn(alo, blo), fn(alo, bhi), fn(ahi, blo), fn(ahi, bhi)
    lo = np.minimum(np.minimum(c1, c2), np.minimum(c3, c4))
    hi = np.maximum(np.maximum(c1, c2), np.maximum(c3, c4))
    return lo, hi


def _source_of(eqn) -> str:
    try:
        from jax._src import source_info_util
        frame = source_info_util.user_frame(eqn.source_info)
        if frame is not None:
            return f"{frame.file_name}:{frame.start_line}"
        return source_info_util.summarize(eqn.source_info)
    except Exception:
        return "<unknown>"


# Widening ladder: 0, +-powers of two, +-SAT. Domain-specific thresholds
# (MASK, LOOSE_MAX, fold bounds) are appended by the caller via `hints`.
_BASE_LADDER = [0] + [1 << k for k in range(0, 62)]


class IntervalInterpreter:
    """Abstract interpreter: run with :meth:`eval_closed`, inspect
    ``violations``/``max_abs`` afterwards.

    Args:
      ladder_hints: extra widening thresholds (e.g. the limb layout's
        MASK/LOOSE_MAX) for the long-scan fixed-point fallback, so
        widened invariants land on the bounds the design intends
        instead of the next power of two.
      max_unroll: trip-count ceiling for exact scan unrolling; longer
        scans use the widened fixed point.
    """

    def __init__(self, ladder_hints: Sequence[int] = (),
                 widen_after: int = 8, max_fp_iters: int = 400,
                 max_unroll: int = 256):
        pos = sorted(set(_BASE_LADDER) |
                     {abs(int(h)) for h in ladder_hints} | {int(SAT)})
        self._ladder = np.array(
            sorted({-v for v in pos} | set(pos)), dtype=np.int64)
        self._widen_after = widen_after
        self._max_fp_iters = max_fp_iters
        self._max_unroll = max_unroll
        self.violations: List[Violation] = []
        self.max_abs: int = 0
        self._recording = True
        self._seen_sites: set = set()
        self._handlers: Dict[str, Callable] = self._build_handlers()

    # ---------------- public API ----------------

    def eval_closed(self, closed_jaxpr, invals: Sequence[AbsVal],
                    path: str = "jaxpr") -> List[AbsVal]:
        from jax.extend import core
        jaxpr = closed_jaxpr.jaxpr if isinstance(
            closed_jaxpr, core.ClosedJaxpr) else closed_jaxpr
        consts = closed_jaxpr.consts if isinstance(
            closed_jaxpr, core.ClosedJaxpr) else []
        return self._eval(jaxpr, consts, list(invals), path)

    # ---------------- core loop ----------------

    def _eval(self, jaxpr, consts, invals, path) -> List[AbsVal]:
        from jax.core import DropVar
        from jax.extend import core
        env: Dict = {}
        for var, c in zip(jaxpr.constvars, consts):
            env[var] = AbsVal.from_concrete(np.asarray(c))
        if len(jaxpr.invars) != len(invals):
            raise Unsupported(
                f"{path}: arity mismatch {len(jaxpr.invars)} vs "
                f"{len(invals)}")
        for var, v in zip(jaxpr.invars, invals):
            env[var] = v

        def read(v):
            if isinstance(v, core.Literal):
                return AbsVal.from_concrete(np.asarray(v.val))
            return env[v]

        for idx, eqn in enumerate(jaxpr.eqns):
            ins = [read(v) for v in eqn.invars]
            outs = self.run_eqn(eqn, ins, path, idx)
            for var, out in zip(eqn.outvars, outs):
                if not isinstance(var, DropVar):
                    env[var] = out
        return [read(v) for v in jaxpr.outvars]

    def _check(self, eqn, out: AbsVal, aval, path, idx) -> AbsVal:
        dlo, dhi = interval_for_dtype(aval.dtype)
        vlo = int(out.lo.min()) if out.lo.size else 0
        vhi = int(out.hi.max()) if out.hi.size else 0
        if vlo < dlo or vhi > dhi:
            if self._recording:
                site = (path, idx)
                if site not in self._seen_sites:
                    self._seen_sites.add(site)
                    self.violations.append(Violation(
                        path=path, eqn_index=idx,
                        primitive=eqn.primitive.name,
                        dtype=str(np.dtype(aval.dtype)), lo=vlo, hi=vhi,
                        dtype_min=dlo, dtype_max=dhi,
                        where=_source_of(eqn)))
            # wrap semantics: a wrapped value can be anything in range
            # (zero wraps to zero and equal values wrap equally, so
            # both refinements survive the clamp)
            out = AbsVal(np.clip(out.lo, dlo, dhi),
                         np.clip(out.hi, dlo, dhi), out.shape, out.dtype,
                         out.excl, out.vuni)
        if self._recording:
            self.max_abs = max(self.max_abs, out.max_abs())
        return out

    # ---------------- handlers ----------------

    def _build_handlers(self) -> Dict[str, Callable]:
        h = {
            "add": self._h_add, "add_any": self._h_add,
            "sub": self._h_sub, "mul": self._h_mul,
            "neg": self._h_neg, "abs": self._h_abs,
            "sign": self._h_sign,
            "max": self._h_max, "min": self._h_min,
            "and": self._h_and, "or": self._h_or, "not": self._h_not,
            "xor": self._h_xor,
            "shift_left": self._h_shift_left,
            "shift_right_arithmetic": self._h_shift_right_arith,
            "shift_right_logical": self._h_shift_right_logical,
            "eq": self._h_cmp, "ne": self._h_cmp, "lt": self._h_cmp,
            "le": self._h_cmp, "gt": self._h_cmp, "ge": self._h_cmp,
            "select_n": self._h_select_n,
            "convert_element_type": self._h_convert,
            "broadcast_in_dim": self._h_broadcast_in_dim,
            "reshape": self._h_reshape, "squeeze": self._h_squeeze,
            "transpose": self._h_transpose, "rev": self._h_rev,
            "concatenate": self._h_concatenate, "pad": self._h_pad,
            "tile": self._h_tile,
            "slice": self._h_slice, "dynamic_slice": self._h_dynamic_slice,
            "iota": self._h_iota,
            "reduce_sum": self._h_reduce_sum,
            "reduce_max": self._h_reduce_max,
            "reduce_min": self._h_reduce_min,
            "reduce_and": self._h_reduce_and,
            "reduce_or": self._h_reduce_or,
            "scatter-add": self._h_scatter_add,
            "dot_general": self._h_dot_general,
            "device_put": self._h_identity, "copy": self._h_identity,
            "stop_gradient": self._h_identity,
            "pjit": self._h_pjit, "jit": self._h_pjit,
            "closed_call": self._h_pjit,
            "scan": self._h_scan,
        }
        return h

    # --- elementwise arithmetic ---

    def _out(self, eqn, lo, hi) -> AbsVal:
        aval = eqn.outvars[0].aval
        return AbsVal(_clamp(lo), _clamp(hi), aval.shape, aval.dtype)

    def _h_add(self, eqn, ins, path, idx):
        a, b = ins
        alo, ahi, blo, bhi = _binop_arrays(a, b)
        return self._out(eqn, alo + blo, ahi + bhi)

    def _h_sub(self, eqn, ins, path, idx):
        a, b = ins
        alo, ahi, blo, bhi = _binop_arrays(a, b)
        return self._out(eqn, alo - bhi, ahi - blo)

    def _h_mul(self, eqn, ins, path, idx):
        a, b = ins
        alo, ahi, blo, bhi = _binop_arrays(a, b)
        lo, hi = _corner_minmax(_safe_mul, alo, ahi, blo, bhi)
        out = self._out(eqn, lo, hi)
        # a product is nonzero only where BOTH factors are: exclusivity
        # along an axis survives from either factor
        nd = out.lo.ndim
        out.excl = frozenset(
            {ax + (nd - a.lo.ndim) for ax in a.excl} |
            {ax + (nd - b.lo.ndim) for ax in b.excl})
        return out

    def _h_neg(self, eqn, ins, path, idx):
        a = ins[0]
        return self._out(eqn, -a.hi, -a.lo)

    def _h_abs(self, eqn, ins, path, idx):
        a = ins[0]
        lo = np.where((a.lo <= 0) & (a.hi >= 0), 0,
                      np.minimum(np.abs(a.lo), np.abs(a.hi)))
        hi = np.maximum(np.abs(a.lo), np.abs(a.hi))
        return self._out(eqn, lo, hi)

    def _h_sign(self, eqn, ins, path, idx):
        a = ins[0]  # sign is monotone: corner bounds are exact
        return self._out(eqn, np.sign(a.lo), np.sign(a.hi))

    def _h_max(self, eqn, ins, path, idx):
        a, b = ins
        alo, ahi, blo, bhi = _binop_arrays(a, b)
        return self._out(eqn, np.maximum(alo, blo), np.maximum(ahi, bhi))

    def _h_min(self, eqn, ins, path, idx):
        a, b = ins
        alo, ahi, blo, bhi = _binop_arrays(a, b)
        return self._out(eqn, np.minimum(alo, blo), np.minimum(ahi, bhi))

    # --- bitwise ---

    def _h_and(self, eqn, ins, path, idx):
        a, b = ins
        alo, ahi, blo, bhi = _binop_arrays(a, b)
        if np.dtype(eqn.outvars[0].aval.dtype) == np.bool_:
            return self._out(eqn, np.minimum(alo, blo),
                             np.minimum(ahi, bhi))
        a_nn, b_nn = alo >= 0, blo >= 0
        hi = np.where(a_nn & b_nn, np.minimum(ahi, bhi),
                      np.where(a_nn, ahi,
                               np.where(b_nn, bhi,
                                        np.maximum(ahi, bhi))))
        lo = np.where(a_nn | b_nn, np.zeros_like(alo),
                      np.full_like(alo, -SAT))
        # exact when one side is a known submask-preserving range
        return self._out(eqn, lo, hi)

    def _h_or(self, eqn, ins, path, idx):
        a, b = ins
        alo, ahi, blo, bhi = _binop_arrays(a, b)
        if np.dtype(eqn.outvars[0].aval.dtype) == np.bool_:
            return self._out(eqn, np.maximum(alo, blo),
                             np.maximum(ahi, bhi))
        both_nn = (alo >= 0) & (blo >= 0)
        # x|y >= min(x, y) in all sign cases (setting bits moves a
        # negative toward -1); >= max(x, y) when both non-negative.
        lo = np.where(both_nn, np.maximum(alo, blo),
                      np.minimum(alo, blo))
        # x|y <= x + y for non-negative x, y; a possibly-negative
        # operand contributes 0 to the upper bound (result <= other|0).
        # Refinement (SHA-256 kernel): OR cannot set a bit above the
        # highest bit of either operand, so for non-negative operands
        # min in the power-of-two ceiling of max(ahi, bhi) — without
        # it, uint32 full-range ORs would falsely escape uint32.
        hi = _clamp(np.maximum(ahi, 0) + np.maximum(bhi, 0))
        hi = np.where(both_nn,
                      np.minimum(hi, _bitmask_bound(
                          np.maximum(ahi, bhi))), hi)
        return self._out(eqn, lo, hi)

    def _h_xor(self, eqn, ins, path, idx):
        a, b = ins
        alo, ahi, blo, bhi = _binop_arrays(a, b)
        if np.dtype(eqn.outvars[0].aval.dtype) == np.bool_:
            lo = np.where((alo == ahi) & (blo == bhi),
                          np.abs(alo - blo), np.zeros_like(alo))
            hi = np.where((alo == ahi) & (blo == bhi),
                          np.abs(alo - blo), np.ones_like(ahi))
            return self._out(eqn, lo, hi)
        both_nn = (alo >= 0) & (blo >= 0)
        lo = np.where(both_nn, np.zeros_like(alo), np.full_like(alo, -SAT))
        # same bit-ceiling refinement as OR: XOR of non-negative
        # operands never sets a bit above either operand's highest —
        # the bound that keeps the SHA-256 schedule/round XORs inside
        # uint32 instead of the (sound but useless) ahi + bhi.
        hi = np.where(both_nn,
                      np.minimum(_clamp(ahi + bhi),
                                 _bitmask_bound(np.maximum(ahi, bhi))),
                      np.full_like(ahi, SAT))
        return self._out(eqn, lo, hi)

    def _h_not(self, eqn, ins, path, idx):
        a = ins[0]
        dtype = np.dtype(eqn.outvars[0].aval.dtype)
        if dtype == np.bool_:
            return self._out(eqn, 1 - a.hi, 1 - a.lo)
        if dtype.kind == "u":
            # unsigned bitwise-not is dtype_max - x, not -1 - x
            _dlo, dhi = interval_for_dtype(dtype)
            return self._out(eqn, dhi - a.hi, dhi - a.lo)
        return self._out(eqn, -1 - a.hi, -1 - a.lo)

    def _h_shift_left(self, eqn, ins, path, idx):
        a, s = ins
        alo, ahi, slo, shi = _binop_arrays(a, s)
        slo = np.clip(slo, 0, 62)
        shi = np.clip(shi, 0, 62)

        def shl(x, k):
            return _safe_mul(x, np.int64(1) << k)
        lo, hi = _corner_minmax(shl, alo, ahi, slo, shi)
        return self._out(eqn, lo, hi)

    def _h_shift_right_arith(self, eqn, ins, path, idx):
        a, s = ins
        alo, ahi, slo, shi = _binop_arrays(a, s)
        slo = np.clip(slo, 0, 63)
        shi = np.clip(shi, 0, 63)
        lo, hi = _corner_minmax(np.right_shift, alo, ahi, slo, shi)
        return self._out(eqn, lo, hi)

    def _h_shift_right_logical(self, eqn, ins, path, idx):
        a, s = ins
        if int(a.lo.min()) < 0:
            # logical shift reinterprets the sign bit: bound by dtype
            dlo, dhi = interval_for_dtype(eqn.outvars[0].aval.dtype)
            return AbsVal.from_range(eqn.outvars[0].aval, 0, dhi)
        return self._h_shift_right_arith(eqn, ins, path, idx)

    # --- comparisons ---

    def _h_cmp(self, eqn, ins, path, idx):
        a, b = ins
        alo, ahi, blo, bhi = _binop_arrays(a, b)
        name = eqn.primitive.name
        if name in ("lt", "ge"):
            surely = ahi < blo          # a < b always
            never = alo >= bhi          # a < b never
            if name == "ge":
                surely, never = never, surely
        elif name in ("le", "gt"):
            surely = ahi <= blo
            never = alo > bhi
            if name == "gt":
                surely, never = never, surely
        elif name == "eq":
            surely = (alo == ahi) & (blo == bhi) & (alo == blo)
            never = (ahi < blo) | (bhi < alo)
        else:  # ne
            never = (alo == ahi) & (blo == bhi) & (alo == blo)
            surely = (ahi < blo) | (bhi < alo)
        lo = np.where(surely, 1, 0)
        hi = np.where(never, 0, 1)
        out = self._out(eqn, lo, hi)
        if name == "eq":
            out.excl = self._onehot_axes(a, b, out)
        return out

    @staticmethod
    def _onehot_axes(a: AbsVal, b: AbsVal, out: AbsVal) -> frozenset:
        """Axes along which eq(a, b) is one-hot: one side is a constant
        with pairwise-distinct values varying ONLY along that axis, the
        other side broadcast along it — the `iota == digit[None]`
        window-select idiom. At most one position can compare equal.

        Soundness hinges on the *concrete* (aval) size of the other
        side being 1 along the axis: broadcasting then guarantees the
        SAME runtime value at every position, so distinct constants can
        match at most once. Stored-size-1 would NOT be enough — that
        only means uniform *bounds*, and a value-varying operand (e.g.
        a traced (8,) input) could match every position."""
        axes = set()
        nd = out.lo.ndim
        for x, y in ((a, b), (b, a)):
            if not np.array_equal(x.lo, x.hi):
                continue  # not a constant
            for ax in range(x.lo.ndim):
                oax = ax + (nd - x.lo.ndim)
                if x.lo.shape[ax] != x.shape[ax] or x.shape[ax] <= 1:
                    continue
                if any(x.lo.shape[d] != 1
                       for d in range(x.lo.ndim) if d != ax):
                    continue  # constant varies along more than one axis
                if np.unique(x.lo).size != x.lo.size:
                    continue  # values not pairwise distinct
                yax = oax - (nd - y.lo.ndim)
                if yax >= 0 and y.shape[yax] != 1 and \
                        yax not in y.vuni:
                    continue  # the other side must carry the SAME
                    # runtime value at every position along the axis:
                    # size-1 concrete extent or a tracked broadcast
                    # (vuni) — uniform bounds alone are not enough
                axes.add(oax)
        return frozenset(axes)

    def _h_select_n(self, eqn, ins, path, idx):
        pred, *cases = ins
        plo, phi = pred.lo, pred.hi
        nd = max([c.lo.ndim for c in cases] + [plo.ndim])

        def lift(x):
            return x.reshape((1,) * (nd - x.ndim) + x.shape)
        plo, phi = lift(plo), lift(phi)
        lo = hi = None
        for k, c in enumerate(cases):
            clo, chi = lift(c.lo), lift(c.hi)
            selectable = (plo <= k) & (k <= phi)
            k_lo = np.where(selectable, clo, SAT)
            k_hi = np.where(selectable, chi, -SAT)
            lo = k_lo if lo is None else np.minimum(lo, k_lo)
            hi = k_hi if hi is None else np.maximum(hi, k_hi)
        return self._out(eqn, lo, hi)

    def _h_convert(self, eqn, ins, path, idx):
        a = ins[0]
        new = np.dtype(eqn.params["new_dtype"])
        if new == np.bool_:
            nonzero_sure = (a.lo > 0) | (a.hi < 0)
            zero_sure = (a.lo == 0) & (a.hi == 0)
            lo = np.where(nonzero_sure, 1, 0)
            hi = np.where(zero_sure, 0, 1)
            return AbsVal(lo, hi, eqn.outvars[0].aval.shape, new,
                          a.excl, a.vuni)
        if new.kind not in "iu":
            raise Unsupported(
                f"{path}[{idx}]: convert to {new} at {_source_of(eqn)}")
        if a.dtype == np.bool_ or a.dtype.kind in "iu":
            # zero converts to zero and equal values convert equally:
            # both refinements survive
            return AbsVal(a.lo, a.hi, eqn.outvars[0].aval.shape, new,
                          a.excl, a.vuni)
        raise Unsupported(f"{path}[{idx}]: convert from {a.dtype}")

    # --- structural ---

    def _h_identity(self, eqn, ins, path, idx):
        a = ins[0]
        aval = eqn.outvars[0].aval
        return AbsVal(a.lo, a.hi, aval.shape, aval.dtype)

    def _h_broadcast_in_dim(self, eqn, ins, path, idx):
        a = ins[0]
        aval = eqn.outvars[0].aval
        bdims = tuple(eqn.params["broadcast_dimensions"])
        if bdims != tuple(sorted(bdims)):
            raise Unsupported(f"{path}[{idx}]: permuted broadcast_in_dim")
        # source dim i lands at output dim bdims[i]; new and broadcast
        # (1 -> N) dims stay stored-1 (uniform by construction)
        tgt = [1] * len(aval.shape)
        for i, d in enumerate(bdims):
            tgt[d] = a.lo.shape[i]
        excl = frozenset(bdims[ax] for ax in a.excl)
        # value-uniform: new axes and size-1 -> N expansions replicate
        # ONE value by construction; mapped axes keep their tag
        vuni = set(range(len(aval.shape))) - set(bdims)
        for i, d in enumerate(bdims):
            if i in a.vuni or a.shape[i] == 1:
                vuni.add(d)
        return AbsVal(a.lo.reshape(tgt), a.hi.reshape(tgt),
                      aval.shape, aval.dtype, excl, frozenset(vuni))

    def _h_squeeze(self, eqn, ins, path, idx):
        a = ins[0]
        dims = eqn.params["dimensions"]
        lo = np.squeeze(a.lo, axis=tuple(dims))
        hi = np.squeeze(a.hi, axis=tuple(dims))
        aval = eqn.outvars[0].aval
        def remap(axes):
            return frozenset(ax - sum(1 for d in dims if d < ax)
                             for ax in axes if ax not in dims)
        return AbsVal(lo, hi, aval.shape, aval.dtype, remap(a.excl),
                      remap(a.vuni))

    def _h_transpose(self, eqn, ins, path, idx):
        a = ins[0]
        perm = eqn.params["permutation"]
        aval = eqn.outvars[0].aval
        def remap(axes):
            return frozenset(perm.index(ax) for ax in axes)
        return AbsVal(np.transpose(a.lo, perm), np.transpose(a.hi, perm),
                      aval.shape, aval.dtype, remap(a.excl),
                      remap(a.vuni))

    def _h_rev(self, eqn, ins, path, idx):
        a = ins[0]
        dims = [d for d in eqn.params["dimensions"]
                if a.lo.shape[d] > 1]
        lo, hi = a.lo, a.hi
        if dims:
            lo = np.flip(lo, axis=tuple(dims))
            hi = np.flip(hi, axis=tuple(dims))
        aval = eqn.outvars[0].aval
        return AbsVal(lo, hi, aval.shape, aval.dtype)

    def _h_reshape(self, eqn, ins, path, idx):
        a = ins[0]
        aval = eqn.outvars[0].aval
        if eqn.params.get("dimensions") is not None:
            raise Unsupported(f"{path}[{idx}]: reshape with dimensions")
        new_shape = tuple(aval.shape)
        # greedy group factoring: match products of old dims to new dims
        groups = self._reshape_groups(a.shape, new_shape)
        out_stored: Optional[List[int]] = [] if groups is not None else None
        excl, vuni = set(), set()
        if groups is not None:
            for in_dims, out_dims in groups:
                stored = [a.lo.shape[d] for d in in_dims]
                concrete = [a.shape[d] for d in in_dims]
                if len(in_dims) == 1 and len(out_dims) == 1:
                    if in_dims[0] in a.excl:
                        excl.add(out_dims[0])
                    if in_dims[0] in a.vuni:
                        vuni.add(out_dims[0])
                elif not in_dims:
                    vuni.update(out_dims)  # inserted size-1 axes
                if stored == concrete:
                    # fully materialized group: reshape carries through
                    out_stored.extend(new_shape[d] for d in out_dims)
                elif all(s == 1 for s in stored):
                    # fully collapsed group stays collapsed
                    out_stored.extend(1 for _ in out_dims)
                else:
                    out_stored = None  # mixed group: fall back
                    break
        if out_stored is None:
            lo, hi = a.full()
            out = AbsVal(lo.reshape(new_shape), hi.reshape(new_shape),
                         new_shape, aval.dtype)
            return out.collapsed()
        return AbsVal(a.lo.reshape(out_stored), a.hi.reshape(out_stored),
                      new_shape, aval.dtype, frozenset(excl),
                      frozenset(vuni))

    @staticmethod
    def _reshape_groups(old: Tuple[int, ...], new: Tuple[int, ...]):
        """Factor a reshape into (old_dims, new_dims) groups with equal
        products, or None if the greedy factorization fails."""
        groups = []
        i = j = 0
        while i < len(old) or j < len(new):
            gi, gj = [i], [j]
            if i >= len(old) or j >= len(new):
                # trailing 1s
                while i < len(old):
                    if old[i] != 1:
                        return None
                    groups.append(([i], []))
                    i += 1
                while j < len(new):
                    if new[j] != 1:
                        return None
                    groups.append(([], [j]))
                    j += 1
                break
            pi, pj = old[i], new[j]
            i += 1
            j += 1
            while pi != pj:
                if pi < pj:
                    if i >= len(old):
                        return None
                    pi *= old[i]
                    gi.append(i)
                    i += 1
                else:
                    if j >= len(new):
                        return None
                    pj *= new[j]
                    gj.append(j)
                    j += 1
            groups.append((gi, gj))
        return groups

    def _h_concatenate(self, eqn, ins, path, idx):
        dim = eqn.params["dimension"]
        aval = eqn.outvars[0].aval
        nd = len(aval.shape)
        # materialize the concat axis; broadcast others to a common shape
        los, his = [], []
        common = [1] * nd
        for a in ins:
            for d in range(nd):
                if d != dim:
                    common[d] = max(common[d], a.lo.shape[d])
        for a in ins:
            lo, hi = a.materialize([dim])
            tgt = list(common)
            tgt[dim] = lo.shape[dim]
            los.append(np.broadcast_to(lo, tgt))
            his.append(np.broadcast_to(hi, tgt))
        lo = np.concatenate(los, axis=dim)
        hi = np.concatenate(his, axis=dim)
        return AbsVal(lo, hi, aval.shape, aval.dtype).collapsed()

    def _h_tile(self, eqn, ins, path, idx):
        # jnp.tile's primitive (JAX >= 0.9): values repeat, so bounds
        # repeat; a collapsed (size-1) axis stays collapsed
        (a,) = ins
        aval = eqn.outvars[0].aval
        reps = [r if a.lo.shape[d] > 1 else 1
                for d, r in enumerate(eqn.params["reps"])]
        return AbsVal(np.tile(a.lo, reps), np.tile(a.hi, reps),
                      aval.shape, aval.dtype).collapsed()

    def _h_pad(self, eqn, ins, path, idx):
        a, padval = ins
        cfg = eqn.params["padding_config"]
        aval = eqn.outvars[0].aval
        if any(lo < 0 or hi < 0 for lo, hi, _ in cfg):
            raise Unsupported(f"{path}[{idx}]: negative padding")
        pad_axes = [d for d, (l, h, i) in enumerate(cfg)
                    if (l, h, i) != (0, 0, 0)]
        lo, hi = a.materialize(pad_axes)
        out_stored = []
        for d, (l, h, i) in enumerate(cfg):
            if d in pad_axes:
                out_stored.append(aval.shape[d])
            else:
                out_stored.append(lo.shape[d])
        plo = int(padval.lo.min())
        phi = int(padval.hi.max())
        out_lo = np.full(out_stored, plo, np.int64)
        out_hi = np.full(out_stored, phi, np.int64)
        sl = []
        for d, (l, h, i) in enumerate(cfg):
            if d in pad_axes:
                n = lo.shape[d]
                sl.append(slice(l, l + (n - 1) * (i + 1) + 1 if n else l,
                                i + 1))
            else:
                sl.append(slice(None))
        out_lo[tuple(sl)] = lo
        out_hi[tuple(sl)] = hi
        return AbsVal(out_lo, out_hi, aval.shape, aval.dtype)

    def _h_slice(self, eqn, ins, path, idx):
        a = ins[0]
        starts = eqn.params["start_indices"]
        limits = eqn.params["limit_indices"]
        strides = eqn.params["strides"] or (1,) * len(starts)
        aval = eqn.outvars[0].aval
        sl = []
        for d, (s, l, st) in enumerate(zip(starts, limits, strides)):
            if a.lo.shape[d] == 1:
                sl.append(slice(0, 1, 1))
            else:
                sl.append(slice(s, l, st))
        return AbsVal(a.lo[tuple(sl)], a.hi[tuple(sl)],
                      aval.shape, aval.dtype)

    def _h_dynamic_slice(self, eqn, ins, path, idx):
        a = ins[0]
        starts = ins[1:]
        sizes = eqn.params["slice_sizes"]
        aval = eqn.outvars[0].aval
        lo, hi = a.lo, a.hi
        for d, (st, size) in enumerate(zip(starts, sizes)):
            dimsz = a.shape[d]
            s_lo = max(0, min(int(st.lo.min()), dimsz - size))
            s_hi = max(0, min(int(st.hi.max()), dimsz - size))
            if lo.shape[d] == 1:
                continue  # uniform along this axis: any window is equal
            if s_lo == s_hi:
                sl = [slice(None)] * lo.ndim
                sl[d] = slice(s_lo, s_lo + size)
                lo, hi = lo[tuple(sl)], hi[tuple(sl)]
            else:
                # union over feasible windows (sliding min/max)
                parts_lo, parts_hi = [], []
                for k in range(s_lo, s_hi + 1):
                    sl = [slice(None)] * lo.ndim
                    sl[d] = slice(k, k + size)
                    parts_lo.append(lo[tuple(sl)])
                    parts_hi.append(hi[tuple(sl)])
                lo = np.minimum.reduce(parts_lo)
                hi = np.maximum.reduce(parts_hi)
        return AbsVal(lo, hi, aval.shape, aval.dtype)

    def _h_iota(self, eqn, ins, path, idx):
        aval = eqn.outvars[0].aval
        dim = eqn.params["dimension"]
        n = aval.shape[dim]
        shape = [1] * len(aval.shape)
        shape[dim] = n
        vals = np.arange(n, dtype=np.int64).reshape(shape)
        return AbsVal(vals, vals.copy(), aval.shape, aval.dtype)

    # --- reductions ---

    def _reduce(self, eqn, ins, fn):
        a = ins[0]
        axes = sorted(eqn.params["axes"], reverse=True)
        lo, hi = a.lo, a.hi
        for ax in axes:
            lo = fn(lo, axis=ax)
            hi = fn(hi, axis=ax)
        aval = eqn.outvars[0].aval
        return AbsVal(lo, hi, aval.shape, aval.dtype)

    def _h_reduce_sum(self, eqn, ins, path, idx):
        a = ins[0]
        axes = sorted(eqn.params["axes"], reverse=True)
        lo, hi = a.lo, a.hi
        excl = set(a.excl)
        for ax in axes:
            n = a.shape[ax]
            if ax in excl:
                # at most one nonzero along ax: the sum is that single
                # element or zero — union bound, not an n-fold sum
                lo = np.minimum(lo.min(axis=ax), 0)
                hi = np.maximum(hi.max(axis=ax), 0)
            elif lo.shape[ax] == 1:
                lo = _clamp(_safe_mul(np.squeeze(lo, ax), np.int64(n)))
                hi = _clamp(_safe_mul(np.squeeze(hi, ax), np.int64(n)))
            else:
                lo = _clamp(_safe_sum(lo, ax))
                hi = _clamp(_safe_sum(hi, ax))
            excl = {e - 1 if e > ax else e for e in excl if e != ax}
        aval = eqn.outvars[0].aval
        return AbsVal(lo, hi, aval.shape, aval.dtype, frozenset(excl))

    def _h_reduce_max(self, eqn, ins, path, idx):
        return self._reduce(eqn, ins, np.max)

    def _h_reduce_min(self, eqn, ins, path, idx):
        return self._reduce(eqn, ins, np.min)

    def _h_reduce_and(self, eqn, ins, path, idx):
        # AND over an axis: true iff all true — min of lows / min of highs
        return self._reduce(eqn, ins, np.min)

    def _h_reduce_or(self, eqn, ins, path, idx):
        return self._reduce(eqn, ins, np.max)

    # --- scatter-add (the `.at[i].add(v)` fixup in table_select) ---

    def _h_scatter_add(self, eqn, ins, path, idx):
        operand, indices, updates = ins
        dn = eqn.params["dimension_numbers"]
        aval = eqn.outvars[0].aval
        if not np.array_equal(indices.lo, indices.hi):
            raise Unsupported(
                f"{path}[{idx}]: scatter-add with non-constant indices")
        idx_vals = np.broadcast_to(indices.lo, indices.shape)
        sdims = tuple(dn.scatter_dims_to_operand_dims)
        if idx_vals.size != len(sdims):
            raise Unsupported(
                f"{path}[{idx}]: scatter-add with multiple scatter "
                "points")
        if tuple(dn.inserted_window_dims) != sdims:
            raise Unsupported(f"{path}[{idx}]: scatter-add window shape")
        coords = [int(v) for v in idx_vals.ravel()]
        # materialize operand along indexed dims (the update makes them
        # non-uniform); updates broadcast into the window slice
        lo, hi = operand.materialize(list(sdims))
        lo, hi = lo.copy(), hi.copy()
        sl = [slice(None)] * lo.ndim
        ok = True
        for d, c in zip(sdims, coords):
            if not (0 <= c < operand.shape[d]):
                ok = False  # FILL_OR_DROP: out-of-bounds update dropped
            sl[d] = slice(c, c + 1)
        if ok:
            win_dims = [d for d in range(lo.ndim) if d not in sdims]
            if len(dn.update_window_dims) != updates.lo.ndim:
                raise Unsupported(
                    f"{path}[{idx}]: scatter-add update rank "
                    f"{updates.lo.ndim} vs window dims "
                    f"{dn.update_window_dims}")
            ulo, uhi = updates.lo, updates.hi
            tgt = [1] * lo.ndim
            for ud, d in enumerate(win_dims):
                tgt[d] = ulo.shape[ud] if ud < ulo.ndim else 1
            lo[tuple(sl)] = _clamp(lo[tuple(sl)] + ulo.reshape(tgt))
            hi[tuple(sl)] = _clamp(hi[tuple(sl)] + uhi.reshape(tgt))
        return AbsVal(lo, hi, aval.shape, aval.dtype)

    # --- dot_general (defensive: none in the current kernel) ---

    def _h_dot_general(self, eqn, ins, path, idx):
        a, b = ins
        (lhs_c, rhs_c), (lhs_b, rhs_b) = eqn.params["dimension_numbers"]
        aval = eqn.outvars[0].aval
        if lhs_b or rhs_b:
            raise Unsupported(f"{path}[{idx}]: batched dot_general")
        alo, ahi = a.full()
        blo, bhi = b.full()
        lhs_free = [d for d in range(alo.ndim)
                    if d not in lhs_c]
        rhs_free = [d for d in range(blo.ndim)
                    if d not in rhs_c]
        # einsum over the four corner products
        import string
        letters = string.ascii_lowercase
        l_sub = [""] * alo.ndim
        r_sub = [""] * blo.ndim
        k = 0
        for lc, rc in zip(lhs_c, rhs_c):
            l_sub[lc] = r_sub[rc] = letters[k]
            k += 1
        out_sub = ""
        for d in lhs_free:
            l_sub[d] = letters[k]
            out_sub += letters[k]
            k += 1
        for d in rhs_free:
            r_sub[d] = letters[k]
            out_sub += letters[k]
            k += 1
        spec = f"{''.join(l_sub)},{''.join(r_sub)}->{out_sub}"

        def dot(x, y):
            return np.einsum(spec, x.astype(np.float64),
                             y.astype(np.float64))
        c = [dot(alo, blo), dot(alo, bhi), dot(ahi, blo), dot(ahi, bhi)]
        # elementwise product bounds would be tighter; corner bound is
        # sound because min/max of sums <= sums of min/max per corner
        lo_f = np.minimum.reduce(c)
        hi_f = np.maximum.reduce(c)
        lo = np.where(np.abs(lo_f) >= float(SAT),
                      np.where(lo_f > 0, SAT, -SAT),
                      lo_f.astype(np.int64))
        hi = np.where(np.abs(hi_f) >= float(SAT),
                      np.where(hi_f > 0, SAT, -SAT),
                      hi_f.astype(np.int64))
        return AbsVal(lo, hi, aval.shape, aval.dtype).collapsed()

    # --- nesting ---

    def _h_pjit(self, eqn, ins, path, idx):
        inner = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
        name = eqn.params.get("name", "call")
        return self.eval_closed(inner, ins, f"{path}/{name}@{idx}")

    # --- scan ---
    #
    # Every loop in the verify kernel is a fori_loop with a static trip
    # count (pinned by tests/test_kernel_cost.py), so the primary
    # strategy is EXACT unrolling: per-iteration bounds, no widening, no
    # over-approximation. A join fixed point cannot close over the loop
    # counter anyway (f([0,n]) = [1,n+1] for an incrementing index — no
    # finite f-closed set exists), so bounded iteration is the only
    # sound route; the incremental evaluator below makes it cheap by
    # re-evaluating only the body equations whose inputs changed since
    # the previous iteration (after a few iterations the limb bounds
    # stabilize and only the index chain recomputes). Scans longer than
    # ``max_unroll`` fall back to a widened fixed point whose carry is
    # clamped through the dtype check — sound, possibly imprecise, and
    # loud about it if the imprecision reaches a violation.

    def _h_scan(self, eqn, ins, path, idx):
        p = eqn.params
        if p.get("reverse"):
            raise Unsupported(f"{path}[{idx}]: reverse scan")
        body = p["jaxpr"]
        length = int(p["length"])
        nc, ncar = int(p["num_consts"]), int(p["num_carry"])
        consts = ins[:nc]
        init = ins[nc:nc + ncar]
        xs = ins[nc + ncar:]
        spath = f"{path}/scan@{idx}"

        def xs_elem_at(t: int) -> List[AbsVal]:
            out = []
            for x in xs:
                if x.lo.shape[0] == 1:
                    lo, hi = x.lo[0:1], x.hi[0:1]
                else:
                    lo, hi = x.lo[t:t + 1], x.hi[t:t + 1]
                out.append(AbsVal(np.squeeze(lo, 0), np.squeeze(hi, 0),
                                  x.shape[1:], x.dtype))
            return out

        def finish(carry_out: List[AbsVal], ys: List[AbsVal]):
            outs = list(carry_out)
            for y, outvar in zip(ys, eqn.outvars[ncar:]):
                yl = y.lo[np.newaxis]
                yh = y.hi[np.newaxis]
                outs.append(AbsVal(yl, yh, outvar.aval.shape,
                                   outvar.aval.dtype))
            return outs

        if length <= self._max_unroll:
            evaluator = _IncrementalBody(self, body, spath)
            # intern per-iteration xs slices: reuse the previous slice
            # OBJECT when bounds are equal so the evaluator's
            # change-propagation can skip everything downstream of an
            # unchanged window (e.g. uniform digit rows)
            carry = list(init)
            prev_x: Optional[List[AbsVal]] = None
            ys_join: Optional[List[AbsVal]] = None
            for t in range(length):
                xe = xs_elem_at(t)
                if prev_x is not None:
                    xe = [px if px.same(x) else x
                          for px, x in zip(prev_x, xe)]
                prev_x = xe
                outs = evaluator.run(list(consts) + carry + xe)
                newc = outs[:ncar]
                carry = [pc if pc.same(n) else n
                         for pc, n in zip(carry, newc)]
                ys_t = outs[ncar:]
                if ys_join is None:
                    ys_join = list(ys_t)
                else:
                    ys_join = [a.join(b) for a, b in zip(ys_join, ys_t)]
            return finish(carry, ys_join or [])
        return self._scan_fixed_point(eqn, consts, init, xs, body,
                                      length, ncar, spath, finish)

    def _scan_fixed_point(self, eqn, consts, init, xs, body, length,
                          ncar, spath, finish):
        def xs_joined() -> List[AbsVal]:
            out = []
            for x in xs:
                out.append(AbsVal(x.lo.min(axis=0), x.hi.max(axis=0),
                                  x.shape[1:], x.dtype))
            return out

        def run_body(carry, xelems, recording: bool) -> List[AbsVal]:
            saved = self._recording
            self._recording = recording
            try:
                return self.eval_closed(
                    body, list(consts) + list(carry) + list(xelems),
                    spath)
            finally:
                self._recording = saved

        ladder = np.array(sorted(set(self._ladder.tolist()) |
                                 {length, length + 1, -length}),
                          dtype=np.int64)
        xj = xs_joined()
        carry = list(init)
        converged = False
        for it in range(self._max_fp_iters):
            outs = run_body(carry, xj, recording=False)
            newc = [c.join(n) for c, n in zip(carry, outs[:ncar])]
            if all(c.equals(n) for c, n in zip(carry, newc)):
                converged = True
                break
            if it >= self._widen_after:
                newc = [self._widen(c, n, ladder)
                        for c, n in zip(carry, newc)]
            carry = newc
        if not converged:
            raise Unsupported(
                f"{spath}: carry fixed point did not converge in "
                f"{self._max_fp_iters} iterations")
        # recorded pass under the (dtype-clamped) invariant: checks
        # every body equation for all iterations at once
        outs = run_body(carry, xj, recording=self._recording)
        return finish(outs[:ncar], outs[ncar:])

    def run_eqn(self, eqn, ins: List[AbsVal], path: str,
                idx: int) -> List[AbsVal]:
        """Evaluate one equation (handler + dtype check). Shared by the
        main loop and the incremental body evaluator."""
        handler = self._handlers.get(eqn.primitive.name)
        if handler is None:
            raise Unsupported(
                f"{path}[{idx}]: unhandled primitive "
                f"'{eqn.primitive.name}' at {_source_of(eqn)}")
        outs = handler(eqn, ins, path, idx)
        if not isinstance(outs, (list, tuple)):
            outs = [outs]
        return [self._check(eqn, o, var.aval, path, idx)
                for o, var in zip(outs, eqn.outvars)]

    @staticmethod
    def _widen(old: AbsVal, new: AbsVal, ladder: np.ndarray) -> AbsVal:
        lo, hi = new.lo.copy(), new.hi.copy()
        grow_lo = new.lo < old.lo
        grow_hi = new.hi > old.hi
        if grow_hi.any():
            pos = np.searchsorted(ladder, hi, side="left")
            pos = np.clip(pos, 0, len(ladder) - 1)
            hi = np.where(grow_hi, ladder[pos], hi)
        if grow_lo.any():
            pos = np.searchsorted(ladder, lo, side="right") - 1
            pos = np.clip(pos, 0, len(ladder) - 1)
            lo = np.where(grow_lo, ladder[pos], lo)
        return AbsVal(lo, hi, new.shape, new.dtype)


class _IncrementalBody:
    """Change-propagating evaluator for an unrolled scan body.

    Keeps the previous iteration's per-equation inputs (by object
    identity) and outputs: an equation whose input objects are unchanged
    is skipped outright; a recomputed output that EQUALS its predecessor
    is replaced by the predecessor object so everything downstream skips
    too. Once the limb bounds stabilize (2-3 iterations in practice),
    each remaining iteration only re-evaluates the loop-index chain and
    the window slices — turning O(length x body) into O(length) after a
    constant number of full passes. Bounds are identical to naive
    unrolling by construction (skips happen only on equality)."""

    def __init__(self, interp: IntervalInterpreter, closed_jaxpr,
                 path: str):
        from jax.core import DropVar
        from jax.extend import core
        self._core = core
        self._drop_var = DropVar
        self._interp = interp
        self._jaxpr = closed_jaxpr.jaxpr
        self._path = path
        self._const_env = {
            var: AbsVal.from_concrete(np.asarray(c))
            for var, c in zip(self._jaxpr.constvars, closed_jaxpr.consts)}
        self._lit_cache: Dict[Tuple[int, int], AbsVal] = {}
        n = len(self._jaxpr.eqns)
        self._prev_in: List[Optional[Tuple[int, ...]]] = [None] * n
        self._prev_out: List[Optional[List[AbsVal]]] = [None] * n

    def run(self, invals: Sequence[AbsVal]) -> List[AbsVal]:
        core = self._core
        env: Dict = dict(self._const_env)
        for var, v in zip(self._jaxpr.invars, invals):
            env[var] = v
        for idx, eqn in enumerate(self._jaxpr.eqns):
            ins = []
            for pos, v in enumerate(eqn.invars):
                if isinstance(v, core.Literal):
                    lit = self._lit_cache.get((idx, pos))
                    if lit is None:
                        lit = AbsVal.from_concrete(np.asarray(v.val))
                        self._lit_cache[(idx, pos)] = lit
                    ins.append(lit)
                else:
                    ins.append(env[v])
            in_ids = tuple(id(x) for x in ins)
            if in_ids == self._prev_in[idx]:
                outs = self._prev_out[idx]
            else:
                outs = self._interp.run_eqn(eqn, ins, self._path, idx)
                prev = self._prev_out[idx]
                if prev is not None:
                    outs = [p if p.same(o) else o
                            for p, o in zip(prev, outs)]
                self._prev_in[idx] = in_ids
                self._prev_out[idx] = outs
            for var, out in zip(eqn.outvars, outs):
                if not isinstance(var, self._drop_var):
                    env[var] = out
        out = []
        for v in self._jaxpr.outvars:
            if isinstance(v, core.Literal):
                out.append(AbsVal.from_concrete(np.asarray(v.val)))
            else:
                out.append(env[v])
        return out
