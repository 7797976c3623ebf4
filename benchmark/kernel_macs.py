"""Static multiply count of the verify kernels, from their jaxprs.

Copied from ``tools/kernel_cost.py`` (``count_prims`` and
``_out_elems``): every ``mul``/``dot_general`` equation weighted by its
output elements, ``scan`` bodies by their trip counts. Divided by the
kernel's device time from the trace it gives the achieved multiply
rate, printed on an earlier line: not a roofline share, since the
kernels are int32 VPU work and v5e publishes no int32 VPU peak.
"""

from __future__ import annotations

import numpy as np

_MUL_PRIMS = ("mul", "dot_general")
BATCH = 128


def _out_elems(eqn) -> int:
    n = 0
    for v in eqn.outvars:
        n += int(np.prod(v.aval.shape)) if v.aval.shape else 1
    if eqn.primitive.name == "dot_general":
        lhs = eqn.invars[0].aval.shape
        for d in eqn.params["dimension_numbers"][0][0]:
            n *= int(lhs[d])
    return n


def _sub_jaxprs(eqn):
    from jax.extend import core
    name = eqn.primitive.name
    if name == "scan":
        yield eqn.params["jaxpr"], int(eqn.params["length"])
        return
    if name == "while":
        yield eqn.params["cond_jaxpr"], 1
        yield eqn.params["body_jaxpr"], 1
        return
    if name == "cond":
        for br in eqn.params["branches"]:
            yield br, 1
        return
    for val in eqn.params.values():
        for v in val if isinstance(val, (list, tuple)) else (val,):
            if isinstance(v, (core.ClosedJaxpr, core.Jaxpr)):
                yield v, 1


def weighted_mul_elems(jaxpr) -> int:
    from jax.extend import core
    if isinstance(jaxpr, core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in _MUL_PRIMS:
            total += _out_elems(eqn)
        for sub, trips in _sub_jaxprs(eqn):
            total += trips * weighted_mul_elems(sub)
    return total


def macs_per_row() -> dict:
    """Executed multiply elements per verified row, cold and hot
    kernel, counted at a batch of 128."""
    import jax
    from stellar_tpu.ops import verify
    u8 = jax.ShapeDtypeStruct((BATCH, 32), np.uint8)
    table = jax.ShapeDtypeStruct((BATCH, 128, 3, 20), np.int16)
    cold = jax.make_jaxpr(verify.verify_kernel)(u8, u8, u8, u8)
    hot = jax.make_jaxpr(verify.verify_kernel_hot)(table, u8, u8, u8)
    return {"cold": weighted_mul_elems(cold) / BATCH,
            "hot": weighted_mul_elems(hot) / BATCH}
