"""The TPU batch ed25519 verification kernel — the framework's flagship op.

Device side of the reference's ``PubKeyUtils::verifySig``
(``src/crypto/SecretKey.cpp:435-468``): given a batch of (pubkey, R, s, h)
— with ``h = SHA512(R||A||M) mod L`` computed host-side (hashing is cheap
and sequential; see ``stellar_tpu/crypto/batch_verifier.py``) — checks the
cofactorless group equation ``encode(s*B - h*A) == R`` for every element in
parallel. Policy checks that are pure byte predicates (canonical s < L,
canonical A, small-order blocklist) are done host-side, exactly mirroring
libsodium's decomposition; the final verdict is the AND of both halves.

Shapes: batch rides the trailing axis of every limb array so it maps to the
128-wide TPU vector lanes; the kernel is shape-polymorphic in batch and is
jit-cached per padded bucket size. Multi-chip: the batch axis is sharded
with ``shard_map`` over a 1-D device mesh (pure data parallelism — no
collectives needed, verification is embarrassingly parallel; see
``stellar_tpu.parallel.mesh``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from stellar_tpu.ops import edwards as ed

__all__ = ["verify_kernel", "verify_kernel_hot", "verify_kernel_sharded",
           "signed_digits16_dev", "signed_digits32_dev",
           "signed_digits256_dev"]


def _signed_window_carry_chain(e, window_bits):
    """Unsigned window values -> SIGNED window digits, shared by both
    recodes. ``e``: (windows, batch) int32 window values in
    [0, 2^window_bits), LEAST significant first. Returns (windows,
    batch) digits, MOST significant first, with d_i in
    [-half, half) for i < windows-1 and the TOP window keeping its
    carry as an unsigned residue so the stream reconstructs the scalar
    exactly (sum(d_i * 2^(window_bits*i)) == s).

    The carry chain (c_{i+1} = 1 iff e_i + c_i >= half) is a classic
    generate/propagate recurrence — generate at e_i >= half, propagate
    at e_i == half-1 — computed in log2(windows) parallel steps with
    ``lax.associative_scan`` instead of a sequential chain; the
    half-subtraction is a shift, so no recode work ever reaches the
    multiply ledger."""
    windows = e.shape[0]
    half = 1 << (window_bits - 1)
    gen = e >= half
    prop = e == half - 1

    def comb(lo_pair, hi_pair):
        g1, p1 = lo_pair
        g2, p2 = hi_pair
        return g2 | (p2 & g1), p2 & p1

    g_pre, _ = lax.associative_scan(comb, (gen, prop), axis=0)
    carry_out = g_pre.astype(jnp.int32)             # c_{i+1}
    carry_in = jnp.concatenate(                     # c_i
        [jnp.zeros_like(carry_out[:1]), carry_out[:-1]], axis=0)
    not_top = (jnp.arange(windows, dtype=jnp.int32) < windows - 1)
    d = e + carry_in - jnp.where(not_top[:, None],
                                 carry_out << window_bits, 0)
    return d[::-1]


def signed_digits16_dev(b):
    """(batch, 32) uint8 little-endian scalars -> (64, batch) int32 SIGNED
    radix-16 digits, most significant first: the ref10 signed-window
    recode (libsodium ge25519_scalarmult's slide), vectorized. Runs on
    device so the host ships raw 32-byte scalars (4x less relay/PCIe
    traffic than int32 digit arrays).

    Digits d_i satisfy sum(d_i * 16^i) == s exactly for EVERY 256-bit s,
    with d_i in [-8, 8) for i < 63; the top digit absorbs the final carry
    unsigned, so it stays in [0, 2] for canonical scalars (s < L < 2^253)
    and in [0, 8] for any s < 2^255 — within the 8-entry table range of
    :func:`stellar_tpu.ops.edwards.table_select`. (Scalars >= 9 * 2^252
    overflow the top window; the host canonical-s gate rejects them before
    the verdict, see double_scalarmult's contract.) Carry chain:
    :func:`_signed_window_carry_chain`.
    """
    x = b.astype(jnp.int32)
    lo = x & 15
    hi = x >> 4
    # (64, batch) unsigned nibbles, LEAST significant first
    e = jnp.stack([lo, hi], axis=2).reshape(b.shape[0], 64).T
    return _signed_window_carry_chain(e, 4)


def signed_digits32_dev(b):
    """(batch, 32) uint8 little-endian scalars -> (52, batch) int32
    SIGNED radix-32 digits, most significant first — the 5-bit-window
    sibling of :func:`signed_digits16_dev` for the batched-affine
    radix-32 loop (PR 13; sweep decision in docs/kernel_design.md §3).

    Digits d_i satisfy sum(d_i * 32^i) == s exactly for EVERY 256-bit
    s, with d_i in [-16, 16) for i < 51; the top digit absorbs the
    final carry unsigned. Since window 51 covers bits 255..259 of which
    only bit 255 exists, the top digit stays in [0, 2] for ALL inputs —
    every 256-bit scalar fits the 16-entry table range, a strictly
    stronger contract than the radix-16 recode's (which overflows its
    top window for s >= 9 * 2^252).

    Five-bit windows straddle byte boundaries, so the bytes unpack to
    a 256-bit vector first (shift/mask only — no multiplies reach the
    dsm MAC ledger from the recode); the carry chain (generate at
    e_i >= 16, propagate at e_i == 15) is the SAME shared
    :func:`_signed_window_carry_chain` as the radix-16 recode.
    """
    nbatch = b.shape[0]
    bits = ((b[:, :, None].astype(jnp.int32)
             >> jnp.arange(8, dtype=jnp.int32)) & 1)
    bits = bits.reshape(nbatch, 256)
    bits = jnp.pad(bits, ((0, 0), (0, 260 - 256)))
    e = (bits.reshape(nbatch, 52, 5)
         << jnp.arange(5, dtype=jnp.int32)).sum(-1)
    # (52, batch) unsigned 5-bit windows, LEAST significant first
    return _signed_window_carry_chain(e.T, 5)


def signed_digits256_dev(b):
    """(batch, 32) uint8 little-endian scalars -> (32, batch) int32
    SIGNED radix-256 digits, most significant first — the byte-aligned
    recode for the hot-signer loop (PR 16; docs/kernel_design.md §5).

    Eight-bit windows land exactly on byte boundaries, so the BYTES ARE
    the unsigned window values and the recode is the shared
    :func:`_signed_window_carry_chain` alone — no bit unpack at all.
    Digits d_i satisfy sum(d_i * 256^i) == s exactly for EVERY 256-bit
    s, with d_i in [-128, 128) for i < 31; the top digit absorbs the
    final carry unsigned, staying <= 32 for every gate-passed scalar
    (s < L < 2^253) — inside the 128-entry hot-table range. Scalars
    >= 2^255 - 128 can push the top digit past the table; the host
    canonical-s gate rejects them before any verdict, and the hot
    dispatch path additionally never routes a gate-failed row
    (double_scalarmult_hot's contract)."""
    # (32, batch) unsigned byte windows, LEAST significant first
    e = b.astype(jnp.int32).T
    return _signed_window_carry_chain(e, 8)


def dsm_stage(s_bytes, h_bytes, a_neg):
    """Signed-window recode + double-scalarmult: the traceable 'dsm' stage
    of the kernel (tools/kernel_cost.py accounts cost per stage; the
    limb layout, window scheme, and MAC ledger live in
    docs/kernel_design.md). Radix-32 batched-affine since PR 13."""
    with jax.named_scope("dsm"):
        return ed.double_scalarmult(signed_digits32_dev(s_bytes),
                                    signed_digits32_dev(h_bytes), a_neg)


def verify_kernel(a_bytes, r_bytes, s_bytes, h_bytes):
    """Batched group-equation check.

    Args:
      a_bytes: (batch, 32) uint8 — public key encodings.
      r_bytes: (batch, 32) uint8 — signature R halves.
      s_bytes: (batch, 32) uint8 — signature scalars s (little-endian).
      h_bytes: (batch, 32) uint8 — h = SHA512(R||A||M) mod L (little-endian).

    Returns:
      (batch,) bool — True where decompression succeeded and
      encode(s*B + h*(-A)) == R bytewise. The scalar mult runs signed
      radix-16 windows (8-entry tables + conditional negate): exact for
      every s < 2^255, and the composed verifier decision stays
      bit-identical to libsodium because s >= L never reaches a verdict
      (host canonical-s gate).
    """
    with jax.named_scope("decompress"):
        ok, a = ed.decompress(a_bytes)
    rprime = dsm_stage(s_bytes, h_bytes, ed.negate(a))
    with jax.named_scope("compress_compare"):
        eq = ed.compress_equals(rprime, r_bytes)
    return ok & eq


def dsm_stage_hot(s_bytes, h_bytes, a_table):
    """Hot-signer sibling of :func:`dsm_stage` (PR 16): byte-aligned
    radix-256 recode + the cached-table double-scalarmult. ``a_table``
    is the batch-LEADING (batch, 128, 3, 20) int16 operand exactly as
    the signer-table cache ships it; the limb layout wants batch
    TRAILING, so the one transpose lives here at the stage boundary."""
    with jax.named_scope("dsm"):
        tab = jnp.moveaxis(a_table, 0, -1)  # (128, 3, 20, batch)
        return ed.double_scalarmult_hot(signed_digits256_dev(s_bytes),
                                        signed_digits256_dev(h_bytes), tab)


def verify_kernel_hot(a_table, r_bytes, s_bytes, h_bytes):
    """Batched group-equation check for HOT (cache-hit) signers.

    Args:
      a_table: (batch, 128, 3, 20) int16 — affine cached multiples
        1..128 of -A per row, canonical limbs, Z == 1 (built host-side
        by :mod:`stellar_tpu.parallel.signer_tables`).
      r_bytes, s_bytes, h_bytes: as :func:`verify_kernel`.

    Returns:
      (batch,) bool — True where encode(s*B + h*(-A)) == R bytewise.
      There is NO decompression stage: a signer-table cache entry only
      exists for a pubkey that decompressed successfully at population
      time, so ``ok`` is True by construction for every row the hot
      path serves (the host policy gates — canonical s/A, small-order,
      lengths — still run in encode and AND into the verdict exactly
      like the cold path). Bit-identical to verify_kernel on every row
      both paths accept, which the differential suite pins per bucket.
    """
    rprime = dsm_stage_hot(s_bytes, h_bytes, a_table)
    with jax.named_scope("compress_compare"):
        return ed.compress_equals(rprime, r_bytes)


def verify_kernel_sharded(mesh, axis_name="batch"):
    """Wrap the kernel in shard_map over a 1-D mesh: batch split across
    devices, no cross-device communication (each chip verifies its shard).
    Returns a jitted callable with the same signature as verify_kernel;
    batch must be divisible by mesh size.

    Note: ``BatchVerifier`` no longer dispatches through this wrapper —
    it splits buckets into per-device sub-chunks of the plain kernel so
    failures are attributable to ONE chip (the fault-domain boundary,
    ``docs/robustness.md``). This stays as the single-call collective
    layout for harnesses (``__graft_entry__.dryrun_multichip``) and
    mesh-layout experiments.
    """
    from jax.sharding import PartitionSpec as P

    sharded = jax.shard_map(
        verify_kernel,
        mesh=mesh,
        in_specs=(P(axis_name, None), P(axis_name, None),
                  P(axis_name, None), P(axis_name, None)),
        out_specs=P(axis_name),
        check_vma=False,
    )
    return jax.jit(sharded)
