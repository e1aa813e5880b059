"""Carter-Wegman polynomial MAC over GF(2^31 - 1) (Mersenne prime M31).

This replaces Poly1305 in the TPU hot path (DESIGN.md §2): Poly1305's
130-bit limb arithmetic needs 64-bit multiplies, which TPU vector lanes do
not have.  A polynomial-evaluation MAC over M31 uses only 32-bit integer
ops (with 16-bit split multiplication) and admits a *parallel* form

    tag = ( sum_i m_i * r^(n-i) + s ) mod p

so per-tile partial sums can be combined in a tree — the MAC of a 100 MB
stream parallelizes across lanes/cores like the cipher itself.

Security note (honest): a single M31 evaluation gives ~31-bit forgery
bound; we evaluate with two independent keys and concatenate (62-bit tag),
which is adequate for integrity (not signatures) inside a session.  The
host-side Poly1305 (poly1305_host.py) remains for sealed storage.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

U32 = jnp.uint32
P31 = np.uint32(0x7FFFFFFF)  # 2^31 - 1


def _fold31(x: jax.Array) -> jax.Array:
    """Reduce a uint32 (< 2^32) mod 2^31-1 (one fold + conditional sub)."""
    x = (x & P31) + (x >> np.uint32(31))
    return jnp.where(x >= P31, x - P31, x)


def addmod(a: jax.Array, b: jax.Array) -> jax.Array:
    return _fold31(a + b)  # a,b < 2^31 so a+b < 2^32: safe in u32


def mulmod(a: jax.Array, b: jax.Array) -> jax.Array:
    """(a*b) mod (2^31-1) for a,b < 2^31 using 16-bit split multiplies."""
    a1 = a >> np.uint32(16)          # < 2^15
    a0 = a & np.uint32(0xFFFF)       # < 2^16
    b1 = b >> np.uint32(16)          # < 2^15
    b0 = b & np.uint32(0xFFFF)
    t00 = a0 * b0                    # < 2^32 (fits u32)
    t01 = a0 * b1                    # < 2^31
    t10 = a1 * b0                    # < 2^31
    t11 = a1 * b1                    # < 2^30
    mid = t01 + t10                  # < 2^32
    # value = t11*2^32 + mid*2^16 + t00  (mod p: 2^32 = 2, 2^31 = 1)
    mid_h = mid >> np.uint32(15)     # * 2^31 -> * 1
    mid_l = (mid & np.uint32(0x7FFF)) << np.uint32(16)
    acc = _fold31(t00)
    acc = addmod(acc, _fold31(t11 * np.uint32(2)))
    acc = addmod(acc, _fold31(mid_h))
    acc = addmod(acc, _fold31(mid_l))
    return acc


def _to_limbs(words: jax.Array) -> jax.Array:
    """Split (N,) uint32 into (2N,) 16-bit limbs (< p) for injectivity."""
    lo = words & np.uint32(0xFFFF)
    hi = words >> np.uint32(16)
    return jnp.stack([lo, hi], axis=-1).reshape(-1)


def r_powers(r: jax.Array, n: int) -> jax.Array:
    """[r^n, r^(n-1), ..., r^1] mod p via log-depth doubling.

    O(log n) sequential steps of vectorized mulmods (r^{i+m} = r^i * r^m),
    not an O(n) scan — the MAC of an N-word chunk stays parallel end to end
    (EXPERIMENTS.md §Perf, pipeline iteration)."""
    asc = jnp.asarray(r, U32).reshape(1)
    while asc.shape[0] < n:
        asc = jnp.concatenate([asc, mulmod(asc, asc[-1])])
    return asc[:n][::-1]


def mac(words: jax.Array, r: jax.Array, s: jax.Array) -> jax.Array:
    """tag = (sum_i limb_i * r^(n-i) + s) mod p. All scalars u32 < p.

    Parallel form: the elementwise multiply + sum is one reduction, so XLA
    (and the Pallas kernel) can tree-reduce across lanes.
    """
    limbs = _to_limbs(words)
    n = limbs.shape[0]
    ps = r_powers(r, n)
    # elementwise mulmod then tree add-mod (log-depth via binary fold)
    terms = mulmod(limbs, ps)
    acc = terms
    while acc.shape[0] > 1:
        if acc.shape[0] % 2:
            acc = jnp.concatenate([acc, jnp.zeros((1,), U32)])
        acc = addmod(acc[0::2], acc[1::2])
    return addmod(acc[0], s)


def mac2(words: jax.Array, r1: jax.Array, s1: jax.Array,
         r2: jax.Array, s2: jax.Array) -> jax.Array:
    """Two independent M31 evaluations -> (2,) u32 tag (~62-bit bound)."""
    return jnp.stack([mac(words, r1, s1), mac(words, r2, s2)])


# ---------------------------------------------------------------------------
# batched forms (B independent messages / keys in one program)
# ---------------------------------------------------------------------------


def to_limbs_batch(words: jax.Array) -> jax.Array:
    """(B, N) uint32 -> (B, 2N) 16-bit limbs, per-row layout of _to_limbs."""
    lo = words & np.uint32(0xFFFF)
    hi = words >> np.uint32(16)
    return jnp.stack([lo, hi], axis=-1).reshape(words.shape[0], -1)


def r_powers_batch(r: jax.Array, n: int) -> jax.Array:
    """Per-row [r_b^n .. r_b^1]: (B,) keys -> (B, n) powers, by square and
    multiply over the exponents' bits: one rolled loop of log2(n) steps,
    so every program that MACs traces and lowers a few ops, not an
    unrolled doubling chain."""
    r = jnp.asarray(r, U32).reshape(-1, 1)
    exps = jnp.arange(n, 0, -1, dtype=U32)[None, :]

    def step(j, carry):
        acc, sq = carry
        bit = (exps >> j.astype(U32)) & np.uint32(1)
        return jnp.where(bit == 1, mulmod(acc, sq), acc), mulmod(sq, sq)

    acc, _ = jax.lax.fori_loop(0, max(1, int(n).bit_length()), step,
                               (jnp.ones((r.shape[0], n), U32), r))
    return acc


def mac_batch(words: jax.Array, r: jax.Array, s: jax.Array) -> jax.Array:
    """Row-wise MAC: (B, N) words under (B,) keys -> (B,) tags.

    Same polynomial as :func:`mac`, but the elementwise mulmod and the
    log-depth add-mod tree run over the whole batch at once — one program
    MACs every block of a mailbox round.
    """
    limbs = to_limbs_batch(words)
    ps = r_powers_batch(r, limbs.shape[1])
    acc = mulmod(limbs, ps)
    while acc.shape[1] > 1:
        if acc.shape[1] % 2:
            acc = jnp.concatenate(
                [acc, jnp.zeros((acc.shape[0], 1), U32)], axis=1)
        acc = addmod(acc[:, 0::2], acc[:, 1::2])
    return addmod(acc[:, 0], s)


def mac2_batch(words: jax.Array, r1: jax.Array, s1: jax.Array,
               r2: jax.Array, s2: jax.Array) -> jax.Array:
    """Row-wise dual-key MAC: (B, N) words -> (B, 2) tags.

    Both evaluations share one kernel pass: the (r1, s1) and (r2, s2) rows
    are stacked into a single (2B,)-key batch.
    """
    B = words.shape[0]
    tags = mac_batch(jnp.concatenate([words, words]),
                     jnp.concatenate([jnp.asarray(r1, U32).reshape(-1),
                                      jnp.asarray(r2, U32).reshape(-1)]),
                     jnp.concatenate([jnp.asarray(s1, U32).reshape(-1),
                                      jnp.asarray(s2, U32).reshape(-1)]))
    return jnp.stack([tags[:B], tags[B:]], axis=-1)


def mac_reference(words: np.ndarray, r: int, s: int) -> int:
    """Host-side oracle with Python ints (used by tests)."""
    p = (1 << 31) - 1
    limbs = []
    for w in np.asarray(words, dtype=np.uint64):
        limbs += [int(w) & 0xFFFF, int(w) >> 16]
    acc = 0
    for m in limbs:
        acc = ((acc + m) * r) % p
    return (acc + s) % p
