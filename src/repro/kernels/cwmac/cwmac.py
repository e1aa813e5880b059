"""Pallas TPU kernel: tiled Carter-Wegman MAC partials over GF(2^31-1).

The MAC tag is Σ_i limb_i · r^(n-i) + s.  Factoring by tile t of TS limbs:

    tag = Σ_t  r^(TS·(T-1-t)) · P_t,     P_t = Σ_j limb_{t,j} · r^(TS-j)

Each grid program reduces one (8-row, TS-limb) VMEM tile against the rows'
precomputed powers (r^TS .. r^1) down to 128 lane partials; folding those
into P_t, the per-tile factors and the final Horner pass are done in jnp
(ops.py).  Integer-only 32-bit arithmetic throughout — see
repro.crypto.cwmac for the field math.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

U32 = jnp.uint32
P31 = np.uint32(0x7FFFFFFF)


def _fold31(x):
    x = (x & P31) + (x >> np.uint32(31))
    return jnp.where(x >= P31, x - P31, x)


def _addmod(a, b):
    return _fold31(a + b)


def _mulmod(a, b):
    a1 = a >> np.uint32(16)
    a0 = a & np.uint32(0xFFFF)
    b1 = b >> np.uint32(16)
    b0 = b & np.uint32(0xFFFF)
    mid = a0 * b1 + a1 * b0
    acc = _fold31(a0 * b0)
    acc = _addmod(acc, _fold31((a1 * b1) * np.uint32(2)))
    acc = _addmod(acc, _fold31(mid >> np.uint32(15)))
    acc = _addmod(acc, _fold31((mid & np.uint32(0x7FFF)) << np.uint32(16)))
    return acc


LANES = 128
ROWS = 8   # one (8, 128) u32 tile: the smallest block Mosaic accepts


def _mac_tile_batch_kernel(limbs_ref, pows_ref, out_ref):
    acc = _mulmod(limbs_ref[...], pows_ref[...])   # (ROWS, tile) u32 < p
    # log-depth tree add-mod across lane-aligned halves; the last 128
    # lane partials are folded by the caller
    n = acc.shape[1]
    while n > LANES:
        half = n // 2
        acc = _addmod(acc[:, :half], acc[:, half:n])
        n = half
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def mac_partials_batch(limbs: jax.Array, powers: jax.Array, *,
                       tile: int = 4096, interpret: bool = True) -> jax.Array:
    """Per-row tiled lane partials: limbs (B, N) u32 < p with B % 8 == 0
    and N % tile == 0; powers (B, tile) per-row [r_b^TS .. r_b^1].

    tile is a power of two >= 128.  Returns (B, T * 128) with T = N / tile:
    for row b and tile t, the lanes ``[t*128, (t+1)*128)`` add up (mod p)
    to that tile's partial P_t.  One grid sweep covers every (8-row, tile)
    block, and every block is made of whole (8, 128) tiles.
    """
    B, N = limbs.shape
    assert B % ROWS == 0, (B, ROWS)
    assert N % tile == 0 and tile >= LANES and (tile & (tile - 1)) == 0, \
        (N, tile)
    T = N // tile
    return pl.pallas_call(
        _mac_tile_batch_kernel,
        grid=(B // ROWS, T),
        in_specs=[
            pl.BlockSpec((ROWS, tile), lambda b, t: (b, t)),
            pl.BlockSpec((ROWS, tile), lambda b, t: (b, 0)),
        ],
        out_specs=pl.BlockSpec((ROWS, LANES), lambda b, t: (b, t)),
        out_shape=jax.ShapeDtypeStruct((B, T * LANES), U32),
        interpret=interpret,
    )(limbs, powers)
