"""Public op: full CW-MAC via the tiled Pallas kernel + jnp combine."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.crypto.cwmac import addmod, mulmod, r_powers_batch, to_limbs_batch
from repro.kernels import interpret_mode
from repro.kernels.cwmac.cwmac import LANES, ROWS, mac_partials_batch

U32 = jnp.uint32


def _pick_tile(n_limbs: int, tile: int) -> int:
    """Smallest power-of-two tile >= 128 lanes that covers the message,
    capped at the requested tile (padding is always to a whole number of
    tiles, so tiny messages pad to one 128-lane tile)."""
    t = LANES
    while t < tile and t < n_limbs:
        t *= 2
    return t


def _fold_lanes(x: jax.Array) -> jax.Array:
    """(..., w) lane partials -> (...,) add-mod over the last axis."""
    while x.shape[-1] > 1:
        half = x.shape[-1] // 2
        x = addmod(x[..., :half], x[..., half:])
    return x[..., 0]


@functools.partial(jax.jit, static_argnames=("tile",))
def mac_batch(words: jax.Array, r: jax.Array, s: jax.Array, *,
              tile: int = 4096) -> jax.Array:
    """Row-wise kernel-tiled MAC: (B, N) words under (B,) keys -> (B,) tags.

    tag = Σ_t r^(TS·(T-1-t)) · P_t + s: the partials kernel sweeps a
    (B/8, T) grid, so one launch MACs the whole batch."""
    limbs = to_limbs_batch(words)
    B, n = limbs.shape
    tile = _pick_tile(n, tile)
    # front-pad (zero limbs contribute 0) to keep low powers at message
    # end; pad the batch to whole 8-row blocks (rows sliced off below)
    pad_rows = (-B) % ROWS
    limbs = jnp.pad(limbs, ((0, pad_rows), ((-n) % tile, 0)))
    T = limbs.shape[1] // tile
    r = jnp.pad(jnp.asarray(r, U32).reshape(-1), (0, pad_rows))
    pows_tile = r_powers_batch(r, tile)                  # (Bp, tile)
    lanes = mac_partials_batch(limbs, pows_tile, tile=tile,
                               interpret=interpret_mode())
    partials = _fold_lanes(lanes[:B].reshape(B, T, -1))  # (B, T)
    rTS = pows_tile[:B, 0]                               # (B,) r^tile

    def step(carry, p_t):   # Horner over tiles, batched carry (B,)
        return addmod(mulmod(carry, rTS), p_t), None

    acc, _ = jax.lax.scan(step, jnp.zeros((B,), U32), partials.T)
    return addmod(acc, jnp.asarray(s, U32))


def mac2_batch(words: jax.Array, r1: jax.Array, s1: jax.Array,
               r2: jax.Array, s2: jax.Array, *,
               tile: int = 4096) -> jax.Array:
    """Row-wise dual-key MAC -> (B, 2) tags; both keys ride one launch."""
    B = words.shape[0]
    tags = mac_batch(jnp.concatenate([words, words]),
                     jnp.concatenate([jnp.asarray(r1, U32).reshape(-1),
                                      jnp.asarray(r2, U32).reshape(-1)]),
                     jnp.concatenate([jnp.asarray(s1, U32).reshape(-1),
                                      jnp.asarray(s2, U32).reshape(-1)]),
                     tile=tile)
    return jnp.stack([tags[:B], tags[B:]], axis=-1)


def mac(words: jax.Array, r: jax.Array, s: jax.Array, *,
        tile: int = 4096) -> jax.Array:
    """tag = (sum_i limb_i r^(n-i) + s) mod 2^31-1 of one (N,) message:
    :func:`mac_batch` over a single row."""
    return mac_batch(words.reshape(1, -1), jnp.asarray(r, U32).reshape(1),
                     jnp.asarray(s, U32).reshape(1), tile=tile)[0]
