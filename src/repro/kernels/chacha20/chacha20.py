"""Pallas TPU kernel: ChaCha20-CTR encrypt/decrypt over uint32 word blocks.

Grid: one program per tile of `block_rows` cipher blocks; each block is 16
uint32 words, so a tile is a (block_rows, 16) u32 VMEM buffer (block_rows=512
=> 32 KiB in + 32 KiB out, comfortably inside VMEM with double buffering).
The keystream is derived in-register from (key, nonce, counter) — the
HBM->VMEM DMA moves only ciphertext, which is the paper's MEE boundary
analogy (DESIGN.md §2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.chacha20.common import keystream_vectors

U32 = jnp.uint32


def _chacha_kernel(key_ref, nonce_ref, ctr_ref, data_ref, out_ref, *,
                   block_rows: int):
    pid = pl.program_id(0)
    key = [key_ref[0, i] for i in range(8)]
    nonce = [nonce_ref[0, i] for i in range(3)]
    base = ctr_ref[0, 0] + (pid * block_rows).astype(U32)
    counters = base + jax.lax.broadcasted_iota(U32, (block_rows,), 0)
    ks = keystream_vectors(key, nonce, counters)      # 16 x (rows,)
    data = data_ref[...]                              # (rows, 16) u32
    ks_mat = jnp.stack(ks, axis=-1)                   # (rows, 16)
    out_ref[...] = data ^ ks_mat


def _chacha_rows_kernel(key_ref, nonce_ref, ctr_ref, data_ref, out_ref):
    """Per-row (key, nonce, counter) tile: the batched-AEAD fast path.

    Every VMEM row is one cipher block with its own key/nonce/counter
    column vectors, so a whole (batch, counters 0..N) seal batch is a
    single grid sweep — no per-item dispatch.
    """
    key = [key_ref[:, i] for i in range(8)]       # 8 x (rows,)
    nonce = [nonce_ref[:, i] for i in range(3)]   # 3 x (rows,)
    counters = ctr_ref[:, 0]                      # (rows,)
    ks = keystream_vectors(key, nonce, counters)  # 16 x (rows,)
    out_ref[...] = data_ref[...] ^ jnp.stack(ks, axis=-1)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def chacha20_xor_rows(keys: jax.Array, nonces: jax.Array, counters: jax.Array,
                      data_rows: jax.Array, *, block_rows: int = 256,
                      interpret: bool = True) -> jax.Array:
    """XOR (R, 16) u32 rows with per-row keystream blocks.

    keys: (R, 8); nonces: (R, 3); counters: (R,).  R % block_rows == 0.
    The counters enter the kernel as an (R, 1) column: a 1-D block of
    ``block_rows`` does not match the TPU's tiling of a long u32 vector.
    """
    R = data_rows.shape[0]
    assert R % block_rows == 0, (R, block_rows)
    grid = (R // block_rows,)
    return pl.pallas_call(
        _chacha_rows_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, 8), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 3), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 16), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, 16), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(data_rows.shape, U32),
        interpret=interpret,
    )(keys.astype(U32), nonces.astype(U32),
      counters.astype(U32).reshape(R, 1), data_rows)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def chacha20_xor_blocks(key: jax.Array, nonce: jax.Array, counter0,
                        data_blocks: jax.Array, *, block_rows: int = 512,
                        interpret: bool = True) -> jax.Array:
    """data_blocks: (N, 16) u32, N % block_rows == 0. Returns XORed blocks."""
    N = data_blocks.shape[0]
    assert N % block_rows == 0, (N, block_rows)
    grid = (N // block_rows,)
    key2 = key.reshape(1, 8).astype(U32)
    nonce2 = nonce.reshape(1, 3).astype(U32)
    ctr = jnp.asarray(counter0, U32).reshape(1, 1)
    return pl.pallas_call(
        functools.partial(_chacha_kernel, block_rows=block_rows),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 8), lambda i: (0, 0)),
            pl.BlockSpec((1, 3), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((block_rows, 16), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, 16), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(data_blocks.shape, U32),
        interpret=interpret,
    )(key2, nonce2, ctr, data_blocks)
