"""Public op: ChaCha20-CTR over flat uint32 words (auto-padded to blocks).

Chooses the Pallas kernel (interpret on CPU, compiled on TPU) and handles
the flat-words <-> (N,16)-blocks framing.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.chacha20.chacha20 import chacha20_xor_blocks, \
    chacha20_xor_rows


def xor_rows(key, nonces, counters, rows, *, block_rows: int = 256):
    """Per-row keystream XOR over (R, 16) u32 rows (auto-padded to tiles).

    key: (8,) shared or (R, 8) per-row; nonces: (R, 3); counters: (R,).
    The padded tail rows use key/nonce/counter zeros and are sliced off.
    """
    R = rows.shape[0]
    keys = key.reshape(1, 8) * jnp.ones((R, 1), jnp.uint32) \
        if key.ndim == 1 else key
    pad = (-R) % block_rows
    if pad:
        keys = jnp.pad(keys, ((0, pad), (0, 0)))
        nonces = jnp.pad(nonces, ((0, pad), (0, 0)))
        counters = jnp.pad(counters, (0, pad))
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    out = chacha20_xor_rows(keys, nonces, counters, rows,
                            block_rows=block_rows, interpret=interpret_mode())
    return out[:R]


def encrypt_words(key, nonce, words, counter0: int = 1, *,
                  block_rows: int = 512):
    n = words.shape[0]
    n_blocks = max((n + 15) // 16, 1)
    pad_rows = (-n_blocks) % block_rows
    total = (n_blocks + pad_rows) * 16
    padded = jnp.pad(words, (0, total - n)).reshape(-1, 16)
    out = chacha20_xor_blocks(key, nonce, counter0, padded,
                              block_rows=block_rows,
                              interpret=interpret_mode())
    return out.reshape(-1)[:n]


decrypt_words = encrypt_words
