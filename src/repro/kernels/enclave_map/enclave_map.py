"""Pallas TPU kernel: the ENCLAVE EXECUTOR — fused decrypt -> op -> encrypt.

This is the paper's central mechanism transposed to TPU (DESIGN.md §2):
the SGX enclave becomes a VMEM-resident kernel.  The HBM->VMEM DMA delivers
*ciphertext*; the keystream XOR (decrypt), the user operator, and the
re-encrypt all happen on VMEM tiles inside one kernel launch, so plaintext
never exists in HBM — exactly how the MEE keeps plaintext inside the CPU
package while DRAM sees ciphertext.

The operator is selected statically (the "enclaved bytecode" is fixed at
attestation time, like the paper's statically-linked Lua extensions):

* ``identity``       — pure re-key (router-to-router transfer)
* ``scale_f32``      — y = x * c          (map)
* ``relu_f32``       — y = max(x, 0)      (map)
* ``square_f32``     — y = x * x          (map)
* ``threshold_mask`` — y = (x > c) ? x : 0  (filter as dense mask)
* ``delay_filter_u32`` — the DelayedFlights predicate on packed records
"""
from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.chacha20.common import keystream_vectors

U32 = jnp.uint32
F32 = jnp.float32


def _bitcast_f32(words_u32):
    return jax.lax.bitcast_convert_type(words_u32, F32)


def _bitcast_u32(x_f32):
    return jax.lax.bitcast_convert_type(x_f32, U32)


def _op_identity(x, c):
    return x


def _op_scale_f32(x, c):
    return _bitcast_u32(_bitcast_f32(x) * c)


def _op_relu_f32(x, c):
    return _bitcast_u32(jnp.maximum(_bitcast_f32(x), 0.0))


def _op_square_f32(x, c):
    f = _bitcast_f32(x)
    return _bitcast_u32(f * f)


def _op_threshold_mask(x, c):
    f = _bitcast_f32(x)
    return _bitcast_u32(jnp.where(f > c, f, 0.0))


def _op_delay_filter_u32(x, c):
    # DelayedFlights: records are (rows,16) u32 with word 1 = delay minutes;
    # keep the record (dense mask) iff delay > c.
    delay = x[:, 1:2].astype(jnp.int32)
    keep = delay > jnp.int32(c)
    return jnp.where(keep, x, jnp.zeros_like(x))


OPS: Dict[str, Callable] = {
    "identity": _op_identity,
    "scale_f32": _op_scale_f32,
    "relu_f32": _op_relu_f32,
    "square_f32": _op_square_f32,
    "threshold_mask": _op_threshold_mask,
    "delay_filter_u32": _op_delay_filter_u32,
}


def _enclave_rows_kernel(kin_ref, kout_ref, nonce_ref, ctr_ref,
                         nonce_out_ref, ctr_out_ref, data_ref,
                         out_ref, *, op: str, const: float):
    """Per-row (key, nonce, counter) variant: the window-batched executor.

    Every VMEM row is one cipher block carrying its own key/nonce/counter
    columns, so a whole window of chunks (each chunk = a run of rows
    sharing its nonce, counters 1..n_blocks) is ONE grid sweep — the
    batched sibling of ``_enclave_kernel``, with the same VMEM-confined
    plaintext guarantee: decrypt, operator, re-encrypt never leave the
    tile.  The outbound keystream has its own (nonce, counter) columns:
    in steady state they equal the inbound ones, but a fault-tolerant
    re-execution must re-seal under a FRESH counter block (the inbound
    coordinates were already spent on ``kout`` by the first dispatch),
    so the re-encrypt coordinates are independent inputs.
    """
    kin = [kin_ref[:, i] for i in range(8)]        # 8 x (rows,)
    kout = [kout_ref[:, i] for i in range(8)]
    nonce = [nonce_ref[:, i] for i in range(3)]    # 3 x (rows,)
    counters = ctr_ref[:, 0]                       # (rows,)
    nonce_out = [nonce_out_ref[:, i] for i in range(3)]
    counters_out = ctr_out_ref[:, 0]

    # ---- decrypt (plaintext exists only from here ...)
    ks_in = keystream_vectors(kin, nonce, counters)
    pt = data_ref[...] ^ jnp.stack(ks_in, axis=-1)
    # ---- the enclaved operator
    y = OPS[op](pt, const)
    # ---- re-encrypt (... to here — never written to HBM)
    ks_out = keystream_vectors(kout, nonce_out, counters_out)
    out_ref[...] = y ^ jnp.stack(ks_out, axis=-1)


@functools.partial(jax.jit, static_argnames=("op", "const", "block_rows",
                                             "interpret"))
def enclave_apply_rows(keys_in: jax.Array, keys_out: jax.Array,
                       nonces: jax.Array, counters: jax.Array,
                       data_rows: jax.Array, *, op: str = "identity",
                       const: float = 0.0, block_rows: int = 256,
                       interpret: bool = True,
                       nonces_out: jax.Array = None,
                       counters_out: jax.Array = None) -> jax.Array:
    """Apply ``op`` to ciphertext rows with per-row cipher parameters.

    data_rows: (R, 16) u32 ciphertext; keys_in/keys_out: (R, 8) u32;
    nonces: (R, 3) u32; counters: (R,) u32.  R % block_rows == 0.  Row r
    is decrypted under (keys_in[r], nonces[r], counters[r]), transformed,
    and re-encrypted under keys_out[r] at the same (nonce, counter) —
    unless ``nonces_out``/``counters_out`` are given, in which case the
    re-encrypt uses those coordinates instead (the fault-tolerance
    replay path: a retried row must never re-spend a (key, nonce,
    counter) triple already used on the outbound key).  Counters enter
    the kernel as (R, 1) columns, as in ``chacha20_xor_rows``.
    """
    R = data_rows.shape[0]
    assert R % block_rows == 0, (R, block_rows)
    if nonces_out is None:
        nonces_out = nonces
    if counters_out is None:
        counters_out = counters
    grid = (R // block_rows,)
    return pl.pallas_call(
        functools.partial(_enclave_rows_kernel, op=op, const=const),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, 8), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 8), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 3), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 3), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 16), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, 16), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(data_rows.shape, U32),
        interpret=interpret,
    )(keys_in.astype(U32), keys_out.astype(U32), nonces.astype(U32),
      counters.astype(U32).reshape(R, 1), nonces_out.astype(U32),
      counters_out.astype(U32).reshape(R, 1), data_rows)


def _enclave_kernel(kin_ref, kout_ref, nonce_ref, ctr_ref, data_ref, out_ref,
                    *, op: str, const: float, block_rows: int):
    pid = pl.program_id(0)
    base = ctr_ref[0, 0] + (pid * block_rows).astype(U32)
    counters = base + jax.lax.broadcasted_iota(U32, (block_rows,), 0)
    nonce = [nonce_ref[0, i] for i in range(3)]

    # ---- decrypt (plaintext exists only from here ...)
    ks_in = keystream_vectors([kin_ref[0, i] for i in range(8)], nonce,
                              counters)
    pt = data_ref[...] ^ jnp.stack(ks_in, axis=-1)
    # ---- the enclaved operator
    y = OPS[op](pt, const)
    # ---- re-encrypt (... to here — never written to HBM)
    ks_out = keystream_vectors([kout_ref[0, i] for i in range(8)], nonce,
                               counters)
    out_ref[...] = y ^ jnp.stack(ks_out, axis=-1)


@functools.partial(jax.jit, static_argnames=("op", "const", "block_rows",
                                             "interpret"))
def enclave_apply(key_in: jax.Array, key_out: jax.Array, nonce: jax.Array,
                  counter0, data_blocks: jax.Array, *, op: str = "identity",
                  const: float = 0.0, block_rows: int = 512,
                  interpret: bool = True) -> jax.Array:
    """Apply `op` to AEAD-CTR ciphertext blocks without exposing plaintext.

    data_blocks: (N, 16) u32 ciphertext under (key_in, nonce, counter0).
    Returns ciphertext of op(plaintext) under (key_out, nonce, counter0).
    """
    N = data_blocks.shape[0]
    assert N % block_rows == 0, (N, block_rows)
    grid = (N // block_rows,)
    return pl.pallas_call(
        functools.partial(_enclave_kernel, op=op, const=const,
                          block_rows=block_rows),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 8), lambda i: (0, 0)),
            pl.BlockSpec((1, 8), lambda i: (0, 0)),
            pl.BlockSpec((1, 3), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((block_rows, 16), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, 16), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(data_blocks.shape, U32),
        interpret=interpret,
    )(key_in.reshape(1, 8).astype(U32), key_out.reshape(1, 8).astype(U32),
      nonce.reshape(1, 3).astype(U32), jnp.asarray(counter0, U32).reshape(1, 1),
      data_blocks)
