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
* ``ysb_view_filter`` — the Yahoo Streaming Benchmark's view filter
* ``ysb_window_count`` — YSB's count per campaign per event-time window,
  across every row of the call (:data:`AGGREGATE_OPS`)

``ysb_campaign_join`` reads a table as well as its rows, so it runs in a
kernel of its own, :func:`campaign_join_rows` (:data:`TABLE_OPS`): the
table arrives sealed and is opened inside the kernel, next to the rows.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.data import ysb
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


def _word(x, i):
    return x[:, i:i + 1]                           # (rows, 1)


def _op_ysb_view_filter(x, c):
    # YSB: a record passes whole iff its event type is a view
    keep = _word(x, ysb.EVENT_TYPE_WORD) == U32(ysb.VIEW)
    return jnp.where(keep, x, jnp.zeros_like(x))


def join_campaign(x, table):
    """YSB's join: each present row's 128-bit ad id (words 8-11) against
    every slot of the (64, 128) plaintext ``table``
    (:func:`repro.data.ysb.campaign_table`).  A present row keeps words
    13-15 (event type and time) and gets its campaign in word 12, or
    :data:`repro.data.ysb.MISS` when no ad matches; user, page and ad ids
    are projected away.  An empty row (event type 0) stays zero."""
    rows = x.shape[0]
    ad = [_word(x, 8 + j) for j in range(4)]
    best = jnp.full((rows, 128), ysb.MISS, jnp.int32)
    for m in range(8):                             # 128 slots at a time
        hit = ad[0] == table[m:m + 1, :]
        for j in range(1, 4):
            hit = hit & (ad[j] == table[8 * j + m:8 * j + m + 1, :])
        camp = table[32 + m:33 + m, :].astype(jnp.int32)
        best = jnp.minimum(best, jnp.where(hit, camp, ysb.MISS))
    campaign = jnp.min(best, axis=1, keepdims=True).astype(U32)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    y = jnp.where(lane == ysb.CAMPAIGN_WORD, campaign,
                  jnp.where(lane > ysb.CAMPAIGN_WORD, x, jnp.zeros_like(x)))
    present = _word(x, ysb.EVENT_TYPE_WORD) != U32(0)
    return jnp.where(present, y, jnp.zeros_like(x))


def _div_u64(lo, hi, d: int):
    """floor((hi * 2^32 + lo) / d) as (low, high) u32 words, for
    0 < d < 2^16, with 32-bit ops only: long division over 16-bit
    limbs (each partial dividend stays below d * 2^16 < 2^32)."""
    dd = U32(d)
    r = jnp.zeros_like(lo)
    q = []
    for limb in (hi >> 16, hi & U32(0xFFFF), lo >> 16, lo & U32(0xFFFF)):
        cur = (r << 16) | limb
        qi = cur // dd
        r = cur - qi * dd
        q.append(qi)
    return (q[2] << 16) | q[3], (q[0] << 16) | q[1]


def _count_keys(x, window_ms, first_row, valid_rows):
    """The count's key columns of rows ``x`` (rows ``first_row``...):
    (present, window low, window high, campaign), (n, 1) u32 each.  A
    row is present when it holds an event and lies below ``valid_rows``;
    its window is ``event_time // window_ms``."""
    n = x.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0) + first_row
    present = (_word(x, ysb.EVENT_TYPE_WORD) != U32(0)) & (row < valid_rows)
    lo, hi = _div_u64(_word(x, ysb.TIME_LO_WORD),
                      _word(x, ysb.TIME_HI_WORD), int(window_ms))
    return present.astype(U32), lo, hi, _word(x, ysb.CAMPAIGN_WORD)


def _count_row(keys, count, lead):
    """Partial-count rows: (window low, window high, campaign, count) in
    words 0-3 where ``lead``, zero elsewhere."""
    _, lo, hi, camp = keys
    lane = jax.lax.broadcasted_iota(jnp.int32, (lo.shape[0], 16), 1)
    y = jnp.where(lane == 0, lo, jnp.where(lane == 1, hi, jnp.where(
        lane == 2, camp, count.astype(U32))))
    return jnp.where(lead & (lane < 4), y, U32(0))


def _op_ysb_window_count(x, c):
    # YSB's count over every row of the call: the views grouped by
    # (event_time // c, campaign).  The first row of each group becomes
    # its partial count (_count_row); every other row becomes zero.
    # Rows are sorted by key here; the kernel (_count_rows_kernel)
    # compares every pair of rows instead, with the same result.
    rows = x.shape[0]
    present, lo, hi, camp = (k[:, 0] for k in _count_keys(x, c, 0, rows))
    idx = jnp.arange(rows, dtype=jnp.int32)
    order = jnp.lexsort((idx, camp, hi, lo, present == 0))
    present, lo, hi, camp, idx = (a[order]
                                  for a in (present, lo, hi, camp, idx))
    key = jnp.stack([present, lo, hi, camp], axis=1)
    new = jnp.concatenate([jnp.ones((1,), bool),
                           jnp.any(key[1:] != key[:-1], axis=1)])
    group = jnp.cumsum(new) - 1
    count = jax.ops.segment_sum(jnp.ones((rows,), jnp.int32), group,
                                num_segments=rows)[group]
    y = _count_row(tuple(k[:, None] for k in (present, lo, hi, camp)),
                   count[:, None], (new & (present != 0))[:, None])
    return jnp.zeros_like(y).at[idx].set(y)


def _count_rows_kernel(kin_ref, kout_ref, nonce_ref, ctr_ref, nonce_out_ref,
                       ctr_out_ref, data_ref, out_ref, pt_ref, ks_ref,
                       across_ref, *, const: float, valid_rows: int):
    """The count hop over every row of the call.  Each grid step opens
    its block into VMEM (``pt_ref``), keeps its outbound keystream
    (``ks_ref``) and lays its keys across lanes (``across_ref``: key k of
    row 128g + l at [k, g, l]).  The last step compares each row with
    every row, eight rows at a time, and seals the whole result, which is
    one block of the output.  Plaintext never leaves VMEM."""
    step = pl.program_id(0)
    block = data_ref.shape[0]
    rows = out_ref.shape[0]
    first = step * block
    kin = [kin_ref[:, i] for i in range(8)]
    kout = [kout_ref[:, i] for i in range(8)]
    nonce = [nonce_ref[:, i] for i in range(3)]
    nonce_out = [nonce_out_ref[:, i] for i in range(3)]
    pt = data_ref[...] ^ jnp.stack(
        keystream_vectors(kin, nonce, ctr_ref[:, 0]), axis=-1)
    at = pl.multiple_of(first, block)
    pt_ref[pl.ds(at, block), :] = pt
    ks_ref[pl.ds(at, block), :] = jnp.stack(
        keystream_vectors(kout, nonce_out, ctr_out_ref[:, 0]), axis=-1)
    keys = _count_keys(pt, const, first, valid_rows)
    for g in range(block // 128):
        for k, col in enumerate(keys):
            across_ref[k, pl.ds(step * (block // 128) + g, 1), :] = \
                col[128 * g:128 * (g + 1)].reshape(1, 128)

    @pl.when(step == pl.num_programs(0) - 1)
    def _():
        across = [across_ref[k] for k in range(4)]      # (rows // 128, 128)
        j = (jax.lax.broadcasted_iota(jnp.int32, (rows // 128, 128), 0)
             * 128 + jax.lax.broadcasted_iota(jnp.int32,
                                              (rows // 128, 128), 1))

        def eight(b, carry):
            at = pl.multiple_of(b * 8, 8)
            keys = _count_keys(pt_ref[pl.ds(at, 8), :], const, at,
                               valid_rows)
            mine = [jnp.broadcast_to(k, (8, 128)) for k in keys[1:]]
            count = jnp.zeros((8, 128), jnp.int32)
            least = jnp.full((8, 128), rows, jnp.int32)
            for g in range(rows // 128):
                same = across[0][g:g + 1, :] != U32(0)
                for k, m in zip(across[1:], mine):
                    same = same & (m == k[g:g + 1, :])
                count = count + same.astype(jnp.int32)
                least = jnp.minimum(least,
                                    jnp.where(same, j[g:g + 1, :], rows))
            count = jnp.sum(count, axis=1, keepdims=True)
            least = jnp.min(least, axis=1, keepdims=True)
            row = jax.lax.broadcasted_iota(jnp.int32, (8, 1), 0) + at
            lead = (keys[0] != U32(0)) & (least == row)
            out_ref[pl.ds(at, 8), :] = (_count_row(keys, count, lead)
                                        ^ ks_ref[pl.ds(at, 8), :])
            return carry

        jax.lax.fori_loop(0, rows // 8, eight, 0)


OPS: Dict[str, Callable] = {
    "identity": _op_identity,
    "scale_f32": _op_scale_f32,
    "relu_f32": _op_relu_f32,
    "square_f32": _op_square_f32,
    "threshold_mask": _op_threshold_mask,
    "delay_filter_u32": _op_delay_filter_u32,
    "ysb_view_filter": _op_ysb_view_filter,
    "ysb_window_count": _op_ysb_window_count,
}

#: ops that read a table: name -> (fn(rows, plaintext table), the table
#: that the stage's const names)
TABLE_OPS: Dict[str, Tuple[Callable, Callable]] = {
    "ysb_campaign_join": (join_campaign,
                          lambda const: ysb.campaign_table(int(const))),
}

# rows per grid step of an aggregate op (its output is one block), and
# the VMEM it may use: its opened rows, their outbound keystream and the
# output, each (rows, 16) u32 (v5e has 128 MiB)
AGGREGATE_BLOCK_ROWS = 256
_AGGREGATE_VMEM_BYTES = 64 * 1024 * 1024


def _enclave_rows_kernel(kin_ref, kout_ref, nonce_ref, ctr_ref,
                         nonce_out_ref, ctr_out_ref, data_ref,
                         *rest, op: str, const: float):
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

    A table op (:data:`TABLE_OPS`) gets two refs more before ``out_ref``:
    its sealed table and the table's key, opened here in VMEM next to the
    rows (:func:`_open_table`).
    """
    out_ref = rest[-1]
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
    if op in TABLE_OPS:
        y = TABLE_OPS[op][0](pt, _open_table(*rest[:2]))
    else:
        y = OPS[op](pt, const)
    # ---- re-encrypt (... to here — never written to HBM)
    ks_out = keystream_vectors(kout, nonce_out, counters_out)
    out_ref[...] = y ^ jnp.stack(ks_out, axis=-1)


@functools.partial(jax.jit, static_argnames=("op", "const", "block_rows",
                                             "interpret", "valid_rows"))
def enclave_apply_rows(keys_in: jax.Array, keys_out: jax.Array,
                       nonces: jax.Array, counters: jax.Array,
                       data_rows: jax.Array, *, op: str = "identity",
                       const: float = 0.0, block_rows: int = 256,
                       interpret: bool = True,
                       nonces_out: jax.Array = None,
                       counters_out: jax.Array = None,
                       valid_rows: int = None) -> jax.Array:
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

    An op of :data:`AGGREGATE_OPS` reads every row of the call: only the
    first ``valid_rows`` (all when None) count, and ``block_rows`` must
    be a multiple of 128.
    """
    R = data_rows.shape[0]
    assert R % block_rows == 0, (R, block_rows)
    if nonces_out is None:
        nonces_out = nonces
    if counters_out is None:
        counters_out = counters
    if op in AGGREGATE_OPS:
        return AGGREGATE_OPS[op](const, block_rows, interpret, valid_rows,
                                 keys_in, keys_out, nonces, counters,
                                 nonces_out, counters_out, data_rows)
    return pl.pallas_call(
        functools.partial(_enclave_rows_kernel, op=op, const=const),
        grid=(R // block_rows,),
        in_specs=_row_specs(block_rows),
        out_specs=pl.BlockSpec((block_rows, 16), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(data_rows.shape, U32),
        interpret=interpret,
    )(*_row_operands(keys_in, keys_out, nonces, counters, nonces_out,
                     counters_out, data_rows))


def _count_rows(const, block_rows, interpret, valid_rows, *args):
    """The call of YSB's count (``ysb_window_count``, the rows' YSB word
    layout): the grid walks the rows, and the output is one block,
    written at the last step (:func:`_count_rows_kernel`).

    Its limits: one call counts one worker share, whose opened rows,
    their outbound keystream, their keys and the output all sit in VMEM
    at once (208 B a row, so a share of at most about 300 000 rows under
    :data:`_AGGREGATE_VMEM_BYTES`); and it compares every pair of rows,
    so its cost grows with the square of the share (8192 rows: about
    0.6 ms on a TPU v5e)."""
    R = args[-1].shape[0]
    assert block_rows % 128 == 0, block_rows
    return pl.pallas_call(
        functools.partial(_count_rows_kernel, const=const,
                          valid_rows=R if valid_rows is None
                          else valid_rows),
        grid=(R // block_rows,),
        in_specs=_row_specs(block_rows),
        out_specs=pl.BlockSpec((R, 16), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((R, 16), U32),
        scratch_shapes=[pltpu.VMEM((R, 16), U32), pltpu.VMEM((R, 16), U32),
                        pltpu.VMEM((4, R // 128, 128), U32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_AGGREGATE_VMEM_BYTES),
        interpret=interpret,
    )(*_row_operands(*args))


#: ops that combine rows, each mapped to its own call: one tile covers
#: every row of the call, so the result is per call (per worker share),
#: and the sink adds the calls up
AGGREGATE_OPS: Dict[str, Callable] = {"ysb_window_count": _count_rows}


def _row_specs(block_rows: int):
    """Block specs of the per-row cipher operands and the rows."""
    return [pl.BlockSpec((block_rows, cols), lambda i: (i, 0))
            for cols in (8, 8, 3, 1, 3, 1, 16)]


def _row_operands(keys_in, keys_out, nonces, counters, nonces_out,
                  counters_out, data_rows):
    R = data_rows.shape[0]
    return (keys_in.astype(U32), keys_out.astype(U32), nonces.astype(U32),
            counters.astype(U32).reshape(R, 1), nonces_out.astype(U32),
            counters_out.astype(U32).reshape(R, 1), data_rows)


def _open_table(table_ref, key_ref):
    """The sealed (64, 128) table opened in VMEM: table slot ``k`` (lane
    ``k % 128`` of rows ``8r + k // 128``) is word ``r`` of ChaCha20 block
    ``k + 1`` under the table's key and nonce (``key_ref``, (1, 16):
    key words 0-7, nonce words 8-10)."""
    shape = (8, 128)
    counters = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * 128
                + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
                + 1).astype(U32)
    ks = keystream_vectors([key_ref[:, i:i + 1] for i in range(8)],
                           [key_ref[:, 8 + i:9 + i] for i in range(3)],
                           counters)
    return jnp.concatenate([table_ref[8 * r:8 * r + 8, :] ^ ks[r]
                            for r in range(8)], axis=0)


@functools.partial(jax.jit, static_argnames=("op", "block_rows",
                                             "interpret"))
def campaign_join_rows(keys_in, keys_out, nonces, counters, data_rows,
                       table, table_key, *, op: str = "ysb_campaign_join",
                       block_rows: int = 256, interpret: bool = True,
                       nonces_out=None, counters_out=None):
    """:func:`enclave_apply_rows` for a table op: ``table`` is the (64,
    128) u32 sealed table and ``table_key`` its (1, 16) key and nonce
    (see :func:`_open_table`); both are the same block at every step."""
    R = data_rows.shape[0]
    assert R % block_rows == 0, (R, block_rows)
    if nonces_out is None:
        nonces_out = nonces
    if counters_out is None:
        counters_out = counters
    return pl.pallas_call(
        functools.partial(_enclave_rows_kernel, op=op, const=0.0),
        grid=(R // block_rows,),
        in_specs=_row_specs(block_rows) + [
            pl.BlockSpec((64, 128), lambda i: (0, 0)),
            pl.BlockSpec((1, 16), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((block_rows, 16), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(data_rows.shape, U32),
        interpret=interpret,
    )(*_row_operands(keys_in, keys_out, nonces, counters, nonces_out,
                     counters_out, data_rows),
      table.astype(U32), table_key.astype(U32))


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
