"""Public op wrappers for the enclave executor kernels."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.enclave_map.enclave_map import (  # noqa: F401
    AGGREGATE_BLOCK_ROWS, AGGREGATE_OPS, OPS, TABLE_OPS, campaign_join_rows,
    enclave_apply, enclave_apply_rows)
from repro.obs.metrics import REGISTRY as _METRICS

# an eager enclave_map call launches exactly one jitted enclave program —
# count it here, in the eager wrapper, never inside the traced kernel.
# The row wrappers below run traced inside the window engine's hop
# program (repro.core.enclave), which counts that program's launch.
_DISPATCHES = _METRICS.counter("device.dispatches")
_DISP_MAP = _METRICS.counter("device.dispatches.enclave_map")


def enclave_map(key_in, key_out, nonce, counter0, data_blocks, *, op,
                const=0.0, block_rows: int = 512):
    _DISPATCHES.inc()
    _DISP_MAP.inc()
    return enclave_apply(key_in, key_out, nonce, counter0, data_blocks,
                         op=op, const=const, block_rows=block_rows,
                         interpret=interpret_mode())


def enclave_map_rows(keys_in, keys_out, nonces, counters, rows, *, op,
                     const=0.0, block_rows: int = 256,
                     nonces_out=None, counters_out=None):
    """Per-row fused decrypt->op->encrypt over (R, 16) u32 rows.

    keys_in/keys_out: (8,) shared or (R, 8) per-row (mixed-epoch windows
    carry per-row keys); nonces: (R, 3); counters: (R,).  Auto-pads R to
    a tile multiple (padded tail rows use zero cipher parameters and are
    sliced off).  One grid sweep processes a whole window of chunks.
    ``nonces_out``/``counters_out`` re-encrypt under separate outbound
    coordinates (fault-tolerant re-execution: the inbound coordinates
    were already spent on the outbound key by the first dispatch).
    Traceable: it counts no launch (see the module's counters).
    """
    R = rows.shape[0]
    kw = {}
    if op in AGGREGATE_OPS:
        # the op reads every row of the call: padded rows must not count
        block_rows, kw["valid_rows"] = AGGREGATE_BLOCK_ROWS, R
    args = _padded_rows(keys_in, keys_out, nonces, counters, rows,
                        nonces_out, counters_out, block_rows)
    out = enclave_apply_rows(*args[:5], op=op, const=const,
                             block_rows=block_rows,
                             interpret=interpret_mode(),
                             nonces_out=args[5], counters_out=args[6], **kw)
    return out[:R]


def enclave_join_rows(keys_in, keys_out, nonces, counters, rows, table,
                      table_key, *, op, block_rows: int = 256,
                      nonces_out=None, counters_out=None):
    """:func:`enclave_map_rows` for a table op (:data:`TABLE_OPS`): the
    rows joined against the sealed ``table`` ((64, 128) u32) opened in
    the kernel under ``table_key`` ((1, 16): key words, then nonce
    words).  Traceable, like :func:`enclave_map_rows`."""
    R = rows.shape[0]
    args = _padded_rows(keys_in, keys_out, nonces, counters, rows,
                        nonces_out, counters_out, block_rows)
    out = campaign_join_rows(*args[:5], table, table_key, op=op,
                             block_rows=block_rows,
                             interpret=interpret_mode(),
                             nonces_out=args[5], counters_out=args[6])
    return out[:R]


def _padded_rows(keys_in, keys_out, nonces, counters, rows, nonces_out,
                 counters_out, block_rows):
    """Per-row cipher operands, every one padded to a tile multiple
    (padded tail rows use zero cipher parameters)."""
    R = rows.shape[0]
    ones = jnp.ones((R, 1), jnp.uint32)
    kin = keys_in.reshape(1, 8) * ones if keys_in.ndim == 1 else keys_in
    kout = keys_out.reshape(1, 8) * ones if keys_out.ndim == 1 else keys_out
    if nonces_out is None:
        nonces_out = nonces
    if counters_out is None:
        counters_out = counters
    pad = (-R) % block_rows
    cols = (kin, kout, nonces, counters, rows, nonces_out, counters_out)
    if not pad:
        return cols
    return tuple(jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                 for a in cols)
