"""AEAD over uint32 word streams: ChaCha20-CTR + CW-MAC (encrypt-then-MAC).

Mirrors the ChaCha20-Poly1305 construction: the MAC keys (r1,s1,r2,s2) are
derived from keystream block 0 (counter=0); payload encryption starts at
counter=1.  ``seal``/``open_`` operate on flat uint32 arrays and derive the
MAC-key block and the payload keystream from ONE ChaCha20 pass over
counters 0..N (a single fused ``chacha20_block`` invocation, not two
separate keystream passes).  The chunked stream layer (repro.core) handles
byte framing and per-chunk nonces.

Batched fast path: :func:`seal_many` / :func:`open_many` process a whole
(B, n_words) batch in one compiled program, dispatching to the Pallas
``kernels/chacha20`` + ``kernels/cwmac`` backends (interpret on CPU,
compiled on TPU) with the pure-jnp reference as oracle/fallback.  Compiled
programs are held in a shape-keyed cache — every window of a stream
reuses identical (B, n_words) shapes, so one compile amortizes over all
subsequent windows (:func:`fastpath_stats` exposes the hit/compile
counters).  :func:`seal_words` / :func:`open_words` are the same bodies
for code already being traced, such as a mesh shard sealing its own rows
inside ``secure_exchange``'s ``shard_map``.
"""
from __future__ import annotations

import functools
from collections import OrderedDict
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.crypto import chacha20, cwmac
from repro.obs.metrics import REGISTRY as _METRICS

U32 = jnp.uint32
P31 = np.uint32(0x7FFFFFFF)


def _clamp(w: jax.Array) -> jax.Array:
    return jnp.minimum(w & P31, P31 - np.uint32(1))


def derive_mac_keys(key: jax.Array, nonce: jax.Array) -> Tuple[jax.Array, ...]:
    """(r1, s1, r2, s2) from keystream block 0, clamped below 2^31-1."""
    blk = chacha20.chacha20_block(key, nonce,
                                  jnp.zeros((1,), U32))[0]  # (16,) u32
    return _clamp(blk[0]), _clamp(blk[1]), _clamp(blk[2]), _clamp(blk[3])


def _fused_stream(key: jax.Array, nonce: jax.Array, n_words: int
                  ) -> Tuple[Tuple[jax.Array, ...], jax.Array]:
    """MAC keys + payload keystream from ONE pass over counters 0..N."""
    n_blocks = (n_words + 15) // 16
    blks = chacha20.chacha20_block(
        key, nonce, jnp.arange(n_blocks + 1, dtype=U32))  # (n_blocks+1, 16)
    mk = tuple(_clamp(blks[0, i]) for i in range(4))
    ks = blks[1:].reshape(-1)[:n_words]
    return mk, ks


def seal(key: jax.Array, nonce: jax.Array,
         plaintext: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """-> (ciphertext (N,) u32, tag (2,) u32)."""
    (r1, s1, r2, s2), ks = _fused_stream(key, nonce, plaintext.shape[0])
    ct = plaintext ^ ks
    tag = cwmac.mac2(ct, r1, s1, r2, s2)
    return ct, tag


def open_(key: jax.Array, nonce: jax.Array, ciphertext: jax.Array,
          tag: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """-> (plaintext, ok: bool scalar). Constant-shape (jit-safe): the caller
    decides what to do with ok=False (the stream layer drops the chunk)."""
    (r1, s1, r2, s2), ks = _fused_stream(key, nonce, ciphertext.shape[0])
    expect = cwmac.mac2(ciphertext, r1, s1, r2, s2)
    ok = jnp.all(expect == tag)
    return ciphertext ^ ks, ok


# ---------------------------------------------------------------------------
# batched fast path: one compiled program per (B, n_words) shape
# ---------------------------------------------------------------------------

BACKENDS = ("pallas", "jnp")
_DEFAULT_BACKEND = "pallas"

_COMPILE_CACHE: "OrderedDict[Tuple, Any]" = OrderedDict()
_COMPILE_CACHE_MAX = 64
# registered instruments (repro.obs.metrics) — fastpath_stats()/reset_*
# below are the legacy shims over these
_FP_COMPILES = _METRICS.counter("aead.fastpath.compiles")
_FP_HITS = _METRICS.counter("aead.fastpath.hits")
# every call below launches exactly ONE cached compiled program, so the
# dispatch counters increment here in the eager wrappers — never inside
# traced code, where an inc() fires once at trace time and disappears
_DISPATCHES = _METRICS.counter("device.dispatches")
_DISP_SEAL = _METRICS.counter("device.dispatches.aead.seal_many")
_DISP_OPEN = _METRICS.counter("device.dispatches.aead.open_many")


def _resolve_backend(backend: Optional[str]) -> str:
    backend = backend or _DEFAULT_BACKEND
    if backend not in BACKENDS:
        raise ValueError(f"unknown AEAD backend {backend!r}; "
                         f"expected one of {BACKENDS}")
    return backend


def _batch_rows(key: jax.Array, nonces: jax.Array, payload: jax.Array
                ) -> Tuple[jax.Array, jax.Array, jax.Array, int]:
    """Flatten a (B, n) batch into per-block rows covering counters 0..N.

    Row (b, 0) carries zeros (its XOR output is raw keystream block 0, the
    MAC-key block); rows (b, 1..N) carry the payload blocks.  The whole
    batch is then ONE row-parallel cipher invocation.
    """
    B, n = payload.shape
    n_blocks = (n + 15) // 16
    R = n_blocks + 1
    data = jnp.pad(payload.astype(U32), ((0, 0), (0, n_blocks * 16 - n)))
    rows = jnp.concatenate([jnp.zeros((B, 1, 16), U32),
                            data.reshape(B, n_blocks, 16)], axis=1)
    counters = jnp.tile(jnp.arange(R, dtype=U32), B)
    row_nonces = jnp.repeat(nonces.astype(U32), R, axis=0)
    row_keys = key.astype(U32) if key.ndim == 1 \
        else jnp.repeat(key.astype(U32), R, axis=0)
    return row_keys, row_nonces, rows.reshape(B * R, 16), counters


def _cipher_pass(key, nonces, payload, backend):
    """-> (mac_keys (B, 4) clamped, payload ^ keystream (B, n))."""
    B, n = payload.shape
    row_keys, row_nonces, rows, counters = _batch_rows(key, nonces, payload)
    if backend == "pallas":
        from repro.kernels.chacha20 import ops as chacha_ops
        out = chacha_ops.xor_rows(row_keys, row_nonces, counters, rows)
    else:
        if row_keys.ndim == 1:
            row_keys = jnp.broadcast_to(row_keys[None, :],
                                        (rows.shape[0], 8))
        out = rows ^ chacha20.chacha20_block_rows(row_keys, row_nonces,
                                                  counters)
    out = out.reshape(B, -1, 16)
    mk = _clamp(out[:, 0, :4])
    return mk, out[:, 1:, :].reshape(B, -1)[:, :n]


def _mac2_batch(words, mk, backend):
    if backend == "pallas":
        from repro.kernels.cwmac import ops as cwmac_ops
        return cwmac_ops.mac2_batch(words, mk[:, 0], mk[:, 1],
                                    mk[:, 2], mk[:, 3])
    return cwmac.mac2_batch(words, mk[:, 0], mk[:, 1], mk[:, 2], mk[:, 3])


def seal_words(key, nonces, words, *, backend=_DEFAULT_BACKEND):
    """Traceable body of :func:`seal_many`, for code that is already
    inside a jit or shard_map (a mesh shard sealing its own rows).  It
    launches nothing itself, so it counts no dispatch."""
    mk, ct = _cipher_pass(key, nonces, words, backend)
    return ct, _mac2_batch(ct, mk, backend)


def open_words(key, nonces, cts, tags, *, backend=_DEFAULT_BACKEND):
    """Traceable body of :func:`open_many` (see :func:`seal_words`)."""
    mk, pt = _cipher_pass(key, nonces, cts, backend)
    expect = _mac2_batch(cts, mk, backend)
    return pt, jnp.all(expect == tags, axis=-1)


@jax.jit
def mac_keys_rows(key, nonces):
    """(B, 4) clamped CW-MAC keys from keystream block 0 of each row —
    the batched form of :func:`derive_mac_keys` (one rolled ChaCha pass).
    ``key``: (8,) shared or (B, 8) per-item; ``nonces``: (B, 3).  The
    enclave hop program MACs ciphertext with it outside the fused kernel
    (ciphertext is public); jitted, so each hop program reuses one trace
    of its ChaCha rounds."""
    zeros = jnp.zeros((nonces.shape[0],), U32)
    blk = chacha20.chacha20_block_rows(key, nonces, zeros)
    return _clamp(blk[:, :4])


def mac2_words(words, mac_keys, *, backend=_DEFAULT_BACKEND):
    """Dual CW-MAC of (B, n_words) u32 under (B, 4) mac-key rows -> (B, 2)
    tags; traceable, like :func:`mac_keys_rows`."""
    return _mac2_batch(words, mac_keys, backend)


def _cached_program(op: str, B: int, n_words: int, backend: str,
                    per_item_key: bool):
    """Shape-keyed compile cache: one jitted program per batch signature."""
    ck = (op, B, n_words, backend, per_item_key)
    fn = _COMPILE_CACHE.get(ck)
    if fn is None:
        _FP_COMPILES.inc()
        impl = {"seal": seal_words, "open": open_words}[op]
        fn = jax.jit(functools.partial(impl, backend=backend))
        _COMPILE_CACHE[ck] = fn
        while len(_COMPILE_CACHE) > _COMPILE_CACHE_MAX:
            _COMPILE_CACHE.popitem(last=False)
    else:
        _FP_HITS.inc()
        _COMPILE_CACHE.move_to_end(ck)
    return fn


def _check_batch(key, nonces, words, what):
    if words.ndim != 2:
        raise ValueError(f"{what} expects (B, n_words), got {words.shape}")
    if words.dtype != jnp.uint32:
        # dtype is part of a program's signature but NOT of the cache key:
        # admitting non-u32 words would silently retrace behind a "hit"
        raise ValueError(f"{what} expects uint32 words (bitcast 4-byte "
                         f"payloads first), got {words.dtype}")
    if nonces.shape != (words.shape[0], 3):
        raise ValueError(f"{what} expects nonces (B, 3) matching B="
                         f"{words.shape[0]}, got {nonces.shape}")
    if key.shape not in ((8,), (words.shape[0], 8)):
        raise ValueError(f"{what} expects key (8,) or (B, 8), "
                         f"got {key.shape}")


def seal_many(key: jax.Array, nonces: jax.Array, words: jax.Array, *,
              backend: Optional[str] = None
              ) -> Tuple[jax.Array, jax.Array]:
    """Batched AEAD seal: a whole (B, n_words) batch in ONE program.

    ``key``: (8,) u32 shared or (B, 8) per-item keys; ``nonces``: (B, 3);
    ``words``: (B, n_words) u32.  Returns (ct (B, n_words), tags (B, 2)),
    item-wise identical to ``vmap(seal)``.  ``backend``: "pallas" (default;
    interpret on CPU, compiled on TPU) or "jnp" (reference oracle).
    """
    backend = _resolve_backend(backend)
    key, nonces, words = map(jnp.asarray, (key, nonces, words))
    _check_batch(key, nonces, words, "seal_many")
    fn = _cached_program("seal", words.shape[0], words.shape[1], backend,
                         key.ndim == 2)
    _DISPATCHES.inc()
    _DISP_SEAL.inc()
    return fn(key.astype(U32), nonces.astype(U32), words)


def open_many(key: jax.Array, nonces: jax.Array, cts: jax.Array,
              tags: jax.Array, *, backend: Optional[str] = None
              ) -> Tuple[jax.Array, jax.Array]:
    """Batched AEAD open: -> (pt (B, n_words), ok (B,) bool verdicts)."""
    backend = _resolve_backend(backend)
    key, nonces, cts, tags = map(jnp.asarray, (key, nonces, cts, tags))
    _check_batch(key, nonces, cts, "open_many")
    if tags.shape != (cts.shape[0], 2):
        raise ValueError(f"open_many expects tags (B, 2), got {tags.shape}")
    fn = _cached_program("open", cts.shape[0], cts.shape[1], backend,
                         key.ndim == 2)
    _DISPATCHES.inc()
    _DISP_OPEN.inc()
    return fn(key.astype(U32), nonces.astype(U32), cts, tags.astype(U32))


def fastpath_stats() -> Dict[str, int]:
    """Compile-cache counters: ``compiles`` (cache misses -> new programs),
    ``hits`` (shape already compiled), ``cached`` (resident programs).

    Shim over the registered counters ``aead.fastpath.compiles`` /
    ``aead.fastpath.hits`` in :data:`repro.obs.metrics.REGISTRY`.
    """
    return {"compiles": int(_FP_COMPILES.value),
            "hits": int(_FP_HITS.value),
            "cached": len(_COMPILE_CACHE)}


def reset_fastpath_cache() -> None:
    """Drop all cached programs and zero the counters (tests/benchmarks
    that need a genuinely cold cache — recompiles cost ~2 s/shape)."""
    _COMPILE_CACHE.clear()
    _FP_COMPILES.reset()
    _FP_HITS.reset()


def reset_fastpath_stats() -> None:
    """Zero the hit/compile counters but KEEP the compiled programs —
    enough for order-independent cache-hit assertions without re-paying
    warm compiles (the per-module test fixture)."""
    _FP_COMPILES.reset()
    _FP_HITS.reset()


# ---------------------------------------------------------------------------
# dtype framing helpers (tensors <-> uint32 words)
# ---------------------------------------------------------------------------


def tensor_to_words(x: jax.Array) -> Tuple[jax.Array, Tuple]:
    """Bit-cast any tensor to a flat uint32 word array (padded to 4 bytes)."""
    raw = jax.lax.bitcast_convert_type(x.reshape(-1), jnp.uint8).reshape(-1) \
        if x.dtype != jnp.uint32 else x.reshape(-1)
    if x.dtype == jnp.uint32:
        return raw, (x.shape, str(x.dtype), 0)
    pad = (-raw.shape[0]) % 4
    raw = jnp.pad(raw, (0, pad))
    words = jax.lax.bitcast_convert_type(raw.reshape(-1, 4), jnp.uint32)
    return words.reshape(-1), (x.shape, str(x.dtype), pad)


def words_to_tensor(words: jax.Array, meta: Tuple) -> jax.Array:
    """Inverse of :func:`tensor_to_words`: rebuild the original tensor
    from its flat u32 words and framing ``meta`` (shape, dtype, pad)."""
    shape, dtype, pad = meta
    if dtype == "uint32":
        return words.reshape(shape)
    raw = jax.lax.bitcast_convert_type(words.reshape(-1, 1),
                                       jnp.uint8).reshape(-1)
    if pad:
        raw = raw[:-pad]
    n = np.prod(shape, dtype=np.int64) if shape else 1
    itemsize = jnp.dtype(dtype).itemsize
    flat = jax.lax.bitcast_convert_type(
        raw.reshape(int(n), itemsize), jnp.dtype(dtype)).reshape(shape)
    return flat


def tensor_to_words_batch(x: jax.Array) -> Tuple[jax.Array, Tuple]:
    """(B, *item) tensor batch -> ((B, n_words) u32, meta).

    Row b carries exactly the words ``tensor_to_words(x[b])`` would — the
    batch form exists so :func:`seal_many` can frame B same-shape tensors
    without B separate dispatches.
    """
    B = x.shape[0]
    item_shape = x.shape[1:]
    if x.dtype == jnp.uint32:
        return x.reshape(B, -1), (item_shape, "uint32", 0)
    raw = jax.lax.bitcast_convert_type(x.reshape(B, -1),
                                       jnp.uint8).reshape(B, -1)
    pad = (-raw.shape[1]) % 4
    raw = jnp.pad(raw, ((0, 0), (0, pad)))
    words = jax.lax.bitcast_convert_type(raw.reshape(B, -1, 4), jnp.uint32)
    return words, (item_shape, str(x.dtype), pad)


def words_to_tensor_batch(words: jax.Array, meta: Tuple) -> jax.Array:
    """Inverse of :func:`tensor_to_words_batch`: (B, n_words) -> (B, *item)."""
    item_shape, dtype, pad = meta
    B = words.shape[0]
    if dtype == "uint32":
        return words.reshape((B,) + tuple(item_shape))
    raw = jax.lax.bitcast_convert_type(words.reshape(B, -1, 1),
                                       jnp.uint8).reshape(B, -1)
    if pad:
        raw = raw[:, :-pad]
    itemsize = jnp.dtype(dtype).itemsize
    flat = jax.lax.bitcast_convert_type(
        raw.reshape(B, -1, itemsize), jnp.dtype(dtype))
    return flat.reshape((B,) + tuple(item_shape))
