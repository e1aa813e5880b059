"""Secure sharded collectives: the ZeroMQ shuffler as encrypted all_to_all.

The paper's map->reduce boundary is a keyed shuffle over TLS links between
workers.  On a mesh the workers are shards of an axis and the shuffle is
one ``all_to_all``; the TLS link becomes an AEAD seal applied *before* the
collective, so the ICI/DCN wire only ever carries ChaCha20 ciphertext and
CW-MAC tags, and each destination shard verifies every block it receives.

Layout convention ("mailbox"): a routed tensor has shape (W, W, ...) with
``x[i, j]`` the sub-block worker i sends to worker j; :func:`exchange`
returns the inbox view ``y[j, i] = x[i, j]``.  Nonces are derived from
``(step, src, dst)`` so no (key, nonce) pair is ever reused across shards
or rounds.
"""
from __future__ import annotations

import functools
import math
from collections import OrderedDict
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.crypto import aead
from repro.crypto.keys import StageKey
from repro.dist.compat import shard_map
from repro.obs.metrics import REGISTRY as _METRICS
from repro.obs.trace import NULL_TRACER

U32 = jnp.uint32

_NONCE_CACHE: "OrderedDict[Tuple[int, int], jax.Array]" = OrderedDict()
_NONCE_CACHE_MAX = 32


@functools.lru_cache(maxsize=8)
def _route_counter_base(W: int) -> np.ndarray:
    """(W*W,) uint64 ``src*W + dst`` grid — the step-independent part."""
    src, dst = np.meshgrid(np.arange(W, dtype=np.uint64),
                           np.arange(W, dtype=np.uint64), indexing="ij")
    # all-uint64 arithmetic: mixing np.uint64 scalars with Python ints
    # promotes to float64 under NumPy 1.x value-based casting
    return (src * np.uint64(W) + dst).reshape(-1)


def _route_nonces_base(W: int, base: int) -> jax.Array:
    """(W*W, 3) nonces for counters ``base + src*W + dst`` of one round.

    Each counter is unique per (key, base, src, dst) as long as the caller
    reserves the whole [base, base + W²) block — no nonce reuse across
    shards or rounds.  The host-side numpy grid is cached per W (and the
    final device array per (W, base)), so repeated rounds pay no
    reconstruction cost.
    """
    ck = (W, int(base))
    hit = _NONCE_CACHE.get(ck)
    if hit is not None:
        _NONCE_CACHE.move_to_end(ck)
        return hit
    c = np.uint64(base) + _route_counter_base(W)
    out = jnp.asarray(np.stack([np.zeros_like(c),
                                c & np.uint64(0xFFFFFFFF),
                                c >> np.uint64(32)],
                               axis=-1).astype(np.uint32))
    _NONCE_CACHE[ck] = out
    while len(_NONCE_CACHE) > _NONCE_CACHE_MAX:
        _NONCE_CACHE.popitem(last=False)
    return out


def _route_nonces(W: int, step: int) -> jax.Array:
    """Legacy step addressing: round ``step`` covers counters
    ``(step*W + src)*W + dst`` — i.e. base ``step * W²``."""
    return _route_nonces_base(W, step * W * W)


def _mailbox_spec(ndim: int, axis: str) -> P:
    return P(axis, *([None] * (ndim - 1)))


def _check_mailbox(x: jax.Array, W: int) -> None:
    if x.ndim < 2 or x.shape[0] != W or x.shape[1] != W:
        raise ValueError(
            f"mailbox layout requires shape (W, W, ...) with W={W}; "
            f"got {x.shape}")


_EXCHANGE_CALLS = _METRICS.counter("dist.exchange_calls")
# one eager exchange() == one launched collective program; counted here,
# next to the legacy per-site counter (never inside the shard_map body)
_DISPATCHES = _METRICS.counter("device.dispatches")
_DISP_EXCHANGE = _METRICS.counter("device.dispatches.dist.exchange")


def exchange_call_count() -> int:
    """Total :func:`exchange` collectives issued (tests/benchmarks assert
    the sealed path costs exactly ONE collective per round).  Shim over
    the registered counter ``dist.exchange_calls``."""
    return int(_EXCHANGE_CALLS.value)


def exchange(x: jax.Array, mesh, axis: str = "model", *,
             tracer=NULL_TRACER) -> jax.Array:
    """Plain all_to_all of mailbox blocks: ``y[j, i] = x[i, j]``."""
    _EXCHANGE_CALLS.inc()
    _DISPATCHES.inc()
    _DISP_EXCHANGE.inc()
    W = int(mesh.shape[axis])
    _check_mailbox(x, W)
    spec = _mailbox_spec(x.ndim, axis)

    def block(xb):  # local (1, W, ...)
        return jax.lax.all_to_all(xb[0], axis, 0, 0, tiled=True)[None]

    with tracer.span("dist.exchange", cat="dispatch", track="dist",
                     W=W, shape=str(tuple(x.shape))):
        return shard_map(block, mesh=mesh, in_specs=spec, out_specs=spec,
                         check_vma=False)(x)


def _resolve_session(key, step: Optional[int],
                     n_counters: int) -> Tuple[StageKey, int]:
    """Resolve (key, base counter) for a round that seals ``n_counters``
    blocks, from a raw StageKey or a KeyDirectory handle.

    With an ``EdgeHandle`` (repro.attest.directory) the key is the edge's
    current-epoch session key and the WHOLE ``n_counters`` block is
    reserved from the directory's per-edge chunk counter — so other
    consumers of the same edge (e.g. ``SecureChannel.protect``) can never
    land inside this round's nonce range, and an epoch rotation resets
    the counter before exhaustion.  An explicit ``step`` is rejected for
    handles: it would bypass the managed counter and collide with a later
    managed allocation (a two-time pad).  A raw StageKey keeps the legacy
    contract: ``step`` is required, addresses a disjoint ``n_counters``-
    sized block per round, and uniqueness is the caller's burden.
    """
    if key is not None and not isinstance(key, StageKey):
        if step is not None:
            raise ValueError(
                "a KeyDirectory edge handle manages its own round "
                "counters; passing an explicit step would collide with a "
                "later managed allocation of the same value (nonce reuse)")
        return key.key(), key.next_counters(n_counters)
    if step is None:
        raise ValueError(
            "secure_exchange requires an explicit per-round step: reusing "
            "a (key, step) pair reuses the ChaCha20 keystream (pass a "
            "KeyDirectory edge handle to get managed counters)")
    return key, step * n_counters


@functools.lru_cache(maxsize=8)
def _sealed_round(mesh, axis: str):
    """One compiled program per (mesh, axis): every shard seals its own
    mailbox row, the packed ct+tags cross the mesh in ONE all_to_all, and
    every shard opens the row it received.

    Sealing and opening run inside the shard_map block because a Pallas
    kernel cannot be partitioned: outside it, a mesh-sharded mailbox
    reaches the cipher kernel as one global array, which the TPU
    compiler refuses.  jit retraces per mailbox shape on its own.
    """
    row = P(axis, None, None)

    def block(kw, words, nonces_out, nonces_in):  # (8,), (1, W, n), (1, W, 3)
        n = words.shape[-1]
        ct, tags = aead.seal_words(kw, nonces_out[0], words[0])
        payload = jnp.concatenate([ct, tags], axis=-1)        # (W, n + 2)
        recv = jax.lax.all_to_all(payload, axis, 0, 0, tiled=True)
        pt, ok = aead.open_words(kw, nonces_in[0], recv[:, :n], recv[:, n:])
        return pt[None], ok[None]

    return jax.jit(shard_map(block, mesh=mesh,
                             in_specs=(P(), row, row, row),
                             out_specs=(row, P(axis, None)),
                             check_vma=False))


def secure_exchange(x: jax.Array, mesh, axis: str = "model", *,
                    key, step: Optional[int] = None, tracer=NULL_TRACER
                    ) -> Tuple[jax.Array, jax.Array]:
    """AEAD-sealed all_to_all: ciphertext + tags cross the wire.

    ``key`` is a KeyDirectory edge handle (preferred — current-epoch
    session key + managed round counters) or a raw StageKey, in which
    case ``step`` is *required* and must be unique per (key, round) —
    reusing it reuses every (key, nonce) pair, i.e. a two-time pad.

    Each (src=i, dst=j) sub-block is sealed with counter
    ``(step*W + i)*W + j`` on the source shard and opened (MAC-checked)
    on the destination shard.  ``x`` must be a 4-byte dtype (words are a
    same-width bitcast).  Returns ``(y, ok)`` with ``y[j, i]`` the opened
    block worker j received from i and ``ok[j, i]`` its MAC verdict.

    The round is ONE compiled program (:func:`_sealed_round`): each shard
    seals its W outgoing blocks with the batched AEAD body
    (:func:`repro.crypto.aead.seal_words`), ciphertext + tags are packed
    into a single payload so the round issues exactly ONE all_to_all, and
    each shard opens its W incoming blocks.  The wire still only ever
    carries ciphertext and MAC tags.
    """
    W = int(mesh.shape[axis])
    key, base = _resolve_session(key, step, W * W)
    _check_mailbox(x, W)
    if x.dtype.itemsize != 4:
        raise ValueError(f"secure_exchange needs a 4-byte dtype, got {x.dtype}")
    blk_shape = x.shape[2:]
    n_words = math.prod(blk_shape) if blk_shape else 1
    kw = jnp.asarray(key.key)

    _EXCHANGE_CALLS.inc()
    _DISPATCHES.inc()
    _DISP_EXCHANGE.inc()
    with tracer.span("dist.secure_exchange", cat="dispatch", track="dist",
                     W=W, n_words=n_words, base_counter=int(base)):
        flat = x.reshape(W, W, n_words)
        words = flat if x.dtype == jnp.uint32 else \
            jax.lax.bitcast_convert_type(flat, jnp.uint32)
        nonces = _route_nonces_base(W, base).reshape(W, W, 3)  # [src, dst]
        # inbox[dst, src] was sealed with the (src, dst) counter
        pt, ok = _sealed_round(mesh, axis)(kw, words, nonces,
                                           nonces.swapaxes(0, 1))
        out = pt if x.dtype == jnp.uint32 else \
            jax.lax.bitcast_convert_type(pt, x.dtype)
        return out.reshape(W, W, *blk_shape), ok


def _consistent_hash(k: jax.Array) -> jax.Array:
    """Cheap integer mix (Knuth multiplicative) for consistent routing."""
    k = k.astype(U32) * U32(0x9E3779B1)
    return k ^ (k >> U32(16))


def keyed_route(x: jax.Array, row_keys: jax.Array, mesh,
                axis: str = "model", *, key=None,
                step: Optional[int] = None, hash_keys: bool = True):
    """The router's ``keyed`` policy as a sharded collective.

    ``x``: (W, n, ...) rows resident shard-wise on ``axis``; ``row_keys``:
    (W, n) integer keys.  Each shard buckets its rows by
    ``hash(key) % W`` (dense, via :func:`repro.core.router.shuffle_by_key`)
    and the buckets cross the mesh through :func:`exchange` — or
    :func:`secure_exchange` when ``key`` is given (a KeyDirectory edge
    handle with managed counters, or a raw StageKey with ``step`` then
    required and unique per round), in which case the wire carries only
    ciphertext:
    the per-bucket row counts ride *inside* the sealed payload so even
    the key-distribution metadata stays hidden.

    Returns ``(inbox, counts, ok)``: ``inbox[j, i]`` = (cap, ...) bucket
    worker j received from i, ``counts[j, i]`` its valid-row count, and
    ``ok`` the per-block MAC verdicts (all-true when unsealed).
    """
    from repro.core.router import shuffle_by_key  # lazy: router imports us

    W = int(mesh.shape[axis])
    if x.shape[0] != W or row_keys.shape[:2] != x.shape[:2]:
        raise ValueError(f"expected x (W={W}, n, ...) and matching keys; "
                         f"got {x.shape} / {row_keys.shape}")

    # shard-local bucketing (eager vmap over the worker dim — on a real
    # mesh this is each shard's local prologue; only the exchange below
    # is a collective program)
    def bucket(xb, kb):  # (n, ...), (n,)
        dest = _consistent_hash(kb) if hash_keys else kb.astype(U32)
        dest = (dest % U32(W)).astype(jnp.int32)
        return shuffle_by_key(xb, dest, W)

    mailbox, counts = jax.vmap(bucket)(x, row_keys)  # (W,W,cap,...), (W,W)

    if key is None:
        inbox = exchange(mailbox, mesh, axis)
        counts_in = exchange(counts[..., None].astype(jnp.int32), mesh,
                             axis)[..., 0]
        return inbox, counts_in, jnp.ones((W, W), bool)

    # sealed path: pack each bucket and its row count into ONE payload so
    # a single (key, step, src, dst) counter covers both — nothing about
    # the key distribution crosses the wire in cleartext.
    if x.dtype.itemsize != 4:
        raise ValueError(f"keyed_route needs a 4-byte dtype, got {x.dtype}")
    data = mailbox.reshape(W, W, -1)
    data_words = data if x.dtype == jnp.uint32 else \
        jax.lax.bitcast_convert_type(data, jnp.uint32)
    payload = jnp.concatenate(
        [data_words, counts[..., None].astype(jnp.uint32)], axis=-1)
    inbox_words, ok = secure_exchange(payload, mesh, axis, key=key, step=step)
    counts_in = inbox_words[..., -1].astype(jnp.int32)
    dw = inbox_words[..., :-1]
    inbox = (dw if x.dtype == jnp.uint32 else
             jax.lax.bitcast_convert_type(dw, x.dtype)
             ).reshape(mailbox.shape)
    return inbox, counts_in, ok
