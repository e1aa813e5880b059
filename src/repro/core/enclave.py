"""Enclave executor: runs operators under one of the paper's three modes.

Fig. 6 of the paper compares three deployments; they map here to:

* ``plain``     — operator on cleartext chunks (baseline, unsafe);
* ``encrypted`` — AEAD decrypt -> operator -> AEAD encrypt as *separate* XLA
  ops: ciphertext on the wire/at rest, but plaintext transits HBM during
  compute (paper: "encrypted data but skip the enclaves" — trusts the
  operator);
* ``enclave``   — the fused Pallas kernel (repro.kernels.enclave_map):
  plaintext exists only in VMEM inside the kernel, HBM sees ciphertext
  end-to-end.  Operators must come from the static registry (the paper's
  no-dynamic-linking constraint, §4).

Integrity: every chunk carries a CW-MAC tag; ``open`` failures surface as
dropped chunks + an error count (reactive ``on_error``).

Window batching: the streaming engine's unit of device work is a window
of chunks, not a chunk.  :meth:`EnclaveExecutor.run_many` /
:meth:`EnclaveExecutor.run_static_many` open a whole window with
``aead.open_many``, apply the stage operator ONCE across the batch, and
re-seal with ``aead.seal_many`` (enclave mode rides the batched
``enclave_map_rows`` grid kernel, so plaintext stays VMEM-confined per
row).  MAC verdicts are **deferred**: the batched entry points return a
per-row device verdict vector without a host sync — the pipeline syncs
once per window and drops failed rows there.  Mixed-epoch windows (a
window straddling a ``rekey_every_n`` flip) resolve per-row keys, so
rows never cross keystreams.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.crypto import aead, chacha20, cwmac
from repro.crypto.keys import StageKey, current_epoch as _cur_epoch, \
    resolve_key as _key_at
from repro.kernels.enclave_map import ops as enclave_ops
from repro.obs.metrics import REGISTRY as _METRICS
from repro.obs.trace import NULL_TRACER

# the scalar enclave path launches cwmac.mac2 eagerly (ciphertext MACs
# happen OUTSIDE the fused kernel); those launches are counted here at
# the call sites — cwmac.mac2 itself also runs traced inside sealed
# programs, where a counter would only fire at trace time
_DISPATCHES = _METRICS.counter("device.dispatches")
_DISP_CWMAC = _METRICS.counter("device.dispatches.cwmac.mac2")
# record rows that enter an aggregate op's hop (every row of the share,
# sealed or not: the host cannot tell an empty row from a record)
_AGG_ROWS_IN = _METRICS.counter("aggregate.rows_in")

U32 = jnp.uint32


@dataclass
class SealedChunk:
    """Fixed-shape ciphertext unit flowing between stages."""
    blocks: jax.Array             # (N, 16) u32 ciphertext (or plaintext words
                                  # in plain mode)
    tag: Optional[jax.Array]      # (2,) u32 CW-MAC or None
    counter: int                  # per-stream chunk counter -> nonce
    meta: Tuple                   # tensor framing (shape, dtype, pad)
    n_words: int                  # valid words before block padding
    epoch: int = 0                # key epoch assigned at ingress; every
                                  # edge seals this chunk under ITS epoch
                                  # (counters are epoch-local — resealing
                                  # under a later epoch would reuse that
                                  # epoch's (key, nonce) pairs)


def _words_to_blocks(words: jax.Array) -> Tuple[jax.Array, int]:
    n = words.shape[0]
    n_blocks = (n + 15) // 16
    padded = jnp.pad(words, (0, n_blocks * 16 - n))
    return padded.reshape(n_blocks, 16), n


def seal_tensor(key, counter: int, x: jax.Array,
                epoch: Optional[int] = None) -> SealedChunk:
    """Seal under ``key`` at ``epoch`` (the handle's current epoch when
    None — ingress; executors pass the chunk's own epoch through)."""
    if epoch is None:
        epoch = _cur_epoch(key)
    k = _key_at(key, epoch)
    words, meta = aead.tensor_to_words(x)
    nonce = jnp.asarray(k.nonce(counter))
    ct, tag = aead.seal(jnp.asarray(k.key), nonce, words)
    blocks, n = _words_to_blocks(ct)
    return SealedChunk(blocks=blocks, tag=tag, counter=counter, meta=meta,
                       n_words=n, epoch=epoch)


def open_tensor(key, chunk: SealedChunk) -> Tuple[jax.Array, jax.Array]:
    k = _key_at(key, chunk.epoch)
    nonce = jnp.asarray(k.nonce(chunk.counter))
    ct = chunk.blocks.reshape(-1)[:chunk.n_words]
    pt, ok = aead.open_(jnp.asarray(k.key), nonce, ct, chunk.tag)
    return aead.words_to_tensor(pt, chunk.meta), ok


@dataclass
class SealedWindow:
    """A batch of same-framing sealed chunks kept as ONE pair of device
    arrays — the streaming engine's unit of flow.

    Keeping the window batched end to end is what makes the engine fast
    on top of the batched AEAD primitives: rows are never re-split into
    per-chunk device arrays between stages (per-row slicing costs one
    eager dispatch per row per hop), only gathered at worker fan-out and
    materialized at the sink.  ``counters``/``epochs`` are host-side
    per-row metadata; a window straddling a rekey flip simply carries
    mixed ``epochs`` and is opened with per-row keys.  ``window_id`` is
    the ingress window's sequence number in its ``run()``, host-side
    metadata that the engine's trace spans carry (``window=``); it
    survives ``select``, the merge and egress.
    """
    words: jax.Array              # (B, n_words) u32 payload rows (ct, or
                                  # plaintext words in plain mode)
    tags: Optional[jax.Array]     # (B, 2) u32 CW-MAC tags or None
    counters: List[int]           # per-row chunk counters -> nonces
    epochs: List[int]             # per-row ingress epochs
    meta: Tuple                   # shared tensor framing (shape, dtype, pad)
    n_words: int
    window_id: int = 0            # ingress window sequence number

    def __len__(self) -> int:
        return len(self.counters)

    def select(self, idxs: Sequence[int]) -> "SealedWindow":
        """Row-gather a sub-window (ONE device gather per array)."""
        idx = jnp.asarray(np.asarray(idxs, np.int32))
        return SealedWindow(
            words=self.words[idx],
            tags=None if self.tags is None else self.tags[idx],
            counters=[self.counters[i] for i in idxs],
            epochs=[self.epochs[i] for i in idxs],
            meta=self.meta, n_words=self.n_words, window_id=self.window_id)


@dataclass(frozen=True)
class SealedTable:
    """A table operand (``repro.kernels.enclave_map.TABLE_OPS``) sealed on
    the device: ``words`` is the (64, 128) u32 table XORed with ChaCha20
    (slot ``k`` of table row ``8r + k // 128`` takes word ``r`` of block
    ``k + 1``), the layout in which the join kernel opens it in VMEM;
    ``key_block`` is (1, 16) u32, the key words then the nonce words."""
    words: jax.Array
    key_block: jax.Array


def _table_keystream(key_block: jax.Array) -> jax.Array:
    n = 1024
    ks = chacha20.chacha20_block_rows(
        key_block[0, :8], jnp.broadcast_to(key_block[0, 8:11], (n, 3)),
        jnp.arange(1, n + 1, dtype=U32))
    return ks[:, :8].T.reshape(64, 128)


def seal_table(key: StageKey, counter: int, table) -> SealedTable:
    """Seal a (64, 128) u32 table onto the device under ``key`` at the
    nonce of ``counter`` (a counter of the table's own session)."""
    block = np.zeros((1, 16), np.uint32)
    block[0, :8], block[0, 8:11] = key.key, key.nonce(counter)
    block = jnp.asarray(block)
    return SealedTable(words=jnp.asarray(table, U32)
                       ^ _table_keystream(block), key_block=block)


def open_table(table: SealedTable) -> jax.Array:
    """The plaintext table, as XLA ops (``encrypted`` mode's trust)."""
    return table.words ^ _table_keystream(table.key_block)


def _hop_span(op: str) -> str:
    """The span of a hop's host work: ``enclave.join`` for a table op,
    ``enclave.count`` for an aggregate op, else ``enclave.op``."""
    if op in enclave_ops.TABLE_OPS:
        return "enclave.join"
    if op in enclave_ops.AGGREGATE_OPS:
        return "enclave.count"
    return "enclave.op"


def _blocks_batch(words: jax.Array) -> jax.Array:
    """(B, n_words) u32 -> (B, n_blocks, 16) zero-padded block rows."""
    B, n = words.shape
    n_blocks = (n + 15) // 16
    return jnp.pad(words, ((0, 0), (0, n_blocks * 16 - n))) \
        .reshape(B, n_blocks, 16)


def window_from_chunks(chunks: Sequence[SealedChunk]) -> SealedWindow:
    """Stack a uniform chunk group into one window (per-chunk interop /
    test path — B row slices; the streaming engine never calls this in
    steady state)."""
    return SealedWindow(
        words=jnp.stack([c.blocks.reshape(-1)[:c.n_words] for c in chunks]),
        tags=None if chunks[0].tag is None
        else jnp.stack([c.tag for c in chunks]),
        counters=[c.counter for c in chunks],
        epochs=[c.epoch for c in chunks],
        meta=chunks[0].meta, n_words=chunks[0].n_words)


def window_to_chunks(win: SealedWindow) -> List[SealedChunk]:
    """Materialize per-chunk views of a window (sink/interop path)."""
    blocks = _blocks_batch(win.words)
    return [SealedChunk(blocks=blocks[b],
                        tag=None if win.tags is None else win.tags[b],
                        counter=win.counters[b], meta=win.meta,
                        n_words=win.n_words, epoch=win.epochs[b])
            for b in range(len(win))]


def _window_cipher_params(key, win: SealedWindow
                          ) -> Tuple[jax.Array, jax.Array]:
    """(keys, nonces) for a window under ``key`` at each row's ingress
    epoch.  Single-epoch windows (the steady state) share one (8,) key —
    the cheaper shared-key compiled program; mixed-epoch windows (rekey
    flips mid-window) get per-row (B, 8) keys so no row is ever
    sealed/opened under another epoch's keystream."""
    if len(set(win.epochs)) == 1:
        k = _key_at(key, win.epochs[0])
        keys = jnp.asarray(k.key)
        nonces = np.stack([np.asarray(k.nonce(c)) for c in win.counters])
    else:
        ks = [_key_at(key, e) for e in win.epochs]
        keys = jnp.asarray(np.stack([np.asarray(k.key) for k in ks]))
        nonces = np.stack([np.asarray(k.nonce(c))
                           for k, c in zip(ks, win.counters)])
    return keys, jnp.asarray(nonces)


def _reseal_coords(win: SealedWindow, reseal_as
                   ) -> Tuple[SealedWindow, List[int], List[int]]:
    """Resolve the OUTBOUND cipher coordinates of a window dispatch.

    ``reseal_as`` is ``None`` (steady state: re-seal under the rows'
    ingress coordinates) or ``(counters, epoch)`` — a freshly reserved
    contiguous counter block at one epoch (``EdgeHandle.reserve_window``)
    that a fault-tolerant re-execution seals under instead, because the
    ingress coordinates were already spent on the outbound key by the
    first dispatch of this share.  Returns (a coordinate *view* window
    for ``_window_cipher_params``, out counters, out epochs).
    """
    if reseal_as is None:
        return win, win.counters, win.epochs
    counters, epoch = reseal_as
    out_counters = [int(c) for c in counters]
    if len(out_counters) != len(win):
        raise ValueError(
            f"reseal_as carries {len(out_counters)} counters for a "
            f"{len(win)}-row window — a re-executed share must reserve "
            f"exactly one fresh counter per row")
    out_epochs = [int(epoch)] * len(win)
    view = replace(win, counters=out_counters, epochs=out_epochs)
    return view, out_counters, out_epochs


def seal_tensors_window(key, counters: Sequence[int],
                        xs: Sequence[jax.Array],
                        epoch: Optional[int] = None) -> SealedWindow:
    """Seal B same-shape tensors under ``key`` at one epoch in ONE batched
    program (``aead.seal_many``) — item-wise identical to B scalar
    :func:`seal_tensor` calls.  The ingress window path: counters come
    from a directory-reserved block (EdgeHandle.reserve_window)."""
    if epoch is None:
        epoch = _cur_epoch(key)
    k = _key_at(key, epoch)
    words, meta = aead.tensor_to_words_batch(jnp.stack(list(xs)))
    nonces = jnp.asarray(np.stack([np.asarray(k.nonce(c))
                                   for c in counters]))
    ct, tags = aead.seal_many(jnp.asarray(k.key), nonces, words)
    return SealedWindow(words=ct, tags=tags,
                        counters=[int(c) for c in counters],
                        epochs=[epoch] * len(ct), meta=meta,
                        n_words=words.shape[1])


def plain_window(counters: Sequence[int],
                 xs: Sequence[jax.Array]) -> SealedWindow:
    """Batched :func:`plain_chunk`: frame B same-shape tensors."""
    words, meta = aead.tensor_to_words_batch(jnp.stack(list(xs)))
    return SealedWindow(words=words, tags=None,
                        counters=[int(c) for c in counters],
                        epochs=[0] * words.shape[0], meta=meta,
                        n_words=words.shape[1])


def seal_tensor_many(key, counters: Sequence[int], xs: Sequence[jax.Array],
                     epoch: Optional[int] = None) -> List[SealedChunk]:
    """Chunk-list view of :func:`seal_tensors_window` (interop/tests)."""
    return window_to_chunks(seal_tensors_window(key, counters, xs,
                                                epoch=epoch))


def open_words_many(key, chunks: Sequence[SealedChunk]
                    ) -> Tuple[jax.Array, jax.Array]:
    """Open a uniform chunk group in ONE program: -> (pt (B, n_words),
    ok (B,) device verdicts — NOT synced to host)."""
    win = window_from_chunks(chunks)
    keys, nonces = _window_cipher_params(key, win)
    return aead.open_many(keys, nonces, win.words, win.tags)


def egress_window(mode: str, key, win: SealedWindow
                  ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Batched trusted-subscriber egress: -> ((B, *item) tensor batch,
    ok verdict vector or None in plain mode).  Verdicts stay on device."""
    if mode == "plain":
        return aead.words_to_tensor_batch(win.words, win.meta), None
    keys, nonces = _window_cipher_params(key, win)
    pt, ok = aead.open_many(keys, nonces, win.words, win.tags)
    return aead.words_to_tensor_batch(pt, win.meta), ok


def egress_many(mode: str, key, chunks: Sequence[SealedChunk]
                ) -> Tuple[List[jax.Array], Optional[jax.Array]]:
    """Batched trusted-subscriber egress of a uniform chunk group:
    -> (tensors, ok verdict vector or None in plain mode)."""
    xb, ok = egress_window(mode, key, window_from_chunks(chunks))
    return [xb[b] for b in range(len(chunks))], ok


def uniform_runs(items: Sequence, key: Callable[[Any], Any]):
    """Split a sequence into consecutive runs of identical ``key(item)``
    — each run is one batched program.  Steady-state streams are a
    single run; a ragged tail gets its own.  Yields (start_index, run)."""
    i = 0
    while i < len(items):
        j = i + 1
        sig = key(items[i])
        while j < len(items) and key(items[j]) == sig:
            j += 1
        yield i, list(items[i:j])
        i = j


def _uniform_runs(chunks: Sequence[SealedChunk]):
    """Chunk-framing runs: consecutive identical (n_words, meta)."""
    for _, group in uniform_runs(chunks, lambda c: (c.n_words, c.meta)):
        yield group


def _apply_op(op: str, const: float, blocks: jax.Array,
              table=None) -> jax.Array:
    """A registered op on plaintext (rows, 16) blocks, as XLA ops; a table
    op reads ``table``, plaintext or a :class:`SealedTable`."""
    if op in enclave_ops.TABLE_OPS:
        if isinstance(table, SealedTable):
            table = open_table(table)
        return enclave_ops.TABLE_OPS[op][0](blocks, table)
    return enclave_ops.OPS[op](blocks, const)


def _apply_static_words(op: str, const: float, words: jax.Array,
                        table=None) -> jax.Array:
    """Batched mirror of :func:`_apply_static_f32` on raw payload words:
    (B, n_words) -> (B, n_words), the operator applied ONCE across every
    block row of the window."""
    B, n = words.shape
    blocks = _blocks_batch(words).reshape(-1, 16)
    out = _apply_op(op, const, blocks, table)
    return out.reshape(B, -1)[:, :n]


def plain_chunk(counter: int, x: jax.Array) -> SealedChunk:
    words, meta = aead.tensor_to_words(x)
    blocks, n = _words_to_blocks(words)
    return SealedChunk(blocks=blocks, tag=None, counter=counter, meta=meta,
                       n_words=n)


def unplain_chunk(chunk: SealedChunk) -> jax.Array:
    return aead.words_to_tensor(chunk.blocks.reshape(-1)[:chunk.n_words],
                                chunk.meta)


class EnclaveExecutor:
    """Executes one stage's operator under the configured security mode.

    ``key_in``/``key_out`` are either static :class:`StageKey`s or
    KeyDirectory edge handles (repro.attest.directory.EdgeHandle): with
    handles the executor opens AND re-seals each chunk under the epoch
    the chunk was ingressed in (chunk counters are epoch-local — mixing
    a counter into a later epoch would reuse that epoch's (key, nonce)
    pairs).  A mid-stream rekey therefore drains old-epoch chunks to the
    sink under their own ratchet lineage while newly ingressed chunks
    ride the new keys.
    """

    def __init__(self, mode: str, key_in, key_out,
                 block_rows: int = 512):
        assert mode in ("plain", "encrypted", "enclave"), mode
        self.mode = mode
        self.key_in = key_in
        self.key_out = key_out
        self.block_rows = block_rows
        self.errors = 0
        # Telemetry hooks: the pipeline's worker pool stamps each executor
        # with the run's tracer and a per-worker track ("s2/w1") so the
        # open->op->seal phase spans land on that worker's timeline.
        # Spans here measure *enqueue* (dispatch is async); device time
        # lands in the pipeline's per-window sync span.
        self.tracer = NULL_TRACER
        self.track = "enclave"

    # -- generic python/jnp operator (plain + encrypted modes) --------------

    def run(self, fn: Callable[[jax.Array], jax.Array],
            chunk: SealedChunk) -> Optional[SealedChunk]:
        if self.mode == "plain":
            x = unplain_chunk(chunk)
            return plain_chunk(chunk.counter, fn(x))
        if self.mode == "encrypted":
            x, ok = open_tensor(self.key_in, chunk)
            if not bool(ok):
                self.errors += 1
                return None
            # reseal under the CHUNK's epoch (not the directory's current
            # one): counters are epoch-local, so sealing an old-epoch chunk
            # under a newer key would reuse that epoch's (key, nonce) pairs
            return seal_tensor(self.key_out, chunk.counter, fn(x),
                               epoch=chunk.epoch)
        raise ValueError(
            "enclave mode only executes registered static operators "
            "(run_static); arbitrary closures cannot be attested — "
            "the paper's no-dynamic-linking rule.")

    # -- static registered operator (all modes; enclave mode fused) ---------

    def run_static(self, op: str, const: float,
                   chunk: SealedChunk) -> Optional[SealedChunk]:
        if self.mode in ("plain", "encrypted"):
            fn = lambda x: _apply_static_f32(op, const, x)
            return self.run(fn, chunk)
        # enclave: fused decrypt->op->encrypt, VMEM-confined plaintext.
        # In and out keys both resolve at the chunk's epoch — see run().
        kin = _key_at(self.key_in, chunk.epoch)
        kout = _key_at(self.key_out, chunk.epoch)
        nonce = jnp.asarray(kin.nonce(chunk.counter))
        pad_rows = (-chunk.blocks.shape[0]) % self.block_rows
        blocks = jnp.pad(chunk.blocks, ((0, pad_rows), (0, 0)))
        # MAC check on ciphertext happens outside the enclave (it is public
        # data); the keystream offset for payload is counter0=1.
        r1, s1, r2, s2 = aead.derive_mac_keys(jnp.asarray(kin.key), nonce)
        ct_words = chunk.blocks.reshape(-1)[:chunk.n_words]
        _DISPATCHES.inc()
        _DISP_CWMAC.inc()
        ok = jnp.all(cwmac.mac2(ct_words, r1, s1, r2, s2) == chunk.tag)
        if not bool(ok):
            self.errors += 1
            return None
        out_blocks = enclave_ops.enclave_map(
            jnp.asarray(kin.key), jnp.asarray(kout.key),
            nonce, 1, blocks, op=op, const=const,
            block_rows=self.block_rows)[:chunk.blocks.shape[0]]
        # re-tag under the outbound key
        nonce_out = jnp.asarray(kout.nonce(chunk.counter))
        ro1, so1, ro2, so2 = aead.derive_mac_keys(
            jnp.asarray(kout.key), nonce_out)
        out_words = out_blocks.reshape(-1)[:chunk.n_words]
        _DISPATCHES.inc()
        _DISP_CWMAC.inc()
        tag = cwmac.mac2(out_words, ro1, so1, ro2, so2)
        return SealedChunk(blocks=out_blocks, tag=tag, counter=chunk.counter,
                           meta=chunk.meta, n_words=chunk.n_words,
                           epoch=chunk.epoch)


    # -- window-native entry points (deferred MAC verdicts) -----------------

    def run_window(self, fn: Callable[[jax.Array], jax.Array],
                   win: SealedWindow, *, reseal_as=None
                   ) -> Tuple[SealedWindow, Optional[jax.Array]]:
        """Batched :meth:`run` on a whole window: ``open_many`` -> ``fn``
        per decoded row -> ``seal_many``.

        Returns (out window, ok): a candidate output for EVERY input row
        plus a per-row device verdict vector (None in plain mode) that is
        NOT synced — MAC-failed rows carry garbage and must be dropped by
        the caller after its one-per-window host sync.  ``fn`` itself is
        applied row-wise (custom closures are not assumed vmappable); the
        static-op path (:meth:`run_static_window`) is fully vectorized.

        ``reseal_as=(counters, epoch)`` seals the OUTPUT under a freshly
        reserved counter block instead of the rows' ingress coordinates —
        the fault-tolerance retry path: the input still opens under its
        original coordinates, but re-sealing under them would re-spend a
        (key, nonce, counter) triple the first dispatch already used on
        the outbound key.  The returned window carries the new
        counters/epochs.
        """
        if self.mode == "plain":
            xb = aead.words_to_tensor_batch(win.words, win.meta)
            yb = jnp.stack([fn(xb[b]) for b in range(len(win))])
            words, meta = aead.tensor_to_words_batch(yb)
            return replace(win, words=words, meta=meta,
                           n_words=words.shape[1]), None
        if self.mode != "encrypted":
            raise ValueError(
                "enclave mode only executes registered static operators "
                "(run_static_window); arbitrary closures cannot be "
                "attested — the paper's no-dynamic-linking rule.")
        out_view, out_ctrs, out_epochs = _reseal_coords(win, reseal_as)
        with self.tracer.span("enclave.open", cat="dispatch",
                              track=self.track, rows=len(win),
                              window=win.window_id):
            keys_in, nonces_in = _window_cipher_params(self.key_in, win)
            pt, ok = aead.open_many(keys_in, nonces_in, win.words, win.tags)
        with self.tracer.span("enclave.op", cat="dispatch",
                              track=self.track, rows=len(win),
                              window=win.window_id):
            xb = aead.words_to_tensor_batch(pt, win.meta)
            yb = jnp.stack([fn(xb[b]) for b in range(len(win))])
            words, meta = aead.tensor_to_words_batch(yb)
        with self.tracer.span("enclave.seal", cat="dispatch",
                              track=self.track, rows=len(win),
                              window=win.window_id):
            keys_out, nonces_out = _window_cipher_params(self.key_out,
                                                         out_view)
            ct, tags = aead.seal_many(keys_out, nonces_out, words)
        return replace(win, words=ct, tags=tags, meta=meta,
                       n_words=words.shape[1], counters=out_ctrs,
                       epochs=out_epochs), ok

    def run_static_window(self, op: str, const: float, win: SealedWindow,
                          *, reseal_as=None, table=None
                          ) -> Tuple[SealedWindow, Optional[jax.Array]]:
        """Batched :meth:`run_static` on a whole window (deferred
        verdicts, see :meth:`run_window`): the steady-state hot path — a
        handful of device dispatches per window regardless of B.

        encrypted: ``open_many`` -> the op applied once across all block
        rows -> ``seal_many``.  enclave: batched ciphertext MAC check +
        one ``enclave_map_rows`` grid sweep (per-row nonce/counter, and
        per-row keys when the window straddles a rekey epoch flip), so
        plaintext stays VMEM-confined row by row.  ``reseal_as`` seals
        the output under a fresh counter block (see :meth:`run_window`);
        in enclave mode the fused kernel re-encrypts directly under the
        outbound coordinates, so plaintext stays VMEM-confined on the
        retry path too.

        A table op (``enclave_map.TABLE_OPS``) reads ``table``: the
        plaintext table in plain mode, else a :class:`SealedTable`, which
        encrypted mode opens as XLA ops and enclave mode opens inside its
        own kernel (``enclave_join_rows``).  An aggregate op
        (``AGGREGATE_OPS``) combines every row of the share into partial
        rows, the same in every mode.
        """
        if op in enclave_ops.AGGREGATE_OPS:
            _AGG_ROWS_IN.inc(len(win) * ((win.n_words + 15) // 16))
        if self.mode == "plain":
            return replace(win, words=_apply_static_words(
                op, const, win.words, table)), None
        out_view, out_ctrs, out_epochs = _reseal_coords(win, reseal_as)
        keys_in, nonces_in = _window_cipher_params(self.key_in, win)
        keys_out, nonces_out = _window_cipher_params(self.key_out, out_view)
        if self.mode == "encrypted":
            with self.tracer.span("enclave.open", cat="dispatch",
                                  track=self.track, rows=len(win),
                                  window=win.window_id):
                pt, ok = aead.open_many(keys_in, nonces_in,
                                        win.words, win.tags)
            with self.tracer.span(_hop_span(op), cat="dispatch",
                                  track=self.track, op=op, rows=len(win),
                                  window=win.window_id):
                words = _apply_static_words(op, const, pt, table)
            with self.tracer.span("enclave.seal", cat="dispatch",
                                  track=self.track, rows=len(win),
                                  window=win.window_id):
                ct, tags = aead.seal_many(keys_out, nonces_out, words)
            return replace(win, words=ct, tags=tags, counters=out_ctrs,
                           epochs=out_epochs), ok
        # enclave: MAC check on ciphertext happens outside the enclave
        # (public data), batched: one mac-key derivation + one MAC program.
        B, n_words = len(win), win.n_words
        n_blocks = (n_words + 15) // 16
        with self.tracer.span("enclave.open", cat="dispatch",
                              track=self.track, rows=B, window=win.window_id):
            mk_in = aead.derive_mac_keys_many(keys_in, nonces_in)
            ok = jnp.all(aead.mac2_many(win.words, mk_in) == win.tags,
                         axis=-1)
        # fused decrypt->op->encrypt over the window's flattened rows;
        # payload keystream offset is counter0=1 per chunk.
        with self.tracer.span(_hop_span(op), cat="dispatch",
                              track=self.track, op=op, rows=B,
                              window=win.window_id):
            rows = _blocks_batch(win.words).reshape(-1, 16)
            row_nonces = jnp.repeat(nonces_in, n_blocks, axis=0)
            row_ctrs = jnp.tile(jnp.arange(1, n_blocks + 1, dtype=U32), B)
            row_kin = keys_in if keys_in.ndim == 1 \
                else jnp.repeat(keys_in, n_blocks, axis=0)
            row_kout = keys_out if keys_out.ndim == 1 \
                else jnp.repeat(keys_out, n_blocks, axis=0)
            kw = {}
            if reseal_as is not None:
                # the fused kernel re-encrypts under the FRESH coordinates
                # (per-block keystream counters stay 1..n_blocks — the
                # chunk counter only enters through the nonce)
                kw["nonces_out"] = jnp.repeat(nonces_out, n_blocks, axis=0)
            if op in enclave_ops.TABLE_OPS:
                out = enclave_ops.enclave_join_rows(
                    row_kin, row_kout, row_nonces, row_ctrs, rows,
                    table.words, table.key_block, op=op, **kw)
            else:
                out = enclave_ops.enclave_map_rows(
                    row_kin, row_kout, row_nonces, row_ctrs, rows, op=op,
                    const=const, **kw)
            out_words = out.reshape(B, -1)[:, :n_words]
        # re-tag under the outbound keys, batched
        with self.tracer.span("enclave.seal", cat="dispatch",
                              track=self.track, rows=B, window=win.window_id):
            mk_out = aead.derive_mac_keys_many(keys_out, nonces_out)
            tags_out = aead.mac2_many(out_words, mk_out)
        return replace(win, words=out_words, tags=tags_out,
                       counters=out_ctrs, epochs=out_epochs), ok

    # -- chunk-list wrappers over the window entry points -------------------

    def run_many(self, fn: Callable[[jax.Array], jax.Array],
                 chunks: Sequence[SealedChunk]
                 ) -> Tuple[List[SealedChunk], Optional[jax.Array]]:
        """Chunk-list view of :meth:`run_window` (interop/tests): splits
        into uniform-framing runs, returns candidate outputs for every
        row + the concatenated deferred verdict vector."""
        return self._many(lambda w: self.run_window(fn, w), chunks)

    def run_static_many(self, op: str, const: float,
                        chunks: Sequence[SealedChunk]
                        ) -> Tuple[List[SealedChunk], Optional[jax.Array]]:
        """Chunk-list view of :meth:`run_static_window` (interop/tests)."""
        return self._many(
            lambda w: self.run_static_window(op, const, w), chunks)

    def _many(self, call, chunks):
        outs: List[SealedChunk] = []
        oks: List[jax.Array] = []
        for group in _uniform_runs(chunks):
            win, ok = call(window_from_chunks(group))
            outs.extend(window_to_chunks(win))
            if ok is None:
                ok = jnp.ones((len(group),), bool)
            oks.append(ok)
        if self.mode == "plain":
            return outs, None
        return outs, oks[0] if len(oks) == 1 else jnp.concatenate(oks)


def _apply_static_f32(op: str, const: float, x: jax.Array,
                      table=None) -> jax.Array:
    """jnp mirror of the kernel's static op registry (on decoded tensors)."""
    words, meta = aead.tensor_to_words(x)
    blocks, n = _words_to_blocks(words)
    out = _apply_op(op, const, blocks, table)
    return aead.words_to_tensor(out.reshape(-1)[:n], meta)


def ingress(mode: str, key: StageKey, counter: int,
            x: jax.Array) -> SealedChunk:
    """Bring a source tensor into the pipeline under the security mode."""
    if mode == "plain":
        return plain_chunk(counter, x)
    return seal_tensor(key, counter, x)


def egress(mode: str, key: StageKey, chunk: SealedChunk):
    """Take a result out of the pipeline (trusted subscriber)."""
    if mode == "plain":
        return unplain_chunk(chunk), jnp.bool_(True)
    return open_tensor(key, chunk)
