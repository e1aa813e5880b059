"""Session keys per pipeline stage.

The paper (§4) assumes "attestation and key establishment was previously
performed" — that assumption is now implemented by ``repro.attest``:
session keys are established per edge by the quote-checked DH handshake
(`repro.attest.handshake`) and owned/ratcheted/revoked by
`repro.attest.directory.KeyDirectory` (which builds StageKeys via
``repro.attest.rotation.key_from_bytes``, not this module's derivation).
This module defines the key *container* and the nonce discipline;
``derive_stage_key`` survives only as the legacy root-seed derivation
exercised by the crypto unit tests (a grep test asserts nothing else
calls it).

Nonces are (domain, chunk_counter) pairs, never reused under one key: the
counter occupies nonce words 1..2 (64 bits) and :meth:`StageKey.nonce`
raises :class:`NonceExhaustedError` before it can wrap — long-running
streams must rotate keys (``KeyDirectory.advance_epoch`` resets the
per-edge counters) well before that hard stop.
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass
from typing import Tuple

import jax.numpy as jnp
import numpy as np

U32 = jnp.uint32

# The chunk counter rides in two u32 nonce words; reusing a (key, nonce)
# pair is a two-time pad, so the guard below is a hard error, not a wrap.
NONCE_COUNTER_BITS = 64
NONCE_COUNTER_MAX = (1 << NONCE_COUNTER_BITS) - 1


class NonceExhaustedError(RuntimeError):
    """The 64-bit chunk counter is exhausted for this key; rotate first
    (repro.attest.rotation / KeyDirectory.advance_epoch)."""


@dataclass(frozen=True)
class StageKey:
    key: np.ndarray          # (8,) uint32 — ChaCha20 key
    stage_id: int

    def nonce(self, chunk_counter: int) -> np.ndarray:
        # Nonce depends only on the chunk counter: edge keys are already
        # unique per edge (so no cross-edge nonce reuse), and the fused
        # enclave kernel re-encrypts under the *outbound* key with the same
        # nonce — sender and receiver must agree on it without knowing each
        # other's stage ids.
        if not 0 <= chunk_counter <= NONCE_COUNTER_MAX:
            raise NonceExhaustedError(
                f"chunk counter {chunk_counter} outside [0, 2^"
                f"{NONCE_COUNTER_BITS}) for stage {self.stage_id}: the "
                f"nonce space is spent — advance the key epoch "
                f"(KeyDirectory.advance_epoch) before the counter wraps")
        return np.array([0,
                         chunk_counter & 0xFFFFFFFF,
                         (chunk_counter >> 32) & 0xFFFFFFFF],
                        dtype=np.uint32)

    def nonces(self, chunk_counters) -> np.ndarray:
        """(B, 3) u32: row b is ``nonce(chunk_counters[b])``, built in one
        vectorised pass, with the same exhaustion check on every row."""
        if len(chunk_counters):
            lo, hi = min(chunk_counters), max(chunk_counters)
            self.nonce(lo if lo < 0 else hi)
        c = np.asarray(chunk_counters, np.uint64)
        out = np.zeros((c.shape[0], 3), np.uint32)
        out[:, 1] = c & np.uint64(0xFFFFFFFF)
        out[:, 2] = c >> np.uint64(32)
        return out


def resolve_key(key, epoch: int = None) -> "StageKey":
    """Resolve a StageKey or a KeyDirectory EdgeHandle at an epoch.

    Raw StageKeys are static (epoch-less) and pass through; handles
    (repro.attest.directory.EdgeHandle, duck-typed to avoid a crypto ->
    attest import) pull the live key from the directory — ``epoch=None``
    means the edge's current epoch.  The single dispatch point for every
    sealing layer (enclave, secure_channel).
    """
    return key if isinstance(key, StageKey) else key.key(epoch)


def current_epoch(key) -> int:
    """The epoch a seal under ``key`` happens in (0 for static keys)."""
    return 0 if isinstance(key, StageKey) else key.epoch


def derive_stage_key(root: bytes, stage_name: str, stage_id: int) -> StageKey:
    h = hashlib.sha256(root + b"|" + stage_name.encode()).digest()
    key = np.frombuffer(h, dtype="<u4").copy()
    return StageKey(key=key, stage_id=stage_id)


def root_key_from_seed(seed: int) -> bytes:
    return hashlib.sha256(f"repro-root-{seed}".encode()).digest()
