"""repro.attest: measurements, quotes, handshake, KeyDirectory lifecycle
(epoch rekeying, revocation), and the rewired sealed paths — including the
8-stage rekey+revocation parity run and the derive_stage_key grep gate."""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.attest.directory import (KeyDirectory, KeyDirectoryError,
                                    NoSessionError, RevokedWorkerError,
                                    ephemeral_edge_key)
from repro.attest.handshake import (HandshakeEnd, HandshakeError,
                                    HandshakeMessage, bind_share)
from repro.attest.measure import IO_ENDPOINT, measure_fn, measure_stage
from repro.attest.quote import QuoteError, QuotePolicy
from repro.attest.rotation import hkdf_sha256, ratchet_key
from repro.crypto.keys import (NONCE_COUNTER_MAX, NonceExhaustedError,
                               StageKey)
from repro.launch.mesh import make_mesh


def _directory(seed=0, **kw):
    d = KeyDirectory(seed=seed, **kw)
    d.enroll("a", IO_ENDPOINT, allow=True)
    d.enroll("b", IO_ENDPOINT, allow=True)
    return d


# ---------------------------------------------------------- measurements


def test_measurements_deterministic_and_sensitive():
    m1 = measure_stage(op="scale", const=2.0)
    assert m1 == measure_stage(op="scale", const=2.0)
    assert m1 != measure_stage(op="scale", const=3.0)      # const matters
    assert m1 != measure_stage(op="add", const=2.0)        # op matters
    assert m1 != measure_stage(op="scale", const=2.0, sgx=False)

    f1 = lambda x: x * 2.0
    f2 = lambda x: x * 2.0
    f3 = lambda x: x * 3.0
    assert measure_fn(f1) == measure_fn(f2)    # same bytecode, same identity
    assert measure_fn(f1) != measure_fn(f3)    # tampered body measured

    # nested code objects measure recursively (repr would embed addresses)
    g1 = lambda x: (lambda y: y + 1.0)(x)
    g2 = lambda x: (lambda y: y + 1.0)(x)
    g3 = lambda x: (lambda y: y + 2.0)(x)
    assert measure_fn(g1) == measure_fn(g2)
    assert measure_fn(g1) != measure_fn(g3)    # inner-body tamper seen

    # closure captures are part of the identity: same bytecode, different
    # captured value -> different behavior -> different measurement
    def make(s):
        return lambda x: x * s
    assert measure_fn(make(2.0)) == measure_fn(make(2.0))
    assert measure_fn(make(2.0)) != measure_fn(make(3.0))
    # ...and so are defaults
    d1 = lambda x, s=2.0: x * s
    d2 = lambda x, s=3.0: x * s
    assert measure_fn(d1) != measure_fn(d2)
    # large captured arrays hash full contents — repr elides interior
    # elements, which would let a mid-array tamper keep verifying
    w1, w2 = np.zeros(2000, np.float32), np.zeros(2000, np.float32)
    w2[1000] = 42.0
    assert measure_fn(make(w1)) == measure_fn(make(w1.copy()))
    assert measure_fn(make(w1)) != measure_fn(make(w2))


# ----------------------------------------------------------------- quotes


def test_quote_verify_and_rejections():
    d = _directory()
    q = d.quote_for("a", b"ctx")
    d.verify(q, expect_report_data=b"ctx")

    # forged signature
    import dataclasses
    bad = dataclasses.replace(q, signature=b"\x00" * 32)
    with pytest.raises(QuoteError, match="bad-signature"):
        d.verify(bad)
    # binding mismatch (quote replayed into another session)
    with pytest.raises(QuoteError, match="report-data-mismatch"):
        d.verify(q, expect_report_data=b"other")
    # measurement not allowlisted
    d.enroll("rogue", b"\xde\xad" * 16)           # enrolled, NOT allowed
    with pytest.raises(QuoteError, match="measurement-not-allowed"):
        d.verify(d.quote_for("rogue"))
    assert not d.is_admitted("rogue")
    # stale: age policy over the logical clock
    ds = _directory(seed=1, policy=None)
    ds.policy.max_quote_age = 2
    ds.enroll("c", IO_ENDPOINT, allow=True)
    q = ds.quote_for("c")
    ds.tick(3)
    with pytest.raises(QuoteError, match="stale"):
        ds.verify(q)
    assert ds.is_admitted("c")                    # a FRESH quote still passes
    # revoked
    d.revoke("b")
    with pytest.raises(RevokedWorkerError):
        d.verify(d.quote_for("b"))
    assert not d.is_admitted("b") and d.is_admitted("a")


def test_enrollment_is_immutable():
    d = _directory()
    with pytest.raises(KeyDirectoryError, match="immutable"):
        d.enroll("a", b"\x01" * 32)
    d.enroll("a", IO_ENDPOINT)                    # same measurement is fine


# -------------------------------------------------------------- handshake


def test_handshake_agrees_and_binds_transcript():
    d = _directory()
    k = d.establish("e", "a", "b", stage_id=4)
    assert isinstance(k, StageKey) and k.stage_id == 4
    assert k.key.shape == (8,) and k.key.dtype == np.uint32
    # the stored session key is what both ends derived
    assert np.array_equal(d.edge_key("e").key, k.key)
    # distinct edges (different contexts) get distinct keys
    k2 = d.establish("e2", "a", "b")
    assert not np.array_equal(k.key, k2.key)
    # re-establishing replaces the session with a fresh key
    k3 = d.establish("e", "a", "b", stage_id=4)
    assert not np.array_equal(k.key, k3.key)


def test_handshake_rejects_mitm_and_unverified_peer():
    d = _directory()
    ends = {}
    for wid in ("a", "b"):
        ends[wid] = HandshakeEnd(
            quote_fn=lambda rd, w=wid: d.quote_for(w, rd),
            verify_fn=lambda q, rd: d.verify(q, expect_report_data=rd),
            secret=d._rng.randrange(2, 1 << 255), context=b"ctx")
    fa, fb = ends["a"].flight(), ends["b"].flight()
    # substituted DH share: the quote no longer binds -> rejected
    evil = HandshakeMessage(pub=pow(2, 12345, int(1e30) + 57), quote=fb.quote)
    with pytest.raises((QuoteError, HandshakeError)):
        ends["a"].derive(fa, evil)
    # a revoked peer's fresh quote is rejected mid-handshake
    d.revoke("b")
    with pytest.raises(RevokedWorkerError):
        ends["a"].derive(fa, HandshakeMessage(
            pub=fb.pub, quote=d._qk.quote("b", IO_ENDPOINT,
                                          bind_share(b"ctx", fb.pub),
                                          now=d.clock)))
    # both honest flights agree when admitted
    d2 = _directory(seed=2)
    k = d2.establish("e", "a", "b")
    assert k.key.shape == (8,)


def test_establish_requires_admissible_endpoints():
    d = _directory()
    d.revoke("b")
    with pytest.raises(RevokedWorkerError):
        d.establish("e", "a", "b")
    with pytest.raises(KeyDirectoryError):
        d.establish("e", "a", "a")               # two distinct endpoints


# ------------------------------------------------ epochs, counters, nonce


def test_advance_epoch_ratchets_and_resets_counters():
    d = _directory()
    d.establish("e", "a", "b")
    k0 = d.edge_key("e")
    assert d.next_counter("e") == 0 and d.next_counter("e") == 1
    assert d.session("e").chunks == 2

    assert d.advance_epoch() == 1
    k1 = d.edge_key("e")
    assert not np.array_equal(k0.key, k1.key)          # ratcheted
    assert d.session("e").chunks == 0                  # counter cleared
    assert d.next_counter("e") == 0
    # the drained epoch stays openable...
    assert np.array_equal(d.edge_key("e", epoch=0).key, k0.key)
    # ...and the ratchet is the public one-way function
    expect = ratchet_key(k0, epoch=1, transcript=d.session("e").transcript)
    assert np.array_equal(k1.key, expect.key)


def test_epoch_history_is_bounded():
    d = _directory(epoch_history=2)
    d.establish("e", "a", "b")
    k0 = d.edge_key("e")
    d.advance_epoch()
    d.advance_epoch()
    with pytest.raises(NoSessionError, match="drained past history"):
        d.edge_key("e", epoch=0)
    assert d.edge_key("e", epoch=1) is not None
    assert not np.array_equal(d.edge_key("e").key, k0.key)


def test_nonce_exhaustion_guard_and_rotation_clears_it():
    k = ephemeral_edge_key("t", seed=0)
    assert k.nonce(NONCE_COUNTER_MAX) is not None      # last valid counter
    with pytest.raises(NonceExhaustedError):
        k.nonce(NONCE_COUNTER_MAX + 1)
    with pytest.raises(NonceExhaustedError):
        k.nonce(-1)
    # the rotation path clears an almost-exhausted per-edge counter
    d = _directory()
    d.establish("e", "a", "b")
    d.session("e").chunks = NONCE_COUNTER_MAX          # one step from wrap
    d.edge_key("e").nonce(d.next_counter("e"))         # still sealable
    with pytest.raises(NonceExhaustedError):
        d.edge_key("e").nonce(d.next_counter("e"))     # would wrap
    d.advance_epoch()
    assert d.session("e").chunks == 0                  # rotation resets
    d.edge_key("e").nonce(d.next_counter("e"))         # sealable again


def test_hkdf_sha256_expands():
    out = hkdf_sha256(b"ikm", salt=b"salt", info=b"info", length=64)
    assert len(out) == 64
    assert out[:32] == hkdf_sha256(b"ikm", salt=b"salt", info=b"info")
    assert out != hkdf_sha256(b"ikm2", salt=b"salt", info=b"info", length=64)


# ------------------------------------------------------------- revocation


def test_revoke_drops_sessions_and_blocks_rehandshake():
    d = _directory()
    d.enroll("c", IO_ENDPOINT, allow=True)
    d.establish("ab", "a", "b")
    d.establish("ac", "a", "c")
    dropped = d.revoke("b")
    assert dropped == ["ab"]
    assert not d.has_session("ab") and d.has_session("ac")
    # a typo'd id must fail loudly, not silently "revoke" nobody
    with pytest.raises(KeyDirectoryError, match="unknown worker"):
        d.revoke("stage/w1")
    with pytest.raises(RevokedWorkerError):
        d.reestablish("ab", "a", "b")
    # survivors re-handshake fine
    d.reestablish("ab2", "a", "c")


def test_run_with_recovery_revokes_and_reestablishes():
    from repro.ft.failures import FailureInjector, run_with_recovery
    d = _directory()
    d.enroll("c", IO_ENDPOINT, allow=True)
    d.establish("stream", "a", "b")
    inj = FailureInjector(schedule={3: "revoked:b"})
    rehandshakes = []

    def reestablish(directory):
        # re-handshake on the surviving set (c replaces b)
        rehandshakes.append(directory.establish("stream", "a", "c"))

    state = {"step": 0}

    def run_steps(start, end):
        for s in range(start, end):
            inj.maybe_fail(s)
            d.edge_key("stream")       # the stream needs a live session
            state["step"] = s + 1
        return state["step"]

    rep = run_with_recovery(total_steps=6, run_steps=run_steps,
                            restore=lambda: state["step"],
                            directory=d, reestablish=reestablish)
    assert rep.final_step == 6
    assert rep.revoked_workers == ["b"]
    assert "b" in d.policy.revoked and len(rehandshakes) == 1
    assert d.session("stream").right == "c"


# --------------------------------------------- pipeline integration (e2e)


def _stage8():
    from repro.core.pipeline import Stage
    return [Stage(f"s{i}", op="scale_f32", const=1.0 + 0.125 * i,
                  workers=2 if i % 3 == 0 else 1) for i in range(8)]


def test_8stage_rekey_and_revocation_bit_identical():
    """Acceptance run: 8 sealed stages, rekey_every_n forcing >= 2 epoch
    flips, one mid-stream revocation — bit-identical output to a
    static-key (no rekey, no revocation) run."""
    from repro.configs.base import SecureStreamConfig
    from repro.core.pipeline import Pipeline

    src = [jnp.asarray(np.random.default_rng(i).standard_normal(
        (64,)).astype(np.float32)) for i in range(9)]

    p_static = Pipeline(_stage8(), SecureStreamConfig(mode="encrypted"))
    got_static = []
    p_static.run(iter(src), on_result=lambda r: got_static.append(
        np.asarray(r)))
    assert p_static.directory.epoch == 0

    p = Pipeline(_stage8(), SecureStreamConfig(mode="encrypted"))

    def source():
        for i, c in enumerate(src):
            if i == 4:   # mid-stream: evict one worker of stage s3
                p.directory.revoke(Pipeline.worker_id("s3", 1))
            yield c

    got = []
    p.run(source(), on_result=lambda r: got.append(np.asarray(r)),
          rekey_every_n=3)
    assert p.directory.epoch >= 2                      # >= 2 epoch flips
    assert not p.directory.is_admitted(Pipeline.worker_id("s3", 1))
    assert len(got) == len(got_static) == len(src)
    for a, b in zip(got, got_static):
        assert np.array_equal(a, b)                    # bit-identical
    # the revoked worker stopped receiving chunks after eviction
    pw = p.report()["s3"]["per_worker"]
    assert len(pw) == 2 and pw[1] < pw[0]

    # ---- the WINDOW-BATCHED engine must agree bit-for-bit too: with
    # epoch_history covering the deeper windowed in-flight lag, whole
    # windows straddle the rekey flips (window 16 chunks vs rekey
    # every 3), so every batched open resolves per-row ingress epochs.
    pb = Pipeline(_stage8(), SecureStreamConfig(mode="encrypted"),
                  directory=KeyDirectory(seed=0, epoch_history=64),
                  window_chunks=8)

    def source_b():
        for i, c in enumerate(src):
            if i == 4:
                pb.directory.revoke(Pipeline.worker_id("s3", 1))
            yield c

    got_b = []
    pb.run(source_b(), on_result=lambda r: got_b.append(np.asarray(r)),
           rekey_every_n=3)
    assert pb.directory.epoch >= 2
    assert not pb.directory.is_admitted(Pipeline.worker_id("s3", 1))
    assert len(got_b) == len(got_static)
    for a, b in zip(got_b, got_static):
        assert np.array_equal(a, b)                    # bit-identical


def test_rekey_never_reuses_a_key_nonce_pair(monkeypatch):
    """Regression: chunk counters are epoch-local, so an executor that
    resealed a drained old-epoch chunk under the *current* epoch would
    collide with the new epoch's own counters — a two-time pad.  Spy on
    every AEAD seal across a rekey+revocation run — the scalar path AND
    every row of the window-batched ``seal_many`` path — and assert no
    (key, nonce) pair is ever issued twice."""
    from repro.configs.base import SecureStreamConfig
    from repro.core.pipeline import Pipeline
    from repro.crypto import aead

    seen = set()
    real_seal = aead.seal
    real_seal_many = aead.seal_many

    def record(key_row, nonce_row):
        kn = (np.asarray(key_row).tobytes(), np.asarray(nonce_row).tobytes())
        assert kn not in seen, "(key, nonce) pair reused across epochs"
        seen.add(kn)

    def spy(key, nonce, words):
        record(key, nonce)
        return real_seal(key, nonce, words)

    def spy_many(key, nonces, words, **kw):
        key = np.asarray(key)
        for b in range(np.asarray(nonces).shape[0]):
            record(key if key.ndim == 1 else key[b],
                   np.asarray(nonces)[b])
        return real_seal_many(key, nonces, words, **kw)

    monkeypatch.setattr(aead, "seal", spy)
    monkeypatch.setattr(aead, "seal_many", spy_many)
    p = Pipeline(_stage8()[:4], SecureStreamConfig(mode="encrypted"))
    src = [jnp.full((16,), float(i + 1), jnp.float32) for i in range(9)]

    def source():
        for i, c in enumerate(src):
            if i == 5:
                p.directory.revoke(Pipeline.worker_id("s0", 1))
            yield c

    got = []
    p.run(source(), on_result=lambda r: got.append(np.asarray(r)),
          rekey_every_n=3)
    assert p.directory.epoch >= 2 and len(got) == len(src)
    assert len(seen) > len(src)        # ingress + every edge resealed
    # a SECOND run on the same pipeline continues the managed counters —
    # re-enumerating from 0 would reseal fresh plaintext under the first
    # run's (key, nonce) pairs (the spy would trip)
    got2 = []
    p.run(iter([jnp.full((16,), 99.0, jnp.float32)] * 2),
          on_result=lambda r: got2.append(np.asarray(r)))
    assert len(got2) == 2


def test_scale_stage_admits_only_verified_workers():
    from repro.configs.base import SecureStreamConfig
    from repro.core.pipeline import Pipeline
    p = Pipeline(_stage8()[:2], SecureStreamConfig(mode="encrypted"))
    wid = Pipeline.worker_id("s1", 0)
    assert p.directory.is_admitted(wid)
    p.directory.revoke(wid)
    p2 = p.scale_stage("s1", 3)
    assert p2.directory is p.directory
    assert not p2.directory.is_admitted(wid)           # stays evicted
    assert p2.directory.is_admitted(Pipeline.worker_id("s1", 1))
    assert p2.directory.is_admitted(Pipeline.worker_id("s1", 2))
    # the stream still runs on the survivors
    out = []
    p2.run(iter([jnp.ones((8,), jnp.float32)]),
           on_result=lambda r: out.append(np.asarray(r)))
    assert len(out) == 1
    # revoking EVERY worker of a stage stalls the stage (a stage-level
    # error, NOT RevokedWorkerError — a stage name is not a worker id)
    for w in range(3):
        p2.directory.revoke(Pipeline.worker_id("s1", w))
    with pytest.raises(KeyDirectoryError, match="every worker"):
        p2.run(iter([jnp.ones((8,), jnp.float32)]))


def test_pipeline_parallel_rekey_across_epoch_boundary():
    """GPipe with rekey_every_n=2 over 6 ticks: hand-offs sealed in epoch E
    open after the flip; output equals the unsealed run exactly."""
    from repro.dist.pipeline_parallel import edge_directory, pipeline_apply
    S, M, mb, d_model = 4, 3, 2, 8
    W = jax.random.normal(jax.random.key(0), (S, d_model, d_model))
    xs = jax.random.normal(jax.random.key(1), (M, mb, d_model))

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    want = pipeline_apply(stage_fn, W, xs, None, seal=False)
    d = edge_directory(S, seed=3)
    out = pipeline_apply(stage_fn, W, xs, None, seal=True, directory=d,
                         rekey_every_n=2)
    assert d.epoch >= 2                                # flips happened
    assert float(jnp.abs(out - want).max()) == 0.0     # exact roundtrip


def test_secure_exchange_with_directory_handle():
    from repro.dist import collectives
    d = _directory()
    d.establish("shuffle", "a", "b")
    h = d.handle("shuffle")
    mesh = make_mesh((1,), ("model",))
    x = jax.random.normal(jax.random.key(3), (1, 1, 16, 4), jnp.float32)
    y, ok = collectives.secure_exchange(x, mesh, "model", key=h)  # no step
    assert bool(ok.all())
    assert d.session("shuffle").chunks == 1            # managed counter
    y2, ok2 = collectives.secure_exchange(x, mesh, "model", key=h)
    assert bool(ok2.all()) and d.session("shuffle").chunks == 2
    # each round reserves the FULL W^2 nonce block, so another consumer
    # of the same edge (SecureChannel etc.) can never land inside it
    assert d.next_counters("shuffle", 4) == 2
    assert d.session("shuffle").chunks == 6
    # raw StageKey without a step is still a hard error
    with pytest.raises(ValueError, match="explicit per-round step"):
        collectives.secure_exchange(x, mesh, "model", key=h.key())
    # handle + explicit step would bypass the managed counter and later
    # collide with a managed allocation of the same value -> rejected
    with pytest.raises(ValueError, match="manages its own round"):
        collectives.secure_exchange(x, mesh, "model", key=h, step=5)


def test_rekey_history_guard_rejects_unsafe_combo():
    """A rekey cadence that could prune keys still needed to drain the
    in-flight window must fail up front, not NoSessionError mid-run."""
    from repro.configs.base import SecureStreamConfig
    from repro.core.pipeline import Pipeline, Stage
    p = Pipeline([Stage("s", op="scale_f32", const=2.0, workers=9)],
                 SecureStreamConfig(mode="encrypted"))
    with pytest.raises(ValueError, match="epoch_history"):
        p.run(iter([jnp.ones((8,), jnp.float32)] * 12), rekey_every_n=1)


def test_plain_mode_skips_handshakes():
    from repro.configs.base import SecureStreamConfig
    from repro.core.pipeline import Pipeline, Stage
    p = Pipeline([Stage("s", op="scale_f32", const=2.0, workers=2)],
                 SecureStreamConfig(mode="plain"))
    assert p.directory.edges() == []           # no sessions established
    assert p.keys == [None, None]
    assert p.directory.is_admitted(Pipeline.worker_id("s", 0))  # still gated
    out = []
    p.run(iter([jnp.ones((8,), jnp.float32)]),
          on_result=lambda r: out.append(np.asarray(r)))
    assert np.allclose(out[0], 2.0)


def test_secure_channel_epoch_drain():
    from repro.core.secure_channel import SecureChannel
    d = _directory()
    d.establish("e", "a", "b")
    ch = SecureChannel(d.handle("e"))
    x = jnp.arange(12, dtype=jnp.float32)
    hdr, ct, tag, meta = ch.protect(x)         # sealed in epoch 0
    d.advance_epoch()
    y, ok = ch.unprotect(hdr, ct, tag, meta)   # opened in epoch 1
    assert bool(ok) and bool((y == x).all())
    hdr2, ct2, tag2, meta2 = ch.protect(x)     # new epoch seals
    assert hdr2[1] == 1 and hdr2[0] == 0       # counter reset by rotation
    y2, ok2 = ch.unprotect(hdr2, ct2, tag2, meta2)
    assert bool(ok2) and bool((y2 == x).all())


# ------------------------------------------------------------- grep gate


def test_derive_stage_key_has_no_stray_call_sites():
    """Key hygiene: nothing outside repro/crypto and repro/attest derives
    stage keys directly — every sealed path goes through a KeyDirectory.
    (tests/test_crypto_properties.py unit-tests the derivation itself.)"""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    allowed = (os.path.join("src", "repro", "crypto") + os.sep,
               os.path.join("src", "repro", "attest") + os.sep)
    offenders = []
    for sub in ("src", "benchmarks", "examples"):
        for dirpath, _, files in os.walk(os.path.join(root, sub)):
            for fname in files:
                if not fname.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fname)
                rel = os.path.relpath(path, root)
                if rel.startswith(allowed):
                    continue
                text = open(path, encoding="utf-8").read()
                if re.search(r"derive_stage_key\s*\(", text):
                    offenders.append(rel)
    assert offenders == [], (
        f"derive_stage_key called outside repro.crypto/repro.attest: "
        f"{offenders} — obtain keys from a KeyDirectory instead")
