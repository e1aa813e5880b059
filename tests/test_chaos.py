"""Chaos harness for the window engine (repro.ft).

The contract under test is the ISSUE's acceptance bar: under seeded
fault schedules — worker crashes (transient and fatal), stalled shares
raced by speculative backups, tampered windows, dropped MAC-verdict
syncs, failed live enrollments — the terminal reduce of the 8-stage
encrypted job is BIT-IDENTICAL to the fault-free oracle, every injected
fault lands in the audit stream exactly once, and no re-execution ever
re-spends a (key, nonce) pair (the replay-buffer nonce discipline).
"""
from __future__ import annotations

from collections import Counter

import jax.numpy as jnp
import numpy as np
import pytest

from repro.attest.directory import KeyDirectory
from repro.configs.base import SecureStreamConfig
from repro.core.pipeline import Pipeline, Stage
from repro.ft.chaos import ChaosPlan, FaultSpec
from repro.ft.recovery import ReplayBuffer
from repro.ft.retry import RetryPolicy
from repro.ft.straggler import BackupDispatcher, StragglerDetector

N_CHUNKS = 12
CHUNK = 64


def _sum_reduce(acc, x):
    return x if acc is None else acc + x


def _stages8():
    sts = [Stage(f"s{i}", "scale_f32", const=1.0 + 0.125 * i,
                 workers=2 if i == 2 else 1) for i in range(8)]
    sts.append(Stage("sink", "custom", reduce_fn=_sum_reduce,
                     reduce_init=None))
    return sts


TOPOLOGY = [(f"s{i}", 2 if i == 2 else 1) for i in range(8)]


def _build(chaos=None, retry=None, seed=7, mode="encrypted",
           window_chunks=4):
    d = KeyDirectory(seed=seed, epoch_history=64)
    return Pipeline(_stages8(), SecureStreamConfig(mode=mode), seed=seed,
                    directory=d, window_chunks=window_chunks,
                    retry=retry, chaos=chaos)


def _source():
    return [jnp.asarray(
        np.random.RandomState(41 + i).rand(CHUNK).astype(np.float32))
        for i in range(N_CHUNKS)]


_ORACLE = {}


def _oracle(rekey=None):
    """Fault-free terminal reduce, computed once per rekey cadence."""
    if rekey not in _ORACLE:
        _ORACLE[rekey] = np.asarray(
            _build().run(iter(_source()), rekey_every_n=rekey))
    return _ORACLE[rekey]


def _ft_events(audit, *kinds):
    """Audit events of the given kinds as (kind, detail) pairs."""
    keep = set(kinds)
    return [(e["kind"], e) for e in audit.dump() if e["kind"] in keep]


# ------------------------------------------------------------- seeded sweep


@pytest.mark.parametrize("seed", range(20))
def test_seeded_chaos_sweep_bit_identical(seed):
    """20 seeded fault schedules over the 8-stage encrypted job: the
    terminal reduce is bit-identical to the fault-free oracle and every
    fired fault has its exactly-once audit footprint."""
    plan = ChaosPlan.seeded(seed, TOPOLOGY, rounds=3, n_faults=3)
    # pinned stall cutoff: injected stalls (>= 0.5 s) always exceed it,
    # so the stall -> backup decision is deterministic on any machine
    policy = RetryPolicy(share_timeout_s=0.25)
    p = _build(chaos=plan, retry=policy, seed=100 + seed)
    out = p.run(iter(_source()))
    assert np.array_equal(_oracle(), np.asarray(out)), \
        f"seed {seed}: terminal reduce diverged from the oracle"

    dump = p.directory.audit.dump()
    fired = {f.kind: [] for f in plan.faults}
    for (kind, stage, rnd, w) in plan.events:
        if kind == "enroll_fail":
            continue
        fired.setdefault(kind, []).append((stage, rnd, w))

    def _wf(reason, stage, rnd, w):
        return [e for e in dump if e["kind"] == "worker_failed"
                and e.get("reason") == reason and e.get("stage") == stage
                and e.get("round") == rnd
                and e.get("worker") == f"{stage}/w{w}"]

    for stage, rnd, w in fired.get("crash", []):
        assert len(_wf("crash", stage, rnd, w)) == 1, \
            f"seed {seed}: crash at {(stage, rnd, w)} not audited once"
        follow = [e for e in dump
                  if e["kind"] in ("share_retried", "share_failover")
                  and e.get("stage") == stage and e.get("round") == rnd]
        assert follow, f"seed {seed}: crash at {(stage, rnd, w)} " \
                       f"triggered neither retry nor failover"
    for stage, rnd, w in fired.get("stall", []):
        assert len(_wf("stall", stage, rnd, w)) == 1, \
            f"seed {seed}: stall at {(stage, rnd, w)} not audited once"
    # replays are audited once per affected SHARE; two faults may share a
    # (stage, round) via different workers, so count grouped
    for reason, kind in (("mac_failure", "tamper"),
                         ("verdict_dropped", "drop_verdict")):
        want = Counter((s, r) for s, r, _ in fired.get(kind, []))
        got = Counter((e["stage"], e["round"]) for e in dump
                      if e["kind"] == "window_replayed"
                      and e.get("reason") == reason)
        assert got == want, \
            f"seed {seed}: {kind} replays {dict(got)} != fired {dict(want)}"
        if kind == "tamper":
            for (stage, _r) in want:
                assert any(e["kind"] == "mac_failure"
                           and e.get("stage") == stage for e in dump), \
                    f"seed {seed}: tamper at {stage} left no mac_failure"


# --------------------------------------------------------- nonce discipline


def test_chaos_recovery_never_reuses_key_nonce(monkeypatch):
    """The FT invariant: a retried / failed-over / replayed share must
    never reseal under a (key, nonce) pair already spent on the outbound
    key.  Spy on every AEAD seal (scalar and batched) and on the
    outbound coordinates of every hop program launch (a stage hop seals
    inside that one program) across a fault schedule that exercises
    retry, tamper-replay, AND verdict-drop replay; any reuse trips the
    spy."""
    from repro.core import enclave
    from repro.crypto import aead

    # the oracle pipeline shares the chaos run's key seed — build it
    # BEFORE arming the spy or its (identical) ingress seals false-trip
    want = _oracle(rekey=3)
    seen = set()
    real_seal, real_seal_many = aead.seal, aead.seal_many

    def record(key_row, nonce_row):
        kn = (np.asarray(key_row).tobytes(),
              np.asarray(nonce_row).tobytes())
        assert kn not in seen, "(key, nonce) pair reused by a recovery"
        seen.add(kn)

    def record_rows(key, nonces):
        key, nonces = np.asarray(key), np.asarray(nonces)
        for b in range(nonces.shape[0]):
            record(key if key.ndim == 1 else key[b], nonces[b])

    def spy(key, nonce, words):
        record(key, nonce)
        return real_seal(key, nonce, words)

    def spy_many(key, nonces, words, **kw):
        record_rows(key, nonces)
        return real_seal_many(key, nonces, words, **kw)

    real_hop = enclave._hop_program

    def spy_hop(mode, *a, **kw):
        prog = real_hop(mode, *a, **kw)

        def launch(words, tags, keys_in, keys_out, nonces_in, nonces_out,
                   table):
            record_rows(keys_out,
                        nonces_in if nonces_out is None else nonces_out)
            return prog(words, tags, keys_in, keys_out, nonces_in,
                        nonces_out, table)
        return launch

    monkeypatch.setattr(aead, "seal", spy)
    monkeypatch.setattr(aead, "seal_many", spy_many)
    monkeypatch.setattr(enclave, "_hop_program", spy_hop)

    plan = ChaosPlan(faults=[
        # crash AFTER the share ran: the original coordinates were
        # already spent on the outbound key — the harshest retry case
        FaultSpec("crash", stage="s1", round=0, worker=0, when="after"),
        FaultSpec("crash", stage="s3", round=1, worker=0, when="after"),
        FaultSpec("tamper", stage="s4", round=0, worker=0, rows=2),
        FaultSpec("drop_verdict", stage="s6", round=1, worker=0),
    ])
    p = _build(chaos=plan, retry=RetryPolicy())
    out = p.run(iter(_source()), rekey_every_n=3)
    assert not plan.pending()
    assert np.array_equal(want, np.asarray(out))
    assert len(seen) > N_CHUNKS          # ingress + every resealed hop


# ------------------------------------------------------- acceptance scenario


def test_acceptance_rekey3_crash_stall_enroll_failure():
    """The ISSUE's acceptance run: 8-stage encrypted pipeline,
    ``rekey_every_n=3``, a seeded schedule with a fatal worker crash
    (forcing a live spare enrollment whose first handshake fails), a
    stalled share lost to a speculative backup, and the injected
    enrollment failure — terminal reduce bit-identical, each fault in
    the ordered audit stream exactly once."""
    plan = ChaosPlan(faults=[
        FaultSpec("crash", stage="s4", round=0, worker=0, when="after",
                  fatal=True),
        FaultSpec("enroll_fail"),
        FaultSpec("stall", stage="s2", round=1, worker=0, seconds=0.8),
    ])
    p = _build(chaos=plan, retry=RetryPolicy(share_timeout_s=0.25))
    out = p.run(iter(_source()), rekey_every_n=3)
    assert np.array_equal(_oracle(rekey=3), np.asarray(out))
    assert not plan.pending()            # every fault fired

    dump = p.directory.audit.dump()
    counts = Counter(e["kind"] for e in dump)
    # the fatal crash: one worker_failed, >=1 failover off the dead
    # worker, and the stage grew exactly one admitted spare
    crash = [e for e in dump if e["kind"] == "worker_failed"
             and e.get("reason") == "crash"]
    assert len(crash) == 1 and crash[0]["fatal"] is True
    assert counts["share_failover"] >= 2          # crash + backup
    s4 = next(s for s in p.stages if s.name == "s4")
    assert s4.workers == 2
    assert p.directory.is_admitted("s4/w1")
    # the chaos-injected enrollment failure took the REAL admission
    # path: exactly one quote_rejected in the same ordered stream
    rejected = [e for e in dump if e["kind"] == "quote_rejected"]
    assert len(rejected) == 1
    assert "chaos" in rejected[0]["reason"]
    # the stall: one worker_failed(stall), and the backup won the race
    stall = [e for e in dump if e["kind"] == "worker_failed"
             and e.get("reason") == "stall"]
    assert len(stall) == 1 and stall[0]["stage"] == "s2"
    backup = [e for e in dump if e["kind"] == "share_failover"
              and e.get("reason") == "backup"]
    assert len(backup) == 1 and backup[0]["stage"] == "s2"
    # epochs actually rotated under all of this
    assert p.directory.epoch >= 2


def test_chaos_plan_replays_bit_for_bit():
    """``replay()`` resets the schedule: the same plan fires the same
    faults at the same addresses on a second run, and both runs produce
    the oracle's bits."""
    plan = ChaosPlan.seeded(5, TOPOLOGY, rounds=3, n_faults=3)
    p = _build(chaos=plan, retry=RetryPolicy(share_timeout_s=0.25))
    out1 = np.asarray(p.run(iter(_source())))
    events1 = list(plan.events)
    plan.replay()
    assert plan.events == [] and all(not f.fired for f in plan.faults)
    out2 = np.asarray(p.run(iter(_source())))
    assert plan.events == events1
    assert np.array_equal(out1, out2)
    assert np.array_equal(out1, _oracle())


# ------------------------------------------- fault-free rounds under retry


@pytest.mark.parametrize("mode", ["plain", "encrypted", "enclave"])
def test_fault_free_ft_round_matches_the_plain_round(mode, monkeypatch):
    """With a retry policy and no fault, the stage loop runs the same
    rounds as without fault tolerance: the same windows reach the sink
    (words, tags, counters, epochs), with the same launches and host
    syncs, no fault-tolerance audit event and no row left retained."""
    from repro.core import pipeline as P

    sts = [Stage("a", "scale_f32", const=1.5, workers=2),
           Stage("b", "relu_f32", workers=2),
           Stage("sink", "custom", reduce_fn=_sum_reduce,
                 reduce_init=None)]
    src = [jnp.asarray(
        np.random.RandomState(3 + i).rand(32).astype(np.float32) - 0.5)
        for i in range(12)]
    seen = []
    real_open = Pipeline._open_egress

    def spy(self, parts, mode, key):
        seen.append([(np.asarray(w.words),
                      None if w.tags is None else np.asarray(w.tags),
                      list(w.counters), list(w.epochs)) for w in parts])
        return real_open(self, parts, mode, key)

    monkeypatch.setattr(Pipeline, "_open_egress", spy)

    def run(retry):
        p = Pipeline(sts, SecureStreamConfig(mode=mode), seed=3,
                     directory=KeyDirectory(seed=3), window_chunks=2)
        seen.clear()
        d0, s0 = P.dispatch_count(), P.host_sync_count()
        out = np.asarray(p.run(iter(src), retry=retry))
        return (p, out, list(seen), P.dispatch_count() - d0,
                P.host_sync_count() - s0)

    _, out0, wins0, disp0, syncs0 = run(None)
    p, out1, wins1, disp1, syncs1 = run(RetryPolicy())
    assert np.array_equal(out0, out1)
    assert len(wins1) == len(wins0) > 1
    for got, want in zip(wins1, wins0):
        assert len(got) == len(want)
        for (w1, t1, c1, e1), (w0, t0, c0, e0) in zip(got, want):
            assert np.array_equal(w1, w0)
            assert (t1 is None) == (t0 is None)
            assert t1 is None or np.array_equal(t1, t0)
            assert (c1, e1) == (c0, e0)
    assert (disp1, syncs1) == (disp0, syncs0)
    assert _ft_events(p.directory.audit, "worker_failed", "share_retried",
                      "share_failover", "window_replayed") == []
    # the hooks ran: every stage retained and acked its rounds
    buf = p._last_ft.buffer
    assert buf.watermark() >= 1 and buf.retained_rows() == 0


def test_a_stall_under_the_cutoff_feeds_the_straggler_detector():
    """A stalled share whose observed time stays under the cutoff keeps
    its result, and the stage's straggler detector observes the stalled
    time (execution plus the stall), not the bare execution time."""
    plan = ChaosPlan(faults=[
        FaultSpec("stall", stage="s0", round=0, worker=0, seconds=4.0)])
    p = _build(chaos=plan, retry=RetryPolicy(share_timeout_s=100.0))
    out = p.run(iter(_source()))
    assert np.array_equal(_oracle(), np.asarray(out))
    assert not plan.pending()
    assert _ft_events(p.directory.audit, "worker_failed",
                      "share_failover") == []
    det = p._last_ft.detector("s0")
    # 3 rounds of one share: still warming up, so mean is the plain mean
    assert det.n == N_CHUNKS // 4 <= det.warmup
    assert det.mean * det.n >= 4.0


# ----------------------------------------------------- engine interlocks


def test_ft_requires_window_engine():
    p = _build(retry=RetryPolicy())
    with pytest.raises(ValueError, match="window_chunks"):
        p.run(iter(_source()), window_chunks=1)


def test_fresh_coords_come_from_ingress_edge():
    """Re-execution counters are reserved from edge0 (the one allocator
    whose blocks are globally collision-free); plain mode has none."""
    p = _build()
    before = p.directory.session("edge0").chunks
    counters, epoch = p._ft_fresh_coords(4)
    assert counters == list(range(before, before + 4))
    assert p.directory.session("edge0").chunks == before + 4
    assert epoch == p.directory.epoch
    plain = Pipeline(_stages8(), SecureStreamConfig(mode="plain"),
                     window_chunks=4)
    assert plain._ft_fresh_coords(4) is None


def test_enclave_mode_chaos_bit_identical():
    """The fused in-enclave kernel path: re-sealing under separate
    outbound (nonce, counter) coordinates (the kernel's new FT inputs)
    preserves bit-identity through crash-retry and tamper-replay."""
    sts = [Stage("a", "scale_f32", const=1.5, workers=2),
           Stage("b", "relu_f32"),
           Stage("sink", "custom", reduce_fn=_sum_reduce,
                 reduce_init=None)]
    src = [jnp.asarray(
        np.random.RandomState(3 + i).rand(32).astype(np.float32) - 0.5)
        for i in range(8)]

    def build(chaos=None, retry=None):
        return Pipeline(sts, SecureStreamConfig(mode="enclave"), seed=3,
                        directory=KeyDirectory(seed=3, epoch_history=64),
                        window_chunks=4, retry=retry, chaos=chaos)

    oracle = np.asarray(build().run(iter(src)))
    plan = ChaosPlan(faults=[
        FaultSpec("crash", stage="a", round=0, worker=1, when="after"),
        FaultSpec("tamper", stage="b", round=0, worker=0, rows=1),
    ])
    out = np.asarray(build(chaos=plan, retry=RetryPolicy()).run(iter(src)))
    assert not plan.pending()
    assert np.array_equal(oracle, out)


def test_enclave_rows_kernel_out_coords_match_ref():
    """Kernel-level parity for the FT re-seal inputs: with distinct
    outbound (nonce, counter) columns, the fused kernel matches the
    pure-jnp oracle, and the default (no out coords) is unchanged."""
    from repro.kernels.enclave_map.enclave_map import enclave_apply_rows
    from repro.kernels.enclave_map.ref import enclave_apply_rows_ref

    rng = np.random.default_rng(0)
    R = 8
    kin = jnp.asarray(rng.integers(0, 2**32, (R, 8), dtype=np.uint32))
    kout = jnp.asarray(rng.integers(0, 2**32, (R, 8), dtype=np.uint32))
    data = jnp.asarray(rng.integers(0, 2**32, (R, 16), dtype=np.uint32))
    nin = jnp.asarray(rng.integers(0, 2**32, (R, 3), dtype=np.uint32))
    nout = jnp.asarray(rng.integers(0, 2**32, (R, 3), dtype=np.uint32))
    cin = jnp.arange(1, R + 1, dtype=jnp.uint32)
    cout = jnp.arange(101, R + 101, dtype=jnp.uint32)
    got = enclave_apply_rows(kin, kout, nin, cin, data, op="scale_f32",
                             const=2.0, block_rows=R, interpret=True,
                             nonces_out=nout, counters_out=cout)
    want = enclave_apply_rows_ref(np.asarray(kin), np.asarray(kout),
                                  np.asarray(nin), np.asarray(cin),
                                  np.asarray(data), op="scale_f32",
                                  const=2.0, nonces_out=np.asarray(nout),
                                  counters_out=np.asarray(cout))
    assert np.array_equal(np.asarray(got), np.asarray(want))
    # distinct out-coords genuinely change the ciphertext
    same = enclave_apply_rows(kin, kout, nin, cin, data, op="scale_f32",
                              const=2.0, block_rows=R, interpret=True)
    assert not np.array_equal(np.asarray(got), np.asarray(same))


# ------------------------------------------------------------- DSL surface


def test_dsl_retry_and_chaos_verbs():
    src = _source()
    plan = ChaosPlan(faults=[
        FaultSpec("crash", stage="m", round=0, worker=0)])
    from repro.dsl import stream
    b = (stream(src).map("scale_f32", const=2.0, name="m", workers=2)
         .reduce(_sum_reduce, None, name="r")
         .secure("encrypted").window(4)
         .retry(RetryPolicy(max_attempts=2)).chaos(plan))
    assert b.retry_policy.max_attempts == 2
    assert b.chaos_plan is plan
    out = b.run()
    want = (stream(src).map("scale_f32", const=2.0, name="m", workers=2)
            .reduce(_sum_reduce, None, name="r")
            .secure("encrypted").window(4)).run()
    assert np.array_equal(np.asarray(want), np.asarray(out))
    assert plan.events == [("crash", "m", 0, 0)]
    seq = b.pipeline.directory.audit.kind_sequence(
        "worker_failed", "share_retried")
    assert seq == ["worker_failed", "share_retried"]


# ---------------------------------------------------------------- ft units


def test_replay_buffer_retain_ack_watermark():
    buf = ReplayBuffer()

    class _W(list):
        pass

    w = _W([1, 2, 3])
    buf.retain("s0", 0, [w])
    assert buf.retained_rows() == 3
    assert buf.get("s0", 0) == [w]
    assert buf.watermark() == -1
    buf.ack("s0", 0)
    assert buf.retained_rows() == 0 and buf.get("s0", 0) is None
    buf.retain("s1", 1, [w, w])
    buf.ack("s1", 1)
    assert buf.watermark() == 0          # min over stages: s0 acked 0


def test_backup_dispatcher_track_and_reissue():
    d = BackupDispatcher(num_workers=3)
    d.track(7, 2)                        # engine-chosen assignment
    assert d.reissue(7) == 0             # backup goes to the NEXT worker
    assert d.complete(7) is True
    assert d.complete(7) is False and d.duplicates == 1
    assert d.reissue(7) is None          # completed: nothing to reissue


def test_retry_policy_backoff_and_timeout():
    pol = RetryPolicy(backoff_base_s=0.1, backoff_factor=2.0,
                      max_backoff_s=0.3)
    assert pol.backoff(1) == pytest.approx(0.1)
    assert pol.backoff(2) == pytest.approx(0.2)
    assert pol.backoff(5) == pytest.approx(0.3)      # capped
    assert RetryPolicy().backoff(3) == 0.0           # immediate default
    det = StragglerDetector()
    pol2 = RetryPolicy(min_timeout_s=0.05, timeout_scale=4.0)
    assert pol2.timeout_for(det) == 0.05             # cold: floor
    for _ in range(det.warmup + 3):
        det.observe(0.1)
    assert pol2.timeout_for(det) == pytest.approx(4.0 * det.mean)
    assert RetryPolicy(share_timeout_s=1.5).timeout_for(det) == 1.5
