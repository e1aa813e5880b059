"""repro.obs: metrics registry, span tracer, security audit log, and the
telemetry wiring through the streaming engine (PR 6 acceptance).

The acceptance run mirrors test_attest's 8-stage rekey+revocation
pipeline, traced: per-window/per-stage/per-worker spans export as valid
Chrome-trace JSON, the audit log's event counts exactly match engine
behaviour (k tampered rows -> exactly k ``mac_failure`` events, rekeys
and the revocation in stream order), and output is bit-identical with
tracing on vs off.
"""
import dataclasses
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import (AuditLog, Counter, Gauge, Histogram, MetricsRegistry,
                       NULL_TRACER, REGISTRY, Tracer)
from repro.obs.trace import _NOOP_SPAN


# ------------------------------------------------------------------ metrics


def test_registry_get_or_create_returns_same_object():
    r = MetricsRegistry()
    c = r.counter("x.count")
    c.inc()
    c.inc(2)
    assert r.counter("x.count") is c          # hot-path refs stay valid
    assert c.value == 3
    r.reset()
    assert r.counter("x.count") is c and c.value == 0


def test_registry_kind_collision_is_an_error():
    r = MetricsRegistry()
    r.counter("x")
    with pytest.raises(TypeError):
        r.gauge("x")
    with pytest.raises(TypeError):
        r.histogram("x")


def test_gauge_and_snapshot():
    r = MetricsRegistry()
    r.gauge("depth").set(7)
    r.counter("n").inc(5)
    snap = r.snapshot()
    assert snap["depth"] == 7 and snap["n"] == 5
    r.reset(prefix="dep")
    assert r.gauge("depth").value == 0 and r.counter("n").value == 5


def test_histogram_percentiles_and_eviction():
    h = Histogram("lat", max_samples=100)
    assert h.percentile(50) is None and h.mean is None
    for v in range(1, 101):
        h.observe(float(v))
    assert h.percentile(0) == 1.0 and h.percentile(100) == 100.0
    assert 50.0 <= h.percentile(50) <= 51.0   # exact index, not interp
    s = h.summary()
    assert s["count"] == 100 and s["max"] == 100.0
    assert s["p95"] == pytest.approx(95.0, abs=1.0)
    # eviction drops the OLDEST sample once past max_samples
    h.observe(1000.0)
    assert h.count == 101                     # lifetime count keeps going
    assert h.percentile(0) == 2.0             # sample 1.0 was evicted
    assert h.summary()["max"] == 1000.0


# ------------------------------------------------------------------- tracer


def test_null_tracer_is_a_shared_noop():
    assert NULL_TRACER.enabled is False
    s1 = NULL_TRACER.span("anything", x=1)
    s2 = NULL_TRACER.span("else")
    assert s1 is s2 is _NOOP_SPAN             # no allocation per span
    with s1:
        pass
    assert NULL_TRACER.instant("mark") is None


def test_tracer_parent_child_and_find():
    tr = Tracer()
    with tr.span("outer", cat="pipeline", track="main", w=1):
        with tr.span("inner", cat="dispatch", track="s0/w0"):
            pass
        tr.instant("mark", track="main")
    assert len(tr) == 3
    outer, inner, mark = tr.spans
    assert inner.parent == outer.id and mark.parent == outer.id
    assert outer.parent is None
    assert outer.end is not None and outer.dur >= inner.dur
    assert [s.name for s in tr.children(outer)] == ["inner", "mark"]
    assert tr.find("inner")[0] is inner
    assert tr.find(cat="dispatch") == [inner]


def test_tracer_chrome_export_roundtrip(tmp_path):
    tr = Tracer()
    with tr.span("a", track="main", rows=4):
        with tr.span("b", track="s0/w1"):
            pass
    tr.instant("flip", cat="security", track="ingress", epoch=1)
    path = tmp_path / "trace.json"
    doc = tr.export_chrome(str(path))
    loaded = json.loads(path.read_text())     # valid JSON on disk
    assert loaded == doc
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    names = {e["args"]["name"] for e in meta}
    assert {"repro.pipeline", "main", "s0/w1", "ingress"} <= names
    xs = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert set(xs) == {"a", "b"} and all("dur" in e for e in xs.values())
    assert xs["a"]["args"]["rows"] == 4
    inst = [e for e in evs if e["ph"] == "i"]
    assert len(inst) == 1 and inst[0]["name"] == "flip"
    # distinct tracks land on distinct tids
    assert xs["a"]["tid"] != xs["b"]["tid"]
    assert "flip" in tr.timeline() and "a" in tr.timeline()


# ---------------------------------------------------------------- audit log


def test_audit_log_order_counts_and_unknown_kind():
    log = AuditLog()
    log.record("rekey", epoch=1)
    log.record("mac_failure", stage="s0", row=3, epoch=0)
    log.record("rekey", epoch=2)
    log.record("revocation", worker="s0/w1")
    assert len(log) == 4
    assert log.kind_sequence() == ["rekey", "mac_failure", "rekey",
                                   "revocation"]
    assert log.kind_sequence("rekey", "revocation") == \
        ["rekey", "rekey", "revocation"]
    assert [e.seq for e in log] == [0, 1, 2, 3]
    assert log.counts()["rekey"] == 2 and log.counts()["eviction"] == 0
    assert log.events("mac_failure")[0].detail["row"] == 3
    assert log.summary() == {"events": 4, "dropped": 0, "rekey": 2,
                             "mac_failure": 1, "revocation": 1}
    assert log.dump()[0] == {"seq": 0, "kind": "rekey", "epoch": 1}
    assert "rekey" in str(log.events("rekey")[0])
    with pytest.raises(ValueError):
        log.record("typo_kind")
    with pytest.raises(ValueError):
        log.events("typo_kind")


def test_audit_log_is_bounded():
    log = AuditLog(max_events=4)
    for i in range(10):
        log.record("rekey", epoch=i)
    assert len(log) == 4 and log.dropped == 6
    assert [e.detail["epoch"] for e in log] == [6, 7, 8, 9]
    assert log.summary()["dropped"] == 6


# ----------------------------------------------- directory lifecycle events


def _two_party_directory(seed=0):
    from repro.attest.directory import KeyDirectory
    from repro.attest.measure import IO_ENDPOINT
    d = KeyDirectory(seed=seed)
    d.enroll("a", IO_ENDPOINT, allow=True)
    d.enroll("b", IO_ENDPOINT, allow=True)
    d.establish("e", "a", "b")
    return d


def test_directory_audits_rekey_and_revocation_in_order():
    d = _two_party_directory()
    d.advance_epoch()
    d.advance_epoch()
    d.revoke("b")
    assert d.audit.kind_sequence("rekey", "revocation") == \
        ["rekey", "rekey", "revocation"]
    assert [e.detail["epoch"] for e in d.audit.events("rekey")] == [1, 2]
    rev = d.audit.events("revocation")[0]
    assert rev.detail["worker"] == "b" and rev.detail["edges"] == ["e"]


def test_directory_audits_quote_rejection():
    d = _two_party_directory()
    d.enroll("evil", b"\x13" * 32)            # measurement NOT allowlisted
    assert not d.is_admitted("evil")
    rejected = d.audit.events("quote_rejected")
    assert rejected and rejected[-1].detail["worker"] == "evil"
    d.revoke("b")
    assert not d.is_admitted("b")
    assert d.audit.events("quote_rejected")[-1].detail["reason"] == "revoked"


def test_directory_audits_nonce_exhaustion():
    from repro.crypto.keys import NONCE_COUNTER_MAX, NonceExhaustedError
    d = _two_party_directory(seed=1)
    d.session("e").chunks = NONCE_COUNTER_MAX
    assert d.next_counters("e", 1) == NONCE_COUNTER_MAX   # last valid one
    with pytest.raises(NonceExhaustedError):
        d.next_counters("e", 1)
    ev = d.audit.events("nonce_exhausted")
    assert len(ev) == 1 and ev[0].detail["edge"] == "e"


# -------------------------------------------------------- legacy count shims


def test_host_sync_shim_reads_the_registered_counter():
    from repro.core import pipeline as P
    P.reset_host_sync_count()
    assert P.host_sync_count() == 0
    REGISTRY.counter("pipeline.host_syncs").inc(3)
    assert P.host_sync_count() == 3
    P.reset_host_sync_count()
    assert REGISTRY.counter("pipeline.host_syncs").value == 0


def test_exchange_call_shim_reads_the_registered_counter():
    from repro.dist import collectives
    c0 = collectives.exchange_call_count()
    REGISTRY.counter("dist.exchange_calls").inc()
    assert collectives.exchange_call_count() == c0 + 1


def test_fastpath_stats_shim_reads_the_registered_counters():
    from repro.crypto import aead
    aead.reset_fastpath_stats()
    s = aead.fastpath_stats()
    assert s["compiles"] == 0 and s["hits"] == 0
    assert REGISTRY.get("aead.fastpath.compiles") is not None
    REGISTRY.counter("aead.fastpath.hits").inc(2)
    assert aead.fastpath_stats()["hits"] == 2
    aead.reset_fastpath_stats()


# ------------------------------------------------------- StageMetrics fixes


def test_stage_metrics_distinguish_unmeasured_from_zero():
    from repro.core.pipeline import StageMetrics
    m = StageMetrics()
    assert m.throughput_mbps is None          # nothing measured yet
    assert m.mac_failure_rate is None         # no rows seen yet
    m.seconds = 0.5                           # time passed, zero payload
    assert m.throughput_mbps == 0.0
    m.bytes = 1_000_000
    assert m.throughput_mbps == 2.0
    m.chunks, m.mac_failures = 6, 2
    assert m.mac_failure_rate == pytest.approx(0.25)
    m2 = StageMetrics(chunks=0, mac_failures=4, seconds=1.0)
    assert m2.mac_failure_rate == 1.0 and m2.throughput_mbps == 0.0


def test_report_is_none_safe_before_any_run():
    from repro.configs.base import SecureStreamConfig
    from repro.core.pipeline import Pipeline, Stage
    p = Pipeline([Stage("s", op="identity")],
                 SecureStreamConfig(mode="plain"))
    rep = p.report()["s"]
    assert rep["throughput_mbps"] is None
    assert rep["mac_failure_rate"] is None
    assert rep["chunks"] == 0 and rep["mac_failures"] == 0


# ------------------------------------------------- engine integration (e2e)


def _stage8():
    from repro.core.pipeline import Stage
    return [Stage(f"s{i}", op="scale_f32", const=1.0 + 0.125 * i,
                  workers=2 if i % 3 == 0 else 1) for i in range(8)]


def _src(n=9):
    return [jnp.asarray(np.random.default_rng(i).standard_normal(
        (64,)).astype(np.float32)) for i in range(n)]


def _run_8stage(src, tracer=None, monitor=None):
    """One 8-stage encrypted run with rekey_every_n=3 and a mid-stream
    revocation of s3/w1; returns (pipeline, outputs, epoch_at_revoke)."""
    from repro.attest.directory import KeyDirectory
    from repro.configs.base import SecureStreamConfig
    from repro.core.pipeline import Pipeline
    p = Pipeline(_stage8(), SecureStreamConfig(mode="encrypted"),
                 directory=KeyDirectory(seed=0, epoch_history=64),
                 window_chunks=8)
    state = {}

    def source():
        for i, c in enumerate(src):
            if i == 4:
                state["epoch_at_revoke"] = p.directory.epoch
                p.directory.revoke(Pipeline.worker_id("s3", 1))
            yield c

    got = []
    p.run(source(), on_result=lambda r: got.append(np.asarray(r)),
          rekey_every_n=3, tracer=tracer, monitor=monitor)
    return p, got, state["epoch_at_revoke"]


def test_traced_8stage_rekey_revocation_acceptance(tmp_path):
    """THE acceptance run: spans + audit + bit-identity, one traced run
    vs one untraced run of the same rekey+revocation stream."""
    src = _src()
    p_off, got_off, _ = _run_8stage(src)                 # tracing off
    tr = Tracer()
    p, got, epoch_at_revoke = _run_8stage(src, tracer=tr)

    # tracing must not change a single bit of the stream
    assert len(got) == len(got_off) == len(src)
    for a, b in zip(got, got_off):
        assert np.array_equal(a, b)

    # -- audit: counts exactly match engine behaviour, in stream order --
    audit = p.directory.audit
    assert audit.counts()["rekey"] == p.directory.epoch >= 2
    assert audit.counts()["revocation"] == 1
    assert audit.counts()["mac_failure"] == 0            # nothing tampered
    assert audit.counts()["eviction"] == 1
    ev = audit.events("eviction")[0]
    assert ev.detail["worker"] == "s3/w1"
    # the revocation sits between exactly the rekeys that preceded and
    # followed it: every rekey to an epoch <= epoch_at_revoke comes
    # before it, every later rekey after
    rev_seq = audit.events("revocation")[0].seq
    for e in audit.events("rekey"):
        if e.detail["epoch"] <= epoch_at_revoke:
            assert e.seq < rev_seq
        else:
            assert e.seq > rev_seq
    # revocation precedes the engine's first skipped dispatch (eviction)
    assert rev_seq < ev.seq

    # -- spans: per-window, per-stage, per-worker ------------------------
    assert tr.find("pipeline.run")
    assert tr.find("ingress.seal") and tr.find("stage.dispatch")
    assert tr.find("sync.verdicts") and tr.find("egress.open")
    assert len(tr.find("rekey")) == p.directory.epoch    # one per flip
    tracks = {s.track for s in tr.spans}
    assert "ingress" in tracks and "sink" in tracks
    assert "s0/w0" in tracks and "s0/w1" in tracks       # per-worker lanes
    # every stage got at least one dispatch span on its own lane
    stage_lanes = {s.track for s in tr.find("stage.dispatch")}
    assert stage_lanes == {f"s{i}" for i in range(8)}
    # phase spans nest under their stage's dispatch span
    open_spans = tr.find("enclave.open")
    assert open_spans
    parents = {tr.spans[s.parent].name for s in open_spans}
    assert parents == {"stage.dispatch"}

    # -- Chrome export: valid, loadable JSON with named lanes ------------
    path = tmp_path / "trace.json"
    doc = tr.export_chrome(str(path))
    loaded = json.loads(path.read_text())
    assert loaded["traceEvents"]
    phs = {e["ph"] for e in loaded["traceEvents"]}
    assert {"X", "M", "i"} <= phs
    lane_names = {e["args"]["name"] for e in loaded["traceEvents"]
                  if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"ingress", "sink", "s3/w0"} <= lane_names
    assert doc == loaded

    # untraced pipeline defaults to the shared zero-cost NULL tracer
    assert p_off.tracer is NULL_TRACER


def test_k_tampered_rows_yield_exactly_k_audit_events(monkeypatch):
    """Tamper k sealed rows on stage s1's output edge: the next stage's
    batched open drops exactly those rows, the audit log records exactly
    k ``mac_failure`` events carrying each row's counter + epoch."""
    from repro.attest.directory import KeyDirectory
    from repro.configs.base import SecureStreamConfig
    from repro.core.enclave import EnclaveExecutor
    from repro.core.pipeline import Pipeline, Stage

    TAMPER = {1, 3, 6}
    k = len(TAMPER)
    pending = set(TAMPER)

    orig_pool = Pipeline._worker_pool

    def patched_pool(self, i, st):
        pool = orig_pool(self, i, st)
        if st.name != "s1":
            return pool
        for ex in pool:
            orig_rsw = ex.run_static_window

            def tampered(op, const, win, _orig=orig_rsw):
                out, ok = _orig(op, const, win)
                hit = [j for j, c in enumerate(out.counters)
                       if c in pending]
                if hit:
                    pending.difference_update(out.counters[j] for j in hit)
                    words = out.words
                    for j in hit:             # flip one word, keep the tag
                        words = words.at[j, 0].add(np.uint32(1))
                    out = dataclasses.replace(out, words=words)
                return out, ok

            ex.run_static_window = tampered
        return pool

    monkeypatch.setattr(Pipeline, "_worker_pool", patched_pool)

    stages = [Stage(f"s{i}", op="scale_f32", const=1.01) for i in range(4)]
    d = KeyDirectory(seed=0)
    p = Pipeline(stages, SecureStreamConfig(mode="encrypted"),
                 directory=d, window_chunks=8)
    src = _src(9)
    got = []
    p.run(iter(src), on_result=lambda r: got.append(np.asarray(r)))

    assert not pending                         # every target row was hit
    # tampered rows are dropped at s2 (the stage that opens s1's output)
    assert len(got) == len(src) - k
    failures = d.audit.events("mac_failure")
    assert len(failures) == k                  # EXACTLY k events, no more
    assert sorted(e.detail["row"] for e in failures) == sorted(TAMPER)
    assert all(e.detail["stage"] == "s2" for e in failures)
    assert all("epoch" in e.detail for e in failures)
    assert p.metrics["s2"].mac_failures == k
    assert p.metrics["s2"].mac_failure_rate == pytest.approx(
        k / len(src))
    rep = p.report()
    assert rep["audit"]["mac_failure"] == k
    # downstream stages only ever saw the survivors
    assert p.metrics["s3"].chunks == len(src) - k


def test_dsl_trace_and_per_stage_histograms():
    """``stream(...).trace()`` attaches a tracer through the compiler,
    and the engine feeds the per-stage latency histograms + queue-depth
    gauges registered in the process-wide REGISTRY."""
    from repro.dsl import stream

    REGISTRY.reset(prefix="pipeline.stage.obs_hist")
    src = _src(8)
    sb = (stream(src)
          .map("scale_f32", const=1.25, name="obs_hist", workers=2)
          .secure("encrypted").window(4).trace())
    assert sb.tracer is not None and sb.tracer.enabled
    got = []
    sb.run(on_result=lambda r: got.append(np.asarray(r)))
    assert len(got) == len(src)
    assert sb.tracer is sb.pipeline.tracer
    assert sb.tracer.find("stage.dispatch")
    h = REGISTRY.get("pipeline.stage.obs_hist.window_seconds")
    assert h is not None and h.count >= 1
    assert h.summary()["p50"] is not None
    assert REGISTRY.get("pipeline.stage.obs_hist.queue_rows") is not None
    # untraced builders stay untraced (zero-cost default)
    assert stream(src).map("identity").tracer is None


def test_chunked_oracle_engine_is_traced_and_audited(monkeypatch):
    """The window_chunks=1 per-chunk oracle engine feeds the same
    telemetry: spans, host-sync counter, and mac_failure audit events."""
    from repro.attest.directory import KeyDirectory
    from repro.configs.base import SecureStreamConfig
    from repro.core import pipeline as P
    from repro.core.pipeline import Pipeline, Stage

    d = KeyDirectory(seed=0)
    p = Pipeline([Stage("s0", op="scale_f32", const=1.5)],
                 SecureStreamConfig(mode="encrypted"), directory=d,
                 window_chunks=1)
    tr = Tracer()
    src = _src(3)
    got = []
    P.reset_host_sync_count()
    p.run(iter(src), on_result=lambda r: got.append(np.asarray(r)),
          tracer=tr)
    assert len(got) == 3
    assert P.host_sync_count() == 6            # per-chunk: stage + egress
    assert len(tr.find("stage.chunk")) == 3
    assert tr.find("pipeline.run")
    assert d.audit.counts()["mac_failure"] == 0


# ------------------------------------------- window ids and host transfers

# every window-engine span and the window id it carries
_WINDOW_SPANS = ("ingress.fill", "ingress.seal", "stage.dispatch",
                 "enclave.open", "enclave.op", "enclave.seal",
                 "sync.verdicts", "stage.merge", "egress.open",
                 "reduce.fold", "reduce.fn")
_HOPS = ("mapper", "filter")
_N_CHUNKS = 20                     # two 8-chunk windows and a ragged tail
_TRANSFERS = ("device.to_host", "device.to_host_bytes",
              "device.to_host_seconds", "pipeline.host_syncs")


@pytest.fixture(scope="module")
def flights_runs():
    """One traced run of a small DelayedFlights job per mode: map,
    filter (2 workers each) and the carrier_delay_stats fold, with the
    transfer counters read over the run."""
    from repro.data.synthetic import flight_chunks
    from repro.dsl import stream
    chunks = list(flight_chunks(16 * _N_CHUNKS, 16, seed=3))
    out = {}
    for mode in ("plain", "encrypted", "enclave"):
        p = (stream().map("identity", name=_HOPS[0], workers=2)
             .filter("delay_filter_u32", const=15, name=_HOPS[1],
                     workers=2)
             .reduce("carrier_delay_stats", name="sink").window(4)
             .seed(0).build(mode))
        tr = Tracer()
        c0 = {k: REGISTRY.counter(k).value for k in _TRANSFERS}
        res = p.run((jnp.asarray(c) for c in chunks), tracer=tr)
        out[mode] = (tr, {k: REGISTRY.counter(k).value - c0[k]
                          for k in _TRANSFERS}, res)
    return out


@pytest.mark.parametrize("mode", ["plain", "encrypted", "enclave"])
def test_every_window_engine_span_carries_its_window(flights_runs, mode):
    tr, _, _ = flights_runs[mode]
    names = {s.name for s in tr.spans} - {"pipeline.run"}
    want = set(_WINDOW_SPANS)
    if mode == "plain":               # the plain executor has no phases
        want -= {"enclave.open", "enclave.op", "enclave.seal"}
    assert names == want
    for name in want:
        ids = {s.args["window"] for s in tr.find(name)}
        assert ids == {0, 1, 2}, name          # 0..n-1, no gaps
    # one fill and one seal per window, siblings under pipeline.run
    fills, seals = tr.find("ingress.fill"), tr.find("ingress.seal")
    assert [s.args["window"] for s in fills] == [0, 1, 2]
    assert [s.args["rows"] for s in fills] == [8, 8, 4]
    run = tr.find("pipeline.run")[0]
    assert {s.parent for s in fills + seals} == {run.id}
    # the engine samples no per-window tracer counters
    assert tr.counters == []


@pytest.mark.parametrize("mode", ["plain", "encrypted", "enclave"])
def test_one_window_is_followed_from_source_to_fold(flights_runs, mode):
    tr, _, _ = flights_runs[mode]

    def of(name, w, track=None):
        return [s for s in tr.find(name) if s.args["window"] == w
                and track in (None, s.track)]

    for w in (0, 1, 2):
        fill, = of("ingress.fill", w)
        seal, = of("ingress.seal", w)
        assert fill.end <= seal.start
        t = seal.end
        for hop in _HOPS:
            disp, = of("stage.dispatch", w, hop)
            sync, = of("sync.verdicts", w, hop)
            merge, = of("stage.merge", w, hop)
            assert t <= disp.start <= disp.end <= sync.start <= sync.end \
                <= merge.start
            t = merge.end
        opened, = of("egress.open", w)
        sync, = of("sync.verdicts", w, "sink")
        fold, = of("reduce.fold", w)
        assert t <= opened.start <= sync.start <= fold.start
        fns = of("reduce.fn", w)
        assert len(fns) == fold.args["rows"] == (4 if w == 2 else 8)
        assert {s.parent for s in fns} == {fold.id}
    # the double buffer: window 0 is handed on only once window 1 is sealed
    assert of("ingress.seal", 1)[0].end \
        <= of("stage.dispatch", 0, _HOPS[0])[0].start
    assert len(tr.find("reduce.fn")) == _N_CHUNKS


@pytest.mark.parametrize("mode", ["plain", "encrypted", "enclave"])
def test_to_host_counts_the_reducer_and_the_verdict_syncs(flights_runs,
                                                          mode):
    """The sink brings each window's opened rows to the host in one
    transfer, and the reducer brings nothing more; each hop's and the
    sink's verdict sync brings one vector per window in the sealed modes,
    and none in plain mode, where verdicts never leave the host."""
    _, got, res = flights_runs[mode]
    syncs = got["pipeline.host_syncs"]
    assert syncs == 3 * 3                       # 3 windows x (2 hops + sink)
    verdicts = 0 if mode == "plain" else syncs
    assert got["device.to_host"] == 3 + verdicts        # one group a window
    # every chunk's 16 records of 16 uint32 words; one bool per verdict row
    assert got["device.to_host_bytes"] == \
        _N_CHUNKS * 16 * 16 * 4 + (0 if mode == "plain" else 3 * _N_CHUNKS)
    assert got["device.to_host_seconds"] > 0
    assert res["count"].sum() > 0


def _flights_job(mode, reduce_fn, init, wc=4):
    """The fixture's DelayedFlights job with the reducer given."""
    from repro.dsl import stream
    return (stream().map("identity", name=_HOPS[0], workers=2)
            .filter("delay_filter_u32", const=15, name=_HOPS[1], workers=2)
            .reduce(reduce_fn, init, name="sink").window(wc)
            .seed(0).build(mode))


def _flights_chunks(n=_N_CHUNKS):
    from repro.data.synthetic import flight_chunks
    return list(flight_chunks(16 * n, 16, seed=3))


def _filtered(chunk):
    """NumPy reference of the job's map and filter on one chunk."""
    return np.where(chunk[:, 1:2].astype(np.int32) > 15, chunk, 0)


def _both_reducers():
    """carrier_delay_stats and sum folded side by side, recording every
    argument the sink passes."""
    from repro.dsl.reducers import resolve_reducer
    cds, cds_init = resolve_reducer("carrier_delay_stats")
    total, _ = resolve_reducer("sum")
    calls = []

    def fn(acc, chunk):
        calls.append(chunk)
        return cds(acc[0], chunk), total(acc[1], chunk)
    return fn, (cds_init, None), calls


@pytest.mark.parametrize("mode", ["plain", "encrypted", "enclave"])
def test_reducer_gets_each_chunk_once_as_a_host_array(mode):
    """On the window engine the reducer is called once per chunk, in
    stream order, with a read-only NumPy row of the chunk's shape and
    dtype, and folds to what the per-chunk oracle engine folds (over a
    full window and a ragged one: the oracle seals chunk by chunk)."""
    chunks = _flights_chunks(10)
    out = {}
    for wc in (4, 1):
        fn, init, calls = _both_reducers()
        res = _flights_job(mode, fn, init, wc).run(
            jnp.asarray(c) for c in chunks)
        out[wc] = res, calls
    (win_cds, win_sum), calls = out[4]
    (ref_cds, ref_sum), ref_calls = out[1]
    assert len(calls) == len(ref_calls) == len(chunks)
    for x, c in zip(calls, chunks):
        assert isinstance(x, np.ndarray) and not x.flags.writeable
        assert x.shape == c.shape and x.dtype == c.dtype
        assert np.array_equal(x, _filtered(c))            # stream order
    for x, y in zip(calls, ref_calls):
        assert np.array_equal(x, np.asarray(y))
    assert np.array_equal(win_cds["count"], ref_cds["count"])
    assert np.array_equal(win_cds["sum"], ref_cds["sum"])
    assert isinstance(win_sum, np.ndarray)
    assert np.array_equal(win_sum, np.asarray(ref_sum))


@pytest.mark.parametrize("tamper", [(9, 12, 13), (16, 17, 18, 19)],
                         ids=["some-rows", "whole-window"])
@pytest.mark.parametrize("mode", ["encrypted", "enclave"])
def test_sink_never_brings_a_tampered_row_to_the_host(monkeypatch, mode,
                                                      tamper):
    """Tamper k rows of one egress window on the last hop's output edge:
    the sink audits exactly k ``mac_failure`` events, brings only that
    window's verified rows to the host, and the reducer sees only the
    untampered chunks, in stream order."""
    from repro.core import pipeline as P
    from repro.core.pipeline import Pipeline
    from repro.dsl.reducers import resolve_reducer

    k = len(tamper)
    pending = set(tamper)
    orig_pool = Pipeline._worker_pool

    def patched_pool(self, i, st):
        pool = orig_pool(self, i, st)
        if st.name != _HOPS[-1]:
            return pool
        for ex in pool:
            orig_rsw = ex.run_static_window

            def tampered(op, const, win, _orig=orig_rsw, **kw):
                out, ok = _orig(op, const, win, **kw)
                hit = [j for j, c in enumerate(out.counters)
                       if c in pending]
                if hit:
                    pending.difference_update(out.counters[j] for j in hit)
                    words = out.words
                    for j in hit:             # flip one word, keep the tag
                        words = words.at[j, 0].add(np.uint32(1))
                    out = dataclasses.replace(out, words=words)
                return out, ok

            ex.run_static_window = tampered
        return pool

    monkeypatch.setattr(Pipeline, "_worker_pool", patched_pool)
    shapes = []

    def counted(x, _orig=P.to_host):
        shapes.append(np.shape(x))
        return _orig(x)

    monkeypatch.setattr(P, "to_host", counted)

    cds, init = resolve_reducer("carrier_delay_stats")
    calls = []

    def fn(acc, chunk):
        calls.append(chunk)
        return cds(acc, chunk)

    p = _flights_job(mode, fn, init)
    chunks = _flights_chunks()
    nbytes = REGISTRY.counter("device.to_host_bytes")
    b0 = nbytes.value
    res = p.run(jnp.asarray(c) for c in chunks)

    assert not pending                         # every target row was hit
    kept = [_filtered(c) for i, c in enumerate(chunks) if i not in tamper]
    assert len(calls) == len(kept) == _N_CHUNKS - k
    for x, want in zip(calls, kept):
        assert np.array_equal(x, want)
    failures = p.directory.audit.events("mac_failure")
    assert len(failures) == k
    assert sorted(e.detail["row"] for e in failures) == sorted(tamper)
    assert all(e.detail["stage"] == "sink" for e in failures)
    assert p.metrics["sink"].mac_failures == k
    assert p.metrics["sink"].chunks == _N_CHUNKS - k
    # one group a window, the tampered window holding only its verified
    # rows (none at all: no transfer); every other transfer is a verdict
    groups = [s[0] for s in shapes if len(s) == 3]
    want = [8, 8, 4]
    want[min(tamper) // 8] -= k
    assert groups == [n for n in want if n]
    assert len(shapes) - len(groups) == 9
    assert nbytes.value - b0 == (_N_CHUNKS - k) * 16 * 16 * 4 + 3 * _N_CHUNKS
    recs = np.concatenate(kept)
    delay = recs[:, 1].astype(np.int64)
    valid = delay > 0
    carrier = recs[valid, 0].astype(np.int64)
    assert np.array_equal(res["count"],
                          np.bincount(carrier, minlength=20))
    assert np.array_equal(res["sum"], np.bincount(
        carrier, weights=delay[valid], minlength=20))


def test_to_host_equals_np_asarray_and_counts_device_arrays():
    from repro.obs import to_host
    n = REGISTRY.counter("device.to_host")
    nbytes = REGISTRY.counter("device.to_host_bytes")
    xs = [jnp.arange(12, dtype=jnp.uint32).reshape(3, 4),
          jnp.asarray([True, False, True]),
          jnp.linspace(0.0, 1.0, 5, dtype=jnp.float32)]
    n0, b0 = n.value, nbytes.value
    for x in xs:
        got, want = to_host(x), np.asarray(x)
        assert isinstance(got, np.ndarray)
        assert got.dtype == want.dtype and np.array_equal(got, want)
    assert n.value - n0 == 3
    assert nbytes.value - b0 == 12 * 4 + 3 + 5 * 4
    host = np.ones(4)
    assert to_host(host) is host                # nothing moves: uncounted
    assert n.value - n0 == 3


def test_tracer_complete_records_a_span_that_ended_now():
    tr = Tracer()
    with tr.span("outer"):
        t = tr.now()
        s = tr.complete("pull", t, cat="source", track="ingress", window=4)
    outer = tr.find("outer")[0]
    assert s.parent == outer.id and s.track == "ingress"
    assert outer.start <= s.start <= s.end <= outer.end
    assert s.args == {"window": 4}
    assert NULL_TRACER.now() == 0.0
    assert NULL_TRACER.complete("pull", 0.0, window=1) is None
