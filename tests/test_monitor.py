"""Live pipeline health (PR 8 acceptance): PipelineMonitor sliding-window
stage stats, Prometheus/JSON exporters + HTTP scrape endpoint, SLO/stall
Watchdog, and per-hop dispatch accounting.

The acceptance runs mirror test_obs's 8-stage jobs: the dispatch-count
regression gate pins compiled-program launches per stage hop for the
8-stage encrypted (and enclave) window job, a deliberately induced stall
and an injected mac-failure burst each trip the watchdog EXACTLY once
with the matching ``stall``/``slo_breach`` audit event, and output is
bit-identical with monitoring on vs off on the rekey+revocation job.
"""
import dataclasses
import importlib.util
import json
import pathlib
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import (Histogram, MetricsRegistry, NULL_MONITOR,
                       PipelineMonitor, REGISTRY, SLORule, Tracer, Watchdog,
                       dispatch_count, prometheus_text, reset_dispatch_count,
                       serve_metrics, snapshot_json)
from repro.obs.audit import AuditLog

ROOT = pathlib.Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location(
    "check_prometheus", ROOT / "scripts" / "check_prometheus.py")
check_prometheus = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(check_prometheus)


class FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ------------------------------------------- histogram edge cases (satellite)


def test_histogram_empty_and_one_sample_percentiles():
    h = Histogram("h")
    assert h.percentile(0) is None and h.percentile(100) is None
    assert h.mean is None
    assert h.summary() == {"count": 0, "mean": None, "p50": None,
                           "p95": None, "p99": None, "max": None}
    h.observe(3.5)
    # one sample: every percentile IS that sample
    for q in (0, 50, 95, 99, 100):
        assert h.percentile(q) == 3.5
    assert h.mean == 3.5 and h.summary()["max"] == 3.5
    with pytest.raises(ValueError):
        h.percentile(101)
    with pytest.raises(ValueError):
        h.percentile(-0.5)


def test_histogram_eviction_keeps_percentiles_exact():
    """Past max_samples the OLDEST sample drops; percentiles stay exact
    over the retained suffix — including with duplicate values."""
    h = Histogram("h", max_samples=4)
    for v in (5.0, 1.0, 5.0, 3.0):
        h.observe(v)
    h.observe(2.0)                 # evicts the first 5.0, NOT the second
    assert sorted(h._sorted) == [1.0, 2.0, 3.0, 5.0]
    assert h.percentile(0) == 1.0 and h.percentile(100) == 5.0
    assert h.count == 5            # lifetime count unaffected by eviction
    h.observe(0.5)                 # evicts the 1.0
    assert sorted(h._sorted) == [0.5, 2.0, 3.0, 5.0]
    assert h.percentile(0) == 0.5
    # retained window is exactly the last max_samples arrivals
    assert h._order == [5.0, 3.0, 2.0, 0.5]


def test_registry_reset_prefix_selectivity():
    r = MetricsRegistry()
    r.counter("a.x").inc(3)
    r.counter("a.y").inc(4)
    r.gauge("b.x").set(7)
    r.histogram("a.h").observe(1.0)
    r.reset(prefix="a.")
    assert r.counter("a.x").value == 0 and r.counter("a.y").value == 0
    assert r.histogram("a.h").count == 0
    assert r.gauge("b.x").value == 7          # untouched: prefix mismatch
    r.reset()                                  # empty prefix = everything
    assert r.gauge("b.x").value == 0


# ------------------------------------------ chrome counter events (satellite)


def test_tracer_counter_events_export_as_chrome_C(tmp_path):
    tr = Tracer()
    with tr.span("work", track="s0"):
        tr.counter("queue_rows", 16, track="s0")
        tr.counter("queue_rows", 8, track="s0")
    tr.counter("windows_per_s", 12.5, track="s1")
    doc = tr.export_chrome(str(tmp_path / "trace.json"))
    loaded = json.loads((tmp_path / "trace.json").read_text())
    assert loaded == doc
    cs = [e for e in doc["traceEvents"] if e["ph"] == "C"]
    assert len(cs) == 3
    q = [e for e in cs if e["name"] == "queue_rows"]
    assert [e["args"]["value"] for e in q] == [16.0, 8.0]
    # counters land on their track's tid (same lane as the spans)
    span_ev = next(e for e in doc["traceEvents"]
                   if e["ph"] == "X" and e["name"] == "work")
    assert all(e["tid"] == span_ev["tid"] for e in q)
    w = next(e for e in cs if e["name"] == "windows_per_s")
    assert w["args"]["value"] == 12.5 and w["tid"] != span_ev["tid"]
    # timestamps are monotone non-decreasing within a track
    assert q[0]["ts"] <= q[1]["ts"]


def test_null_tracer_counter_is_noop():
    from repro.obs import NULL_TRACER
    assert NULL_TRACER.counter("anything", 1.0) is None


# ------------------------------------------------------- monitor unit tests


def test_monitor_sliding_window_evicts_by_time():
    clk = FakeClock()
    mon = PipelineMonitor(window_seconds=10.0, clock=clk)
    for _ in range(4):
        mon.record_window("s0", rows=8, bytes=800, seconds=0.1)
        clk.advance(1.0)
    st = mon.stage_stats("s0")
    assert st["windows"] == 4 and st["windows_total"] == 4
    assert st["rows_per_s"] == pytest.approx(32 / 4.0)   # span = elapsed 4s
    clk.advance(20.0)                   # everything slides out of horizon
    st = mon.stage_stats("s0")
    assert st["windows"] == 0 and st["windows_total"] == 4
    assert st["rows_per_s"] == 0.0 and st["p95_s"] is None


def test_monitor_worker_skew_and_failure_rate():
    clk = FakeClock()
    mon = PipelineMonitor(clock=clk)
    mon.record_window("s0", rows=8, ok_rows=6, seconds=0.1,
                      worker_rows={0: 6, 1: 2})
    st = mon.stage_stats("s0")
    assert st["worker_rows"] == {0: 6, 1: 2}
    assert st["worker_skew"] == pytest.approx(6 / 4.0)   # max/mean
    assert st["mac_failures"] == 2
    assert st["mac_failure_rate"] == pytest.approx(0.25)
    assert mon.stage_stats("nope") is None


def test_monitor_audit_rates_are_timestamped_on_ingest():
    clk = FakeClock()
    mon = PipelineMonitor(window_seconds=10.0, clock=clk)
    log = AuditLog()

    class Dir:
        epoch = 3
        audit = log

    class P:
        directory = Dir()

    mon.attach(P())
    log.record("rekey", epoch=1)
    log.record("rekey", epoch=2)
    clk.advance(5.0)
    mon.record_window("s0", rows=1, seconds=0.01, min_epoch=1)
    snap = mon.snapshot()
    assert snap["pipeline"]["rekey_per_s"] == pytest.approx(2 / 5.0)
    assert snap["stages"]["s0"]["epoch_lag"] == 2        # 3 - 1
    clk.advance(30.0)                   # rekey stamps slide out
    assert "rekey_per_s" not in mon.snapshot()["pipeline"]


def test_null_monitor_is_inert():
    assert NULL_MONITOR.enabled is False
    NULL_MONITOR.record_window("s", rows=1)
    assert NULL_MONITOR.snapshot()["stages"] == {}


# ------------------------------------------------------------------ watchdog


def test_watchdog_stall_trips_exactly_once_with_audit_event():
    clk = FakeClock()
    mon = PipelineMonitor(clock=clk)
    log = AuditLog()
    fired = []
    wd = Watchdog(mon, [SLORule("no-stall", stall_seconds=5.0)],
                  on_breach=[fired.append], audit=log)
    mon.record_window("s0", rows=8, seconds=0.01)
    assert mon.check() == [] and fired == []
    clk.advance(6.0)                    # deliberately induced stall
    breaches = mon.check()
    assert [b.rule for b in breaches] == ["no-stall"]
    assert breaches[0].kind == "stall"
    clk.advance(6.0)
    assert mon.check() == []            # latched: trips EXACTLY once
    assert len(fired) == 1
    events = log.events("stall")
    assert len(events) == 1             # the matching audit event
    assert events[0].detail["rule"] == "no-stall"
    assert events[0].detail["metric"] == "last_progress_age_s"
    assert wd.breached() == ["no-stall"]
    # progress recovers the rule; a fresh stall re-fires
    mon.record_window("s0", rows=8, seconds=0.01)
    assert wd.breached() == []
    clk.advance(6.0)
    assert [b.rule for b in mon.check()] == ["no-stall"]
    assert len(log.events("stall")) == 2


def test_watchdog_rule_limits_and_callback_order():
    clk = FakeClock()
    mon = PipelineMonitor(window_seconds=10.0, clock=clk)
    order = []
    wd = Watchdog(mon, [
        SLORule("latency", stage="s0", max_p95_seconds=0.5),
        SLORule("throughput", stage="s0", min_windows_per_s=0.01),
    ], on_breach=[lambda b: order.append(("first", b.rule)),
                  lambda b: order.append(("second", b.rule))],
        audit=AuditLog())
    clk.advance(1.0)
    mon.record_window("s0", rows=8, seconds=2.0)    # p95 breach
    assert order == [("first", "latency"), ("second", "latency")]
    b = wd.fired[0]
    assert b.metric == "p95_s" and b.value == 2.0 and b.limit == 0.5
    assert b.stage == "s0"
    # unattached-stage rules never fire before data exists
    wd2 = Watchdog(mon, [SLORule("ghost", stage="zzz", min_mbps=1e9)],
                   audit=AuditLog())
    assert wd2.check() == []


def test_watchdog_unattached_fallback_audit_log():
    mon = PipelineMonitor(clock=FakeClock())
    wd = Watchdog(mon, [SLORule("r", stall_seconds=1.0)])
    assert isinstance(wd.audit, AuditLog)


# ----------------------------------------------------------------- exporters


def _loaded_monitor():
    clk = FakeClock()
    mon = PipelineMonitor(clock=clk)
    clk.advance(2.0)
    mon.record_window("s0", rows=8, bytes=2048, seconds=0.01,
                      queue_rows=8, worker_rows={0: 5, 1: 3})
    mon.record_window("ingress", rows=8, bytes=2048, seconds=0.002,
                      dispatches=1)
    return mon


def test_prometheus_text_is_wellformed_with_stage_series():
    reg = MetricsRegistry()
    reg.counter("pipeline.host_syncs").inc(4)
    reg.counter("device.dispatches").inc(9)
    reg.histogram("pipeline.stage.s0.window_seconds").observe(0.01)
    reg.gauge("pipeline.stage.s0.queue_rows").set(8)
    text = prometheus_text(reg, _loaded_monitor())
    problems = check_prometheus.validate(
        text, require_labels=(("stage", "s0"), ("stage", "ingress")),
        min_samples=10)
    assert problems == [], "\n".join(problems)
    assert 'repro_stage_windows_per_second{stage="s0"}' in text
    assert 'repro_pipeline_stage_window_seconds{stage="s0",quantile="0.5"}' \
        in text
    assert "repro_pipeline_host_syncs 4" in text
    assert "repro_device_dispatches 9" in text


def test_prometheus_text_escapes_label_values():
    mon = PipelineMonitor(clock=FakeClock())
    mon.record_window('we"ird\\st\nage', rows=1, seconds=0.01)
    text = prometheus_text(MetricsRegistry(), mon)
    assert check_prometheus.validate(text) == []
    assert '\\"' in text and "\\\\" in text


def test_snapshot_json_is_json_serializable():
    doc = snapshot_json(_loaded_monitor(), MetricsRegistry())
    rt = json.loads(json.dumps(doc))
    assert rt["monitor"]["stages"]["s0"]["windows"] == 1
    assert rt["monitor"]["pipeline"]["windows_total"] == 2


def test_http_endpoints_serve_metrics_health_snapshot():
    mon = _loaded_monitor()
    Watchdog(mon, [SLORule("q", stage="s0", max_queue_rows=4)],
             audit=AuditLog())
    with serve_metrics(0, monitor=mon) as srv:
        assert srv.port != 0
        body = urllib.request.urlopen(srv.url + "/metrics").read().decode()
        assert check_prometheus.validate(body) == []
        assert 'stage="s0"' in body
        health = json.load(urllib.request.urlopen(srv.url + "/health"))
        assert health["status"] == "degraded"       # queue 8 > limit 4
        assert health["breached"] == ["q"]
        snap = json.load(urllib.request.urlopen(srv.url + "/snapshot"))
        assert snap["monitor"]["watchdog"]["breached"] == ["q"]
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(srv.url + "/nope")


# ------------------------------------------------- engine integration (e2e)


def _src(n=9):
    return [jnp.asarray(np.random.default_rng(i).standard_normal(
        (64,)).astype(np.float32)) for i in range(n)]


def _linear8(mode, wc=8):
    from repro.attest.directory import KeyDirectory
    from repro.configs.base import SecureStreamConfig
    from repro.core.pipeline import Pipeline, Stage
    stages = [Stage(f"s{i}", op="scale_f32", const=1.0 + 0.125 * i)
              for i in range(8)]
    return Pipeline(stages, SecureStreamConfig(mode=mode),
                    directory=KeyDirectory(seed=0), window_chunks=wc)


def test_dispatch_gate_8stage_encrypted_window_job():
    """THE per-hop dispatch-count regression gate (ROADMAP megakernel
    item): the 8-stage encrypted window job costs exactly 1 launch per
    stage window (the hop program: open -> op -> seal), 1 per ingress
    window (seal_many) and 1 per egress window (open_many).  A fused
    megakernel must DROP these numbers; a regression to per-chunk
    dispatching would multiply them by the window size."""
    reset_dispatch_count()
    p = _linear8("encrypted")
    src = _src(8)                       # exactly one 8-chunk window
    got = []
    p.run(iter(src), on_result=lambda r: got.append(np.asarray(r)))
    assert len(got) == 8
    rep = p.report()
    for i in range(8):
        assert rep[f"s{i}"]["windows"] == 1
        assert rep[f"s{i}"]["dispatches"] == 1
        assert rep[f"s{i}"]["dispatches_per_window"] == 1.0
    assert rep["dispatch"]["ingress"] == {"windows": 1, "dispatches": 1}
    assert rep["dispatch"]["egress"] == {"windows": 1, "dispatches": 1}
    assert rep["dispatch"]["total"] == 8 * 1 + 1 + 1
    assert dispatch_count() == rep["dispatch"]["total"]


def test_dispatch_gate_8stage_enclave_window_job():
    """Enclave hops pin at 1 launch per stage window: one hop program
    holds the mac-key derive + ciphertext MAC on the way in, the fused
    enclave_map_rows kernel, and the mac-key derive + re-MAC on the way
    out."""
    reset_dispatch_count()
    p = _linear8("enclave")
    src = _src(8)
    got = []
    p.run(iter(src), on_result=lambda r: got.append(np.asarray(r)))
    assert len(got) == 8
    rep = p.report()
    for i in range(8):
        assert rep[f"s{i}"]["dispatches_per_window"] == 1.0
    assert rep["dispatch"]["ingress"]["dispatches"] == 1
    assert rep["dispatch"]["egress"]["dispatches"] == 1
    assert dispatch_count() == 8 * 1 + 1 + 1


def test_monitored_8stage_rekey_revocation_bit_identical():
    """Monitoring must not change a single bit of the acceptance stream
    (8 stages, rekey_every_n=3, mid-stream revocation of s3/w1), and the
    monitor snapshot must carry every stage + the ingress/egress hops."""
    from test_obs import _run_8stage
    src = _src()
    _, got_off, _ = _run_8stage(src)                     # monitor off
    mon = PipelineMonitor()
    p, got, _ = _run_8stage(src, monitor=mon)
    assert len(got) == len(got_off) == len(src)
    for a, b in zip(got, got_off):
        assert np.array_equal(a, b)
    snap = mon.snapshot()
    assert set(snap["stages"]) == {f"s{i}" for i in range(8)} \
        | {"ingress", "egress"}
    s3 = snap["stages"]["s3"]
    assert s3["windows_total"] >= 1 and s3["p95_s"] is not None
    assert s3["dispatches_per_window"] > 0
    # the revoked worker's share shows up in the skew accounting
    assert set(s3["worker_rows"]) <= {0, 1}
    assert snap["pipeline"]["windows_total"] == sum(
        st["windows_total"] for st in snap["stages"].values())
    assert snap["pipeline"]["rekey_per_s"] > 0
    assert snap["pipeline"]["revocation_per_s"] > 0
    # and the whole thing exports cleanly
    assert check_prometheus.validate(
        prometheus_text(REGISTRY, mon),
        require_labels=(("stage", "s3"), ("stage", "egress"))) == []


def test_injected_mac_failure_burst_trips_watchdog_once(monkeypatch):
    """Tamper a burst of rows mid-stream: the stage that opens them sees
    the failure-rate spike, the watchdog trips EXACTLY once, and the
    ``slo_breach`` event lands in the pipeline's own audit log among the
    mac_failure events."""
    from repro.attest.directory import KeyDirectory
    from repro.configs.base import SecureStreamConfig
    from repro.core.pipeline import Pipeline, Stage

    TAMPER = {1, 3, 6}
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
                    for j in hit:
                        words = words.at[j, 0].add(np.uint32(1))
                    out = dataclasses.replace(out, words=words)
                return out, ok

            ex.run_static_window = tampered
        return pool

    monkeypatch.setattr(Pipeline, "_worker_pool", patched_pool)

    mon = PipelineMonitor()
    fired = []
    wd = Watchdog(mon, [SLORule("mac-ceiling",
                                max_mac_failure_rate=0.1)],
                  on_breach=[fired.append])
    stages = [Stage(f"s{i}", op="scale_f32", const=1.01) for i in range(4)]
    d = KeyDirectory(seed=0)
    p = Pipeline(stages, SecureStreamConfig(mode="encrypted"),
                 directory=d, window_chunks=8, monitor=mon)
    got = []
    p.run(iter(_src(9)), on_result=lambda r: got.append(np.asarray(r)))
    assert not pending and len(got) == 9 - len(TAMPER)
    assert [b.rule for b in fired] == ["mac-ceiling"]    # EXACTLY once
    assert fired[0].kind == "slo_breach"
    assert fired[0].stage == "s2"       # the stage that opens s1's output
    # the matching audit event, in the pipeline's own ordered stream
    breaches = d.audit.events("slo_breach")
    assert len(breaches) == 1
    assert breaches[0].detail["rule"] == "mac-ceiling"
    assert breaches[0].detail["metric"] == "mac_failure_rate"
    assert d.audit.counts()["mac_failure"] == len(TAMPER)
    assert wd.breached() == ["mac-ceiling"]
    assert mon.stage_stats("s2")["mac_failures"] == len(TAMPER)


def test_dsl_monitor_verb_and_run_override():
    from repro.dsl import stream
    src = _src(8)
    sb = (stream(src).map("scale_f32", const=1.25, name="m")
          .secure("encrypted").window(4).monitor())
    assert sb.health_monitor is not None and sb.health_monitor.enabled
    got = []
    sb.run(on_result=lambda r: got.append(np.asarray(r)))
    assert len(got) == len(src)
    snap = sb.health_monitor.snapshot()
    assert snap["stages"]["m"]["windows_total"] == 2
    rep = sb.report()["m"]
    assert rep["windows"] == 2 and rep["dispatches_per_window"] == 1.0
    # unmonitored builders stay unmonitored (zero-cost default)
    assert stream(src).map("identity").health_monitor is None
    # per-run override on a bare pipeline
    p = sb.pipeline
    mon2 = PipelineMonitor()
    p.run(iter(src), monitor=mon2)
    assert mon2.snapshot()["stages"]["m"]["windows_total"] == 2
    assert p.monitor is sb.health_monitor       # restored after the run


def test_chunked_oracle_engine_feeds_the_monitor():
    from repro.attest.directory import KeyDirectory
    from repro.configs.base import SecureStreamConfig
    from repro.core.pipeline import Pipeline, Stage
    mon = PipelineMonitor()
    p = Pipeline([Stage("s0", op="scale_f32", const=1.5)],
                 SecureStreamConfig(mode="encrypted"),
                 directory=KeyDirectory(seed=0), window_chunks=1,
                 monitor=mon)
    got = []
    p.run(iter(_src(3)), on_result=lambda r: got.append(np.asarray(r)))
    assert len(got) == 3
    st = mon.stage_stats("s0")
    assert st["windows_total"] == 3     # the oracle's window IS a chunk
    assert p.report()["s0"]["windows"] == 3


def test_dispatch_shims_next_to_host_sync_count():
    from repro.core import pipeline as P
    reset_dispatch_count()
    assert P.dispatch_count() == 0
    REGISTRY.counter("device.dispatches").inc(3)
    REGISTRY.counter("device.dispatches.aead.seal_many").inc(3)
    assert P.dispatch_count() == dispatch_count() == 3
    P.reset_dispatch_count()
    assert dispatch_count() == 0
    assert REGISTRY.counter("device.dispatches.aead.seal_many").value == 0
