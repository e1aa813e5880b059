"""The Yahoo Streaming Benchmark's campaign query through ``repro.dsl`` ->
``Pipeline.run``: keep the views, join each ``ad_id`` to its campaign in
a sealed table, count per campaign per 10 s event-time window.  Every mode
equals a numpy reference of the query exactly (enclave mode with its
kernels interpreted)."""
import dataclasses

import numpy as np
import pytest

from repro.attest.measure import measure_stage
from repro.core.pipeline import Pipeline, Stage, stage_table
from repro.data import ysb
from repro.dsl import DSLValidationError, stream
from repro.kernels.enclave_map.ops import TABLE_OPS
from repro.obs.metrics import REGISTRY
from repro.obs.trace import Tracer

SEED = 7                        # the table seed
ORIGIN = 1_600_000_000_000      # ms, on a window boundary
WINDOW_MS = 10_000
WINDOWS = 5
CHUNK = 16                      # events a chunk
WORKERS, FACTOR = 2, 2          # 4 chunks an engine window, 2 a share
MODES = ("plain", "encrypted", "enclave")


def _events(times, seed=0):
    """(len(times), 16) events at ``times`` (ms) and each one's ad index:
    ads uniform over the table, event types uniform over the three."""
    rng = np.random.default_rng(seed)
    n = len(times)
    ad = rng.integers(0, ysb.CAMPAIGNS * ysb.ADS_PER_CAMPAIGN, n)
    ev = rng.integers(0, 2 ** 32, (n, 16), dtype=np.uint32)
    ev[:, 8:12] = ysb.campaign_ads(SEED)[ad]
    ev[:, 12] = rng.integers(0, 5, n)
    ev[:, 13] = rng.integers(1, 4, n)
    t = np.asarray(times, np.int64)
    ev[:, 14], ev[:, 15] = t & 0xFFFFFFFF, t >> 32
    return ev, ad


def _reference(ev, ad, known=None):
    """Views per (window slot, campaign): YSB's query in numpy.  Views of
    an ad outside the table (``known`` false) count nowhere."""
    t = ev[:, 14].astype(np.int64) | (ev[:, 15].astype(np.int64) << 32)
    view = ev[:, 13] == ysb.VIEW
    if known is not None:
        view &= known
    out = np.zeros((WINDOWS, ysb.CAMPAIGNS), np.int64)
    np.add.at(out, ((t[view] // WINDOW_MS) % WINDOWS,
                    ad[view] // ysb.ADS_PER_CAMPAIGN), 1)
    return out


def _job(table_seed=SEED):
    return (stream()
            .filter("ysb_view_filter", name="views", workers=WORKERS)
            .map("ysb_campaign_join", const=table_seed, name="join",
                 workers=WORKERS)
            .aggregate("ysb_window_count", const=WINDOW_MS, name="count",
                       workers=WORKERS)
            .reduce("ysb_campaign_window_counts", name="sink")
            .window(FACTOR))


def _chunks(ev):
    return list(ev.reshape(-1, CHUNK, 16))


def _counter(name):
    return REGISTRY.counter(name).value


# 8 chunks: a 10 s boundary at event 10 (inside the first chunk, engine
# window and worker share), then event time goes back to the start, as
# when the stream wraps its pool
TIMES = ORIGIN + 9_990 + np.arange(128) % 64


@pytest.mark.parametrize("mode", MODES)
def test_job_equals_the_numpy_reference(mode):
    ev, ad = _events(TIMES)
    want = _reference(ev, ad)
    assert want[0].sum() and want[1].sum()          # both windows hit
    rows0 = _counter("aggregate.rows_in")
    keys0 = _counter("aggregate.keys_out")
    miss0 = _counter("join.misses")
    out = _job().run(_chunks(ev), mode=mode)
    np.testing.assert_array_equal(out["count"], want)
    assert _counter("aggregate.rows_in") - rows0 == len(ev)
    # one partial row per (window, campaign) of each worker share at most
    keys = _counter("aggregate.keys_out") - keys0
    assert 0 < keys <= (want > 0).sum() * len(ev) // (CHUNK * FACTOR)
    assert _counter("join.misses") == miss0


def test_windows_far_apart_and_past_2_to_the_32():
    """Times whose high words differ, spread over every slot, in one
    share: each view lands in its own window."""
    times = ORIGIN + np.arange(64) * 7_777 + (np.arange(64) % 2) * 2 ** 40
    ev, ad = _events(times, seed=3)
    assert len(np.unique(ev[:, 15])) > 1
    for mode in ("plain", "enclave"):
        out = _job().run(_chunks(ev), mode=mode)
        np.testing.assert_array_equal(out["count"], _reference(ev, ad))


@pytest.mark.parametrize("mode", ("plain", "enclave"))
def test_an_unknown_ad_is_a_miss_and_in_no_campaign(mode):
    ev, ad = _events(TIMES, seed=1)
    unknown = np.zeros(len(ev), bool)
    unknown[[3, 17, 40, 41, 100]] = True
    ev[unknown, 9] ^= np.uint32(0x40)
    views = int((unknown & (ev[:, 13] == ysb.VIEW)).sum())
    assert views
    miss0 = _counter("join.misses")
    out = _job().run(_chunks(ev), mode=mode)
    np.testing.assert_array_equal(out["count"],
                                  _reference(ev, ad, known=~unknown))
    assert _counter("join.misses") - miss0 == views


@pytest.mark.parametrize("mode", ("encrypted", "enclave"))
def test_a_tampered_chunk_is_dropped_and_never_counted(mode, monkeypatch):
    """A chunk altered on the join's output edge fails the count hop's
    MAC check: it is dropped there, and none of its views is counted."""
    target = 5                      # ingress counter of the chunk
    orig_pool = Pipeline._worker_pool

    def patched_pool(self, i, st):
        pool = orig_pool(self, i, st)
        if st.name == "join":
            for ex in pool:
                def tampered(op, const, win, _orig=ex.run_static_window,
                             **kw):
                    out, ok = _orig(op, const, win, **kw)
                    hit = [j for j, c in enumerate(out.counters)
                           if c == target]
                    words = out.words
                    for j in hit:
                        words = words.at[j, 13].add(np.uint32(1))
                    return dataclasses.replace(out, words=words), ok
                ex.run_static_window = tampered
        return pool

    monkeypatch.setattr(Pipeline, "_worker_pool", patched_pool)
    ev, ad = _events(TIMES, seed=2)
    kept = np.ones(len(ev), bool)
    kept[target * CHUNK:(target + 1) * CHUNK] = False
    b = _job()
    out = b.run(_chunks(ev), mode=mode)
    np.testing.assert_array_equal(out["count"],
                                  _reference(ev, ad, known=kept))
    assert b.report()["count"]["mac_failures"] == 1


def test_a_swapped_table_changes_the_measurement(monkeypatch):
    st = Stage("join", op="ysb_campaign_join", const=SEED)
    np.testing.assert_array_equal(stage_table(st), ysb.campaign_table(SEED))

    def measured():
        return measure_stage(op=st.op, const=st.const, sgx=st.sgx,
                             table=stage_table(st))
    own, p = measured(), Pipeline([st], seed=0)
    assert own == measured()
    # the same stage and const, with a rule that yields another table
    join = TABLE_OPS["ysb_campaign_join"][0]
    monkeypatch.setitem(TABLE_OPS, "ysb_campaign_join",
                        (join, lambda const: ysb.campaign_table(SEED + 1)))
    assert measured() != own
    q = Pipeline([st], seed=0)
    assert p.directory._workers["stage/join"] \
        != q.directory._workers["stage/join"]


def test_a_spare_of_the_join_stage_is_measured_with_its_table():
    """A spare enrolled live is admitted under its stage's measurement,
    the table's digest included, like the workers built with it."""
    p = Pipeline([Stage("join", op="ysb_campaign_join", const=SEED)],
                 seed=0)
    w = p.enroll_spare("join")
    workers = p.directory._workers
    assert workers[Pipeline.worker_id("join", w)] == workers["stage/join"]


def test_the_sealed_table_is_not_the_table():
    p = _job().build("enclave")
    sealed = p.tables["join"]
    assert not np.array_equal(np.asarray(sealed.words),
                              ysb.campaign_table(SEED))
    from repro.core.enclave import open_table
    np.testing.assert_array_equal(np.asarray(open_table(sealed)),
                                  ysb.campaign_table(SEED))


def test_join_and_count_hops_run_under_their_own_spans():
    ev, _ = _events(TIMES)
    tr = Tracer()
    b = _job()
    b.build("enclave").run(iter(_chunks(ev)), tracer=tr)
    by_track = {}
    for s in tr.spans:
        by_track.setdefault(s.name, set()).add(s.track.split("/")[0])
    assert by_track["enclave.join"] == {"join"}
    assert by_track["enclave.count"] == {"count"}
    assert by_track["enclave.op"] == {"views"}
    assert all("window" in s.args for s in tr.spans
               if s.name in ("enclave.join", "enclave.count"))


def test_as_observable_equals_the_reference():
    ev, ad = _events(TIMES, seed=4)
    assert "aggregate(ysb_window_count)[w=2,sgx]" in _job().describe()
    got = _job().as_observable(_chunks(ev)).subscribe()
    np.testing.assert_array_equal(got["count"], _reference(ev, ad))


@pytest.mark.parametrize("build", [
    lambda: stream().map("ysb_window_count", const=WINDOW_MS),
    lambda: stream().aggregate("ysb_view_filter"),
    lambda: stream().aggregate("ysb_window_count", const=2 ** 16),
    lambda: stream().aggregate("ysb_window_count", const=0.5),
    lambda: stream().map("ysb_campaign_join", const=-1),
    lambda: stream().aggregate("no_such_op"),
], ids=["count-under-map", "filter-under-aggregate", "window-too-long",
        "window-not-whole", "negative-seed", "unknown-op"])
def test_the_dsl_refuses_a_misplaced_or_malformed_op(build):
    with pytest.raises(DSLValidationError):
        build().reduce("ysb_campaign_window_counts").build("plain")


def test_the_per_chunk_engine_refuses_join_and_count():
    ev, _ = _events(TIMES)
    with pytest.raises(ValueError, match="window engine"):
        _job().run(_chunks(ev), mode="plain", window_chunks=1)
