"""The traffic schedule and the metric arithmetic, on hand-made stamps."""
import numpy as np
import pytest

from streambench import arrivals, stats
from streambench.harness import Run
from streambench.layout import load_module


def test_poisson_schedule_is_fixed_by_the_seed_and_ends_on_a_window():
    mix = {"arrival": "poisson", "chunks_per_s": 80.0}
    a = arrivals.schedule(mix, 10.0, 16, seed=2 ** 31 + 12345)
    b = arrivals.schedule(mix, 10.0, 16, seed=2 ** 31 + 12345)
    c = arrivals.schedule(mix, 10.0, 16, seed=7)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)
    assert len(a) == 800 and len(a) % 16 == 0 and len(c) == len(a)
    assert a[0] == 0.0 and np.all(np.diff(a) > 0)
    # every seed draws the same gaps, in its own order
    assert np.allclose(np.sort(np.diff(np.r_[0, a])[1:]),
                       np.sort(np.diff(np.r_[0, c])[1:])) or \
        np.isclose(a[-1], c[-1], rtol=0.05)
    assert 9.0 < a[-1] < 10.5


def test_schedule_rounds_to_whole_windows():
    mix = {"arrival": "poisson", "chunks_per_s": 5.0}
    assert len(arrivals.schedule(mix, 10.0, 16, seed=1)) == 48
    assert len(arrivals.schedule(mix, 0.1, 16, seed=1)) == 16


def test_backlog_has_no_schedule_and_bad_mixes_are_refused():
    assert arrivals.schedule({"arrival": "backlog"}, 10, 16, seed=1) is None
    with pytest.raises(ValueError):
        arrivals.schedule({"arrival": "bursty"}, 10, 16, seed=1)
    with pytest.raises(ValueError):
        arrivals.schedule({"arrival": "poisson"}, 10, 16, seed=1)


def test_poisson_gaps_keep_the_mean_rate():
    mix = {"arrival": "poisson", "chunks_per_s": 100.0}
    t = arrivals.schedule(mix, 20.0, 16, seed=5)
    assert len(t) == 2000
    assert t[0] == 0.0
    assert 19.0 < t[-1] < 20.5


def test_percentile_union_gaps_and_share():
    assert stats.percentile([], 95) is None
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3.0
    assert stats.percentile(list(range(101)), 95) == 95.0
    spans = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0)]
    assert stats.union(spans) == [(0.0, 2.0), (3.0, 4.0)]
    assert stats.covered(spans) == 3.0
    assert stats.gaps(spans, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0),
                                           (4.0, 5.0)]
    assert stats.share(spans, 0.0, 4.0) == 75.0
    assert stats.share(spans, 1.0, 1.0) is None


def _run(**kw):
    due = np.array([0.0, 0.1, 0.2, 0.3])
    base = dict(config={"chunk_records": 1024}, window_chunks=2,
                t_process=-5.0, t_open=0.0, t_end=2.0,
                due=due, take=due + np.array([0.0, 0.0, 0.05, 0.1]),
                fold=due + np.array([0.4, 0.5, 0.6, 0.7]),
                counters={"pipeline.host_syncs": 6.0,
                          "device.dispatches": 44.0},
                peaks={"hbm_bytes_per_s": 1e9})
    base.update(kw)
    return Run(**base)


def read(name, run):
    return load_module("metrics", name).read(run)


def test_end_to_end_readers_on_hand_made_stamps():
    run = _run()
    assert read("records_per_s", run) == 4 * 1024 / 2.0
    assert read("setup_s", run) == 5.0
    assert np.isclose(read("latency_p50_ms", run), 550.0)
    assert np.isclose(read("latency_p95_ms", run), 685.0)


def test_stamp_readers_on_hand_made_stamps():
    run = _run()
    assert np.isclose(read("queue_wait_p95_ms", run), 92.5)
    assert np.isclose(read("in_engine_p50_ms", run), 525.0)
    assert read("host_syncs_per_window", run) == 3.0
    assert read("dispatches_per_window", run) == 22.0


def test_span_shares_on_hand_made_spans():
    run = _run(spans=[("ingress.seal", 0.0, 0.5), ("egress.open", 0.5, 0.6),
                      ("reduce.fold", 0.6, 1.0), ("reduce.fold", 1.5, 2.5),
                      ("stage.dispatch", 1.0, 1.5)],
               interval=(0.0, 2.0))
    assert np.isclose(read("ingress_share", run), 25.0)
    assert np.isclose(read("sink_share", run), 50.0)
    untraced = _run()
    assert read("ingress_share", untraced) is None
    assert read("device_idle_share", untraced) is None
    assert read("chacha20_roofline", untraced) is None
