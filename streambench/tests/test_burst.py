"""The ``bursts`` arrivals of ``flights-enclave.burst``: the mean rate's
work, offered only in on phases, the same gaps for every seed; and a
tiny run of the cell equals the reference."""
import numpy as np

from streambench import arrivals, layout
from streambench.tests import tiny

BURST = "flights-enclave.burst"


def _mix():
    return layout.resolve(layout.load_benchmark(), BURST).traffic


def test_mean_rate_work_in_on_phases_only():
    mix = _mix()
    t = arrivals.schedule(mix, 20.0, 16, seed=2 ** 31 + 3)
    assert len(t) == mix["chunks_per_s"] * 20 and len(t) % 16 == 0
    assert t[0] == 0 and np.all(np.diff(t) >= 0)
    period = mix["on_s"] + mix["off_s"]
    assert np.all(t % period < mix["on_s"])
    # each on phase carries a period's work at the mean rate
    per_phase = np.bincount((t // period).astype(int))
    assert np.all(per_phase == mix["chunks_per_s"] * period)


def test_seeds_reorder_the_same_gaps():
    """Inside each on phase, two seeds' arrivals are the same exponential
    quantiles in another order (each burst leaves out the gap it draws
    last, after its final arrival)."""
    mix = _mix()
    period = mix["on_s"] + mix["off_s"]
    a, b = (arrivals.schedule(mix, 10.0, 16, seed=s)
            for s in (2 ** 31 + 1, 2 ** 31 + 2))
    assert not np.allclose(a, b)
    for p in range(int(10.0 / period)):
        ta, tb = (t[(t // period) == p] for t in (a, b))
        assert ta[0] == tb[0] == p * period
        ga, gb = np.sort(np.diff(ta)), np.sort(np.diff(tb))
        near = np.abs(gb[np.clip(np.searchsorted(gb, ga), 0, len(gb) - 1)]
                      - ga) < 1e-9
        near |= np.abs(gb[np.clip(np.searchsorted(gb, ga) - 1, 0,
                                  len(gb) - 1)] - ga) < 1e-9
        assert near.sum() >= len(ga) - 1


def test_burst_run_equals_the_reference(tmp_path):
    c = tiny.cell(BURST, chunks_per_s=40.0, on_s=0.2, off_s=0.2)
    out = tiny.run(c, tmp_path, seconds=0.8)
    assert out.correct, out.checks
    run = out.run
    assert run.offered == 32 and run.folded == 32
    assert np.all(run.take >= run.due) and np.all(run.fold > run.take)
    assert out.metrics["latency_p95_ms"]["value"] \
        >= out.metrics["latency_p50_ms"]["value"] > 0
