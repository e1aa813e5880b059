"""The enclave configuration at a tiny size on the CPU: the harness's run
equals the plain reference, and the control and each fault the cell can
have make ``correct`` false."""
import numpy as np
import pytest

from streambench import harness
from streambench.control import skip_last_of_window
from streambench.tests import tiny

SATURATE = "flights-enclave.saturate"


def test_saturated_run_equals_the_reference(tmp_path):
    out = tiny.run(tiny.cell(SATURATE), tmp_path)
    assert out.correct, out.checks
    assert out.attempted > 0 and out.attempted % 16 == 0
    assert out.failed == 0
    assert all(c["value"] == 0 for c in out.checks.values())
    assert list(out.line())[-1] == "checks"
    assert set(out.metrics) == {m["name"] for m in
                                tiny.cell(SATURATE).end_to_end}
    assert out.metrics["records_per_s"]["value"] > 0


def test_the_control_is_not_correct(tmp_path):
    c = tiny.cell(SATURATE)
    out = tiny.run(c, tmp_path, skip_fold=skip_last_of_window(
        harness.window_chunks(c.config)))
    assert not out.correct
    assert out.checks["count_gap"]["value"] >= 1


@pytest.mark.parametrize("fault", sorted(tiny.FAULTS))
def test_a_fault_under_the_timed_path_is_not_correct(fault, tmp_path,
                                                     monkeypatch):
    tiny.FAULTS[fault](monkeypatch)
    out = tiny.run(tiny.cell(SATURATE), tmp_path)
    assert not out.correct, (fault, out.checks)


def test_open_loop_run_stamps_every_chunk(tmp_path):
    c = tiny.cell("flights-enclave.rate80", chunks_per_s=60.0)
    out = tiny.run(c, tmp_path, seconds=0.8)
    assert out.correct, out.checks
    run = out.run
    assert run.offered == 48 and run.folded == 48
    assert np.all(run.take >= run.due) and np.all(run.fold > run.take)
    lat = out.metrics["latency_p95_ms"]["value"]
    assert lat >= out.metrics["latency_p50_ms"]["value"] > 0


def test_traced_run_reads_spans_and_counters(tmp_path):
    out = tiny.run(tiny.cell(SATURATE), tmp_path, trace=True)
    # the CPU's trace holds no device plane, so every sealed kernel's calls
    # fall short there; the data's checks hold
    kernels = {k for k in out.checks if k.endswith("_calls_short")}
    assert kernels == {"chacha20_calls_short", "cwmac_calls_short",
                       "enclave_map_calls_short"}
    assert all(out.checks[k]["value"] > 0 for k in kernels)
    assert all(c["value"] == 0 for k, c in out.checks.items()
               if k not in kernels)
    assert out.metrics["host_syncs_per_window"]["value"] == 3.0
    assert 0 < out.metrics["sink_share"]["value"] < 100
    assert 0 < out.metrics["ingress_share"]["value"] < 100
