"""The plain configuration at a tiny size on the CPU: the harness's run
equals the plain reference, and the control and each fault the cell can
have make ``correct`` false."""
import numpy as np
import pytest

from streambench import harness
from streambench.control import skip_last_of_window
from streambench.tests import tiny

SATURATE = "flights-plain.saturate"


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


def test_the_command_refuses_the_cpu(capsys):
    from streambench import run
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", SATURATE, "--seed", "1", "--seconds", "1"])
    assert e.value.code != 0
    assert '"correct"' not in capsys.readouterr().out
