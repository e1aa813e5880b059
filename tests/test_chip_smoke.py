"""chip_smoke.py's phases on the CPU at a tiny size (Pallas interpreted):
every mode's per-carrier result equals the numpy reference, the sealed
shuffle agrees with the plain exchange and the host bucketing, and
``main()`` refuses to run anywhere but on a TPU."""
import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import chip_smoke  # noqa: E402

from repro.data.synthetic import flight_records  # noqa: E402


@pytest.mark.parametrize("mode", chip_smoke.MODES)
def test_smoke_mode_matches_numpy_reference(mode):
    records = flight_records(8192, seed=3)
    r = chip_smoke.run_mode(mode, records, chunk=256, workers=2)
    assert r["window"] == 4096 and r["records"] == 8192
    assert r["ok"]
    ref = chip_smoke.reference(records)
    assert np.array_equal(np.asarray(r["result"]["count"]), ref["count"])
    assert np.array_equal(np.asarray(r["result"]["sum"]), ref["sum"])


def test_reference_counts_only_delayed_flights():
    rec = np.zeros((4, 16), np.uint32)
    rec[:, 0] = [1, 1, 2, 3]
    rec[:, 1] = [16, 15, 40, 0]
    ref = chip_smoke.reference(rec, num_carriers=4)
    assert ref["count"].tolist() == [0, 1, 1, 0]
    assert ref["sum"].tolist() == [0, 16, 40, 0]


def test_smoke_sealed_shuffle_matches_references():
    r = chip_smoke.sealed_shuffle(jax.devices()[:1], n_records=512, seed=0)
    assert r["macs_ok"] and r["equals_plain"] and r["equals_host"]


def test_smoke_main_refuses_the_cpu(capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code != 0
    assert '"ok"' not in capsys.readouterr().out
