"""The YSB configuration at a tiny size on the CPU: the harness's run
equals the plain reference, the control is not correct, and a traced run
reads the join and count hops' spans."""
import numpy as np

from streambench import harness, layout, reference
from streambench.control import skip_last_of_window
from streambench.control_first import skip_first_of_window
from streambench.records import record_pool
from streambench.tests import tiny

SATURATE = "ysb-enclave.saturate"


def test_saturated_run_equals_the_reference(tmp_path):
    out = tiny.run(tiny.cell(SATURATE), tmp_path)
    assert out.correct, out.checks
    assert out.attempted > 0 and out.attempted % 16 == 0
    assert out.failed == 0
    assert set(out.checks) == {"count_gap", "fold_gap"}
    assert all(c["value"] == 0 for c in out.checks.values())
    assert set(out.metrics) == {"records_per_s", "setup_s"}


def test_the_control_is_not_correct(tmp_path):
    c = tiny.cell(SATURATE)
    out = tiny.run(c, tmp_path, skip_fold=skip_last_of_window(
        harness.window_chunks(c.config)))
    assert not out.correct
    assert out.checks["count_gap"]["value"] >= 1


def test_the_first_chunk_control_is_not_correct(tmp_path):
    """Skipping each window's first chunk drops the partial rows that the
    count writes at each key's first row."""
    c = tiny.cell(SATURATE)
    out = tiny.run(c, tmp_path, skip_fold=skip_first_of_window(
        harness.window_chunks(c.config)))
    assert not out.correct
    assert out.checks["count_gap"]["value"] >= 1
    assert out.checks["fold_gap"]["value"] == 0


def test_reference_counts_every_view_once():
    """The reference's counts add up to the pool's views, each in the
    window of its own time; the generator makes no unknown ad."""
    c = tiny.cell(SATURATE)
    pool = record_pool(c.config, seed=5)
    count = reference.per_chunk_result(c.config, pool)["count"]
    views = pool[..., 13] == 1
    assert count.sum() == views.sum() > 0
    t = pool[..., 14].astype(np.int64) | (pool[..., 15].astype(np.int64)
                                          << 32)
    slots = np.unique((t[views] // 10000) % 5)
    assert set(np.flatnonzero(count.sum(axis=(0, 2)))) == set(slots)


def test_full_pool_spans_the_window_slots():
    """At the configuration's own size, event time spans 41.9 s from a
    window boundary: the five windows fill the five slots once."""
    c = layout.resolve(layout.load_benchmark(), SATURATE).config
    gen = layout.load_module("generators", c["generator"])
    recs = gen.records(int(c["pool_records"]), c, seed=2 ** 31 + 9)
    t = recs[:, 14].astype(np.int64) | (recs[:, 15].astype(np.int64) << 32)
    assert t[0] % c["window_ms"] == 0
    windows = np.unique(t // c["window_ms"])
    assert len(windows) == c["job"]["reduce_args"]["windows"]
    assert len(np.unique(windows % len(windows))) == len(windows)


def test_traced_run_reads_the_join_and_count_spans(tmp_path):
    out = tiny.run(tiny.cell(SATURATE), tmp_path, trace=True)
    # the CPU's trace holds no device plane, so every sealed kernel's calls
    # fall short there; the data's checks hold
    kernels = {k for k in out.checks if k.endswith("_calls_short")}
    assert kernels == {"chacha20_calls_short", "cwmac_calls_short",
                       "enclave_map_calls_short"}
    assert all(c["value"] == 0 for k, c in out.checks.items()
               if k not in kernels)
    assert out.metrics["host_syncs_per_window"]["value"] == 4.0
    assert 0 < out.metrics["count_hop_share"]["value"] < 100
    assert "campaign_join_roofline" not in out.metrics


JOIN = ("%campaign_join_rows.1 = u32[8192,16]{1,0:T(8,128)S(1)} custom-call("
        + ", ".join(f"u32[8192,{c}]{{1,0:T(8,128)S(1)}} %copy.{i}"
                    for i, c in enumerate((8, 8, 3, 1, 3, 1, 16)))
        + ", u32[64,128]{1,0:T(8,128)} %p.7, u32[1,16]{1,0} %p.8), "
        "custom_call_target=\"tpu_custom_call\"")


def test_join_roofline_counts_rows_and_table():
    from streambench.devicetrace import DeviceTrace
    from streambench.harness import Run
    metric = layout.load_module("metrics", "campaign_join_roofline")
    need = 8192 * (8 + 8 + 3 + 1 + 3 + 1 + 16 + 16) * 4 + (64 * 128 + 16) * 4
    assert metric.hbm_bytes(JOIN) == need
    assert metric.hbm_bytes(JOIN.replace("u32[1,16]", "u32[2,16]")) is None
    run = Run(config={}, window_chunks=16, t_process=0, t_open=0.0,
              t_end=1.0, due=np.zeros(0), take=np.zeros(0),
              fold=np.zeros(0), counters={},
              peaks={"hbm_bytes_per_s": 819e9}, spans=[],
              device=DeviceTrace({"/device:TPU:0": [
                  (0.1, 0.1 + 1e-3, JOIN), (0.2, 0.3, "%copy.1 = ")]}),
              interval=(0.0, 1.0))
    assert abs(metric.read(run) - 100 * need / 819e9 / 1e-3) < 1e-9
    run.interval = (0.5, 1.0)
    assert metric.read(run) is None
