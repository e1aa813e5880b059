"""The trace reduction on a small synthetic event list, the kernel cost
functions at one engine window's shapes, and the check of a sealed run's
kernel calls against its guarantees."""
import re

import numpy as np
import pytest

from streambench import devicetrace
from streambench.devicetrace import DeviceTrace
from streambench.harness import Run, kernel_calls
from streambench.layout import load_module

# HLO text of the three kernels as the TPU profiler names their calls at
# one DelayedFlights window (16 chunks of 1024 records, 2 workers)
CHACHA = ("%chacha20_xor_rows.1 = u32[16640,16]{1,0:T(8,128)S(1)} "
          "custom-call(u32[16640,8]{1,0:T(8,128)S(1)} %pad.0, "
          "u32[16640,3]{1,0:T(8,128)S(1)} %pad.2, "
          "u32[16640,1]{1,0:T(8,128)S(1)} %reshape.23, "
          "u32[16640,16]{1,0:T(8,128)S(1)} %pad.6), "
          'custom_call_target="tpu_custom_call", operand_layout_constraints='
          "{u32[16640,8]{1,0}, u32[16640,3]{1,0}, u32[16640,1]{1,0}, "
          "u32[16640,16]{1,0}}, frontend_attributes={kernel_metadata={}}")
MAC = ("%mac_partials_batch.1 = u32[16,1024]{1,0:T(8,128)S(1)} "
       "custom-call(u32[16,32768]{1,0:T(8,128)S(1)} %copy.73, "
       "u32[16,4096]{1,0:T(8,128)S(1)} %rev.0), "
       'custom_call_target="tpu_custom_call", operand_layout_constraints='
       "{u32[16,32768]{1,0}, u32[16,4096]{1,0}}")
ENCLAVE = ("%enclave_apply_rows.1 = u32[8192,16]{1,0:T(8,128)S(1)} "
           "custom-call(" + ", ".join(
               f"u32[8192,{c}]{{1,0:T(8,128)S(1)}} %copy.{i}"
               for i, c in enumerate((8, 8, 3, 1, 3, 1, 16))) +
           '), custom_call_target="tpu_custom_call"')
COPY = "%copy.58 = u32[16,16384,2]{0,2,1:T(2,128)S(1)} copy(u32[16,16384,2])"


def test_shapes_read_result_then_operands():
    assert devicetrace.shapes(MAC) == [("u32", (16, 1024)),
                                       ("u32", (16, 32768)),
                                       ("u32", (16, 4096))]
    assert devicetrace.op_kind(CHACHA) == "chacha20_xor_rows"
    assert devicetrace.op_kind(COPY) == "copy"


def test_kernel_bytes_at_one_window():
    chacha = load_module("kernels", "chacha20")
    cwmac = load_module("kernels", "cwmac")
    enclave = load_module("kernels", "enclave_map")
    # 16 chunks x (1024 payload blocks + 1 MAC-key block), padded to 256s
    assert chacha.hbm_bytes(CHACHA) == 16640 * (8 + 3 + 1 + 16 + 16) * 4
    # one worker's 8 chunks, dual MAC: 16 rows of 32768 16-bit limbs
    assert cwmac.hbm_bytes(MAC) == (16 * 32768 + 16 * 4096 + 16 * 1024) * 4
    # one worker's 8 chunks of 1024 records through the enclave hop
    assert enclave.hbm_bytes(ENCLAVE) == 8192 * (8 + 8 + 3 + 1 + 3 + 1
                                                 + 16 + 16) * 4
    for mod, text in ((chacha, CHACHA), (cwmac, MAC), (enclave, ENCLAVE)):
        assert mod.PATTERN.search(text)
        assert not mod.PATTERN.search(COPY)
    assert chacha.hbm_bytes(MAC) is None and cwmac.hbm_bytes(CHACHA) is None


def _trace():
    return DeviceTrace({"/device:TPU:0": [
        (0.0, 1.0, CHACHA), (0.5, 1.5, COPY), (3.0, 4.0, MAC),
        (4.0, 4.5, ENCLAVE), (6.0, 7.0, ENCLAVE), (9.5, 11.0, COPY)]})


def test_busy_union_idle_gaps_and_top_ops():
    tr = _trace()
    assert tr.busy_s(0.0, 10.0) == 1.5 + 1.5 + 1.0 + 0.5
    # idle: 1.5-3.0, 4.5-6.0, 7.0-9.5 (9.5-10 runs a copy)
    gaps = tr.idle_gaps(0.0, 10.0, [("reduce.fold", 1.6, 2.9),
                                    ("pipeline.run", 0.5, 9.0),
                                    ("sync.verdicts", 4.6, 5.0)])
    gaps = {n: round(t, 9) for n, t in gaps}
    assert gaps == {"pipeline.run": 0.2 + 1.1 + 2.0,
                    "reduce.fold": 1.3, "sync.verdicts": 0.4, "none": 0.5}
    top = dict(tr.top_ops(0.0, 10.0))
    assert top == {"copy": 2.5, "enclave_apply_rows": 1.5,
                   "chacha20_xor_rows": 1.0, "mac_partials_batch": 1.0}
    assert tr.events(0.0, 10.0, re.compile("^%mac")) == [(3.0, 4.0, MAC)]


def test_trace_readers_on_the_synthetic_trace():
    run = Run(config={}, window_chunks=16,
              t_process=0, t_open=0.0, t_end=10.0, due=np.zeros(1),
              take=np.zeros(1), fold=np.zeros(1), counters={},
              peaks={"hbm_bytes_per_s": 819e9}, spans=[],
              device=_trace(), interval=(0.0, 10.0))
    idle = load_module("metrics", "device_idle_share").read(run)
    assert np.isclose(idle, 100 * (1 - 4.5 / 10))
    share = load_module("metrics", "enclave_map_roofline").read(run)
    want = 2 * 8192 * 56 * 4 / 819e9 / 1.5 * 100
    assert np.isclose(share, want)
    run.interval = (4.6, 5.9)           # no kernel call in the interval
    assert load_module("metrics", "enclave_map_roofline").read(run) is None


def _window_run(events, mode="enclave", folded=32):
    """Two engine windows of 16 chunks of a two-hop job, folded inside the
    traced interval [0, 10]."""
    config = {"mode": mode, "job": {"stages": [{"sgx": True},
                                               {"sgx": True}]}}
    return Run(config=config, window_chunks=16, t_process=0, t_open=0.0,
               t_end=10.0, due=np.zeros(folded), take=np.zeros(folded),
               fold=np.linspace(1.0, 9.0, folded), counters={},
               peaks={}, spans=[],
               device=DeviceTrace({"/device:TPU:0": [
                   (0.1 * i, 0.1 * i + 0.05, t)
                   for i, t in enumerate(events)]}),
               interval=(0.0, 10.0))


# per window: ingress seal and sink open; ingress tags, two hops' checks
# and the sink's check; two enclave hops
SOUND = [CHACHA] * 2 * 2 + [MAC] * 4 * 2 + [ENCLAVE] * 2 * 2


def test_kernel_calls_of_a_sound_sealed_run_fall_short_of_nothing():
    checks = kernel_calls(_window_run(SOUND))
    assert checks == {f"{k}_calls_short": {"value": 0.0, "limit": 0.0}
                      for k in ("chacha20", "cwmac", "enclave_map")}
    assert kernel_calls(_window_run([], mode="plain")) == {}


@pytest.mark.parametrize("kernel,text,short", [
    ("enclave_map", ENCLAVE, 4.0),     # hops run outside the enclave
    ("cwmac", MAC, 8.0),               # no MAC computed or checked
    ("chacha20", CHACHA, 4.0)])        # the edges left in the clear
def test_a_kernel_left_out_of_a_sealed_run_falls_short(kernel, text, short):
    checks = kernel_calls(_window_run([e for e in SOUND if e != text]))
    assert checks[f"{kernel}_calls_short"]["value"] == short
    assert [k for k, c in checks.items() if c["value"] > c["limit"]] == \
        [f"{kernel}_calls_short"]


def test_kernel_calls_count_only_windows_folded_in_the_interval():
    run = _window_run([CHACHA] * 2 + [MAC] * 4 + [ENCLAVE] * 2)
    run.fold = np.r_[np.linspace(1.0, 9.0, 16), np.full(16, 11.0)]
    assert all(c["value"] == 0.0 for c in kernel_calls(run).values())
