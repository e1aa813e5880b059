"""The reader of the program's ``ingress.fill`` spans, on hand-made spans
and on a traced open-loop run of the enclave cell at a tiny size."""
import numpy as np

from streambench.harness import Run
from streambench.layout import load_module
from streambench.tests import tiny


def read(run):
    return load_module("metrics", "window_fill_p50_ms").read(run)


def _run(spans):
    due = np.array([0.0, 0.1, 0.2, 0.3])
    return Run(config={"chunk_records": 1024}, window_chunks=2,
               t_process=-5.0, t_open=0.0, t_end=2.0, due=due, take=due,
               fold=due + 0.5, counters={}, peaks={}, spans=spans,
               interval=None if spans is None else (0.0, 2.0))


def test_fill_reader_on_hand_made_spans():
    spans = [("pipeline.run", 0.0, 2.0),
             ("ingress.fill", 0.00, 0.04), ("ingress.seal", 0.04, 0.05),
             ("ingress.fill", 0.05, 0.11), ("ingress.seal", 0.11, 0.12),
             ("ingress.fill", 0.12, 0.20), ("source.wait", 0.12, 0.19)]
    assert np.isclose(read(_run(spans)), 60.0)      # 40, 60 and 80 ms


def test_fill_reader_finds_nothing_without_fill_spans():
    assert read(_run(None)) is None                 # untraced
    assert read(_run([("ingress.seal", 0.0, 0.1),
                      ("reduce.fold", 0.2, 0.3)])) is None


def test_traced_open_loop_run_reads_the_window_fill(tmp_path):
    c = tiny.cell("flights-enclave.rate80", chunks_per_s=60.0)
    out = tiny.run(c, tmp_path, seconds=0.8, trace=True)
    assert all(v["value"] == 0 for k, v in out.checks.items()
               if not k.endswith("_calls_short"))
    assert set(out.metrics) == {m["name"] for m in c.per_layer}
    # 48 chunks at 60/s: a 16-chunk window arrives over about 0.27 s
    assert 100 < out.metrics["window_fill_p50_ms"]["value"] < 1000
    names = [s[0] for s in out.run.spans]
    assert names.count("ingress.fill") == out.run.offered // 16
    # the reducer's calls, split out of the fold's own time
    assert names.count("reduce.fn") == out.run.folded
