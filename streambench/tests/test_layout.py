"""BENCHMARK.json and the files it names: every cell resolves its
configuration, mix and metric readers by file name, and a new cell, mix and
metric need new files and entries only."""
import hashlib
import json
import os
import re
import shutil

import numpy as np
import pytest

from streambench import arrivals, layout, records, reference
from streambench.layout import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return layout.load_benchmark()


def test_every_cell_resolves_its_files_by_name(bench):
    for w in bench["workloads"]:
        cell = layout.resolve(bench, w["name"])
        assert cell.config["name"] == w["config"]
        assert callable(layout.load_module(
            "arrivals", cell.traffic["arrival"]).schedule)
        assert callable(layout.load_module(
            "generators", cell.config["generator"]).records)
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in [m["name"] for m in cell.end_to_end]
        for m in cell.end_to_end + cell.per_layer:
            assert callable(layout.load_module("metrics", m["name"]).read)
        for m in cell.per_layer:
            assert m["moves"] in [e["name"] for e in cell.end_to_end]
        for st in cell.config["job"]["stages"]:
            assert callable(layout.load_module("operators", st["op"]).apply)
        assert callable(layout.load_module(
            "aggregates", cell.config["job"]["reduce"]).per_chunk)
    assert layout.names("kernels") == ["chacha20", "cwmac", "enclave_map"]
    for name in layout.names("kernels"):
        mod = layout.load_module("kernels", name)
        assert mod.PATTERN and callable(mod.hbm_bytes)
        assert callable(mod.calls_per_window)


def test_benchmark_keeps_to_the_names_and_shapes_it_promises(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["streambench"]
    assert os.path.isfile(os.path.join(ROOT, bench["command"][1]))
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [x["name"] for x in bench["configs"] + bench["workloads"]
             + metrics]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert all(UNIT.match(m["unit"]) for m in metrics)
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
    for c in bench["configs"]:
        assert c["file"].startswith("streambench/configs/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert set(c["reduced"]) == set(cfg["reduced"])
    for w in bench["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200


def test_peak_table_knows_the_v5e_and_refuses_the_rest():
    assert layout.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        layout.peaks("cpu")


def _digests(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


NEW_FILES = {
    "streambench/arrivals/onoff.py":
        "import numpy as np\n\n"
        "def schedule(mix, seconds, window_chunks, seed):\n"
        "    n = int(mix['chunks']) * window_chunks\n"
        "    return np.arange(n) * 0.5 / n\n",
    "streambench/generators/ones.py":
        "import numpy as np\n\n"
        "def records(n, config, seed):\n"
        "    return np.ones((n, int(config['record_words'])), np.uint32)\n",
    "streambench/operators/double.py":
        "def apply(recs, const):\n    return recs * 2\n",
    "streambench/aggregates/word_sum.py":
        "def per_chunk(recs, config):\n"
        "    return {'sum': recs.sum(axis=(1, 2)).astype('int64')}\n",
    "streambench/metrics/latency_p99_ms.py":
        "def read(run):\n    return 1.0\n",
    "streambench/traffic/burst.json":
        json.dumps({"arrival": "onoff", "chunks": 3}),
    "streambench/configs/ones.json":
        json.dumps({"name": "ones", "mode": "plain", "record_words": 4,
                    "generator": "ones", "chunk_records": 2,
                    "pool_records": 8, "window_factor": 1,
                    "job": {"stages": [{"op": "double", "const": 0,
                                        "workers": 2}],
                            "reduce": "word_sum"},
                    "reduced": []}),
}


def test_a_new_cell_needs_new_files_and_entries_only(tmp_path, bench):
    """A new job (configuration, generator, operator, aggregate), a new
    kind of arrivals, a mix and a metric are new files and entries, and
    the files that are there stay as they are."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "streambench"),
                    os.path.join(root, "streambench"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    before = _digests(os.path.join(root, "streambench"))
    for rel, text in NEW_FILES.items():
        with open(os.path.join(root, rel), "w") as f:
            f.write(text)
    new = json.loads(json.dumps(bench))
    new["configs"].append({"name": "ones", "source": "x",
                           "file": "streambench/configs/ones.json",
                           "reduced": [], "why": "x"})
    new["workloads"].append({"name": "ones.burst", "config": "ones",
                             "traffic": "burst", "chips": 4, "why": "x"})
    new["end_to_end"].append({"name": "latency_p99_ms", "unit": "ms",
                              "better": "lower", "bound": 0.3,
                              "source": "host_clock",
                              "workloads": ["ones.burst"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(new, f)
    after = _digests(os.path.join(root, "streambench"))
    assert {k: v for k, v in after.items() if k in before} == before

    cell = layout.resolve(layout.load_benchmark(root), "ones.burst", root)
    assert cell.chips == 4
    assert [m["name"] for m in cell.end_to_end] == ["setup_s",
                                                    "latency_p99_ms"]
    assert cell.per_layer == []
    assert layout.load_module("metrics", "latency_p99_ms", root).read(
        None) == 1.0
    due = arrivals.schedule(cell.traffic, 1.0, 2, seed=3, root=root)
    assert len(due) == 6
    pool = records.record_pool(cell.config, 3, root)
    assert pool.shape == (4, 2, 4)
    want = reference.expected(
        reference.per_chunk_result(cell.config, pool, root), [0, 1, 1])
    assert np.array_equal(want["sum"], 3 * 2 * 2 * 4)
