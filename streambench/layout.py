"""Where the benchmark's parts live, found by the names in BENCHMARK.json.

* ``BENCHMARK.json`` at the root of the checkout: cells and metrics;
* a configuration: the ``file`` its entry names (``streambench/configs/``);
* a traffic mix: ``streambench/traffic/<traffic>.json``, and the kind of
  arrivals it names: ``streambench/arrivals/<arrival>.py``;
* the job's parts, as the configuration names them: its record generator
  ``streambench/generators/<generator>.py``, and the reference's
  operators ``streambench/operators/<op>.py`` and aggregate
  ``streambench/aggregates/<reduce>.py``;
* a metric: ``streambench/metrics/<name>.py``, whose ``read(run)`` returns
  the number or None when the run has nothing to read;
* a kernel: ``streambench/kernels/<kernel>.py``, with the ``PATTERN`` that
  finds its operations in the device trace, the ``hbm_bytes(text)`` one
  call needs, and ``calls_per_window(config)``, the least calls per engine
  window that the configuration's guarantees need;
* the peaks of each device kind: ``streambench/peaks.json``.

Adding a cell, a configuration, a mix, a metric or a kernel adds files and
entries; nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(kind: str, name: str, root: str = ROOT) -> dict:
    with open(os.path.join(root, "streambench", kind, f"{name}.json")) as f:
        return json.load(f)


_MODULES: Dict[str, object] = {}


def load_module(kind: str, name: str, root: str = ROOT):
    """``streambench/<kind>/<name>.py`` as a module (names may hold dots
    and dashes, so it is loaded by path)."""
    path = os.path.join(root, "streambench", kind, f"{name}.py")
    mod = _MODULES.get(path)
    if mod is None:
        if not os.path.isfile(path):
            raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
        spec = importlib.util.spec_from_file_location(
            f"streambench.{kind}.{name.replace('.', '_').replace('-', '_')}",
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return mod


def names(kind: str, root: str = ROOT) -> List[str]:
    """The names of the ``streambench/<kind>/*.py`` files."""
    d = os.path.join(root, "streambench", kind)
    return sorted(f[:-3] for f in os.listdir(d)
                  if f.endswith(".py") and not f.startswith("_"))


def peaks(kind: str, root: str = ROOT) -> dict:
    """The peak table's row for a device kind; a kind not in the table is
    an error."""
    with open(os.path.join(root, "streambench", "peaks.json")) as f:
        table = json.load(f)
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in streambench/"
                       f"peaks.json ({sorted(table['devices'])})")
    return table["devices"][kind]


@dataclass
class Cell:
    """One workload entry with everything it names, loaded."""
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, cell: str, end_to_end: List[dict]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:          # a per-layer metric follows its metric
        return any(m["name"] == metric["moves"] for m in end_to_end)
    return True


def resolve(bench: dict, workload: str, root: str = ROOT) -> Cell:
    """The cell ``workload`` of ``bench``, its configuration file and
    traffic mix read, and the metrics it reports."""
    try:
        w = next(x for x in bench["workloads"] if x["name"] == workload)
    except StopIteration:
        raise KeyError(f"no workload {workload!r}; have "
                       f"{[x['name'] for x in bench['workloads']]}") from None
    entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload, [])]
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, workload, e2e)]
    return Cell(name=workload, config=config,
                traffic=load_json("traffic", w["traffic"], root),
                chips=int(w["chips"]), end_to_end=e2e, per_layer=per_layer)
