"""Plain numpy reference of a configuration's job, and the comparison that
decides ``correct``.

The job is the configuration's stage list applied record by record, then its
aggregate.  Each operator is a file, ``streambench/operators/<op>.py`` with
``apply(recs, const)``, and each aggregate is a file,
``streambench/aggregates/<reduce>.py`` with ``per_chunk(recs, config)``,
both written from the job's meaning (paper §5.2), not from the program.
Counts and sums are exact integers, so the comparison is exact.
"""
from __future__ import annotations

from typing import Dict, Iterable

import numpy as np

from streambench import layout


def per_chunk_result(config: dict, pool: np.ndarray,
                     root: str = layout.ROOT) -> Dict[str, np.ndarray]:
    """The job's aggregate of every pool chunk on its own:
    {key: (chunks, ...) int64}."""
    recs = pool
    for st in config["job"]["stages"]:
        op = layout.load_module("operators", st["op"], root)
        recs = op.apply(recs, st["const"])
    agg = layout.load_module("aggregates", config["job"]["reduce"], root)
    return agg.per_chunk(recs, config)


def expected(per_chunk: Dict[str, np.ndarray],
             chunk_ids: Iterable[int]) -> Dict[str, np.ndarray]:
    """The aggregate over the stream chunks ``chunk_ids`` (pool indices,
    repeats counted)."""
    n = next(iter(per_chunk.values())).shape[0]
    times = np.bincount(np.asarray(list(chunk_ids), np.int64), minlength=n)
    return {k: np.tensordot(times, v, axes=1) for k, v in per_chunk.items()}


def gaps(result, want: Dict[str, np.ndarray]) -> Dict[str, float]:
    """Largest absolute difference per aggregate key; a missing result
    differs by the whole expected value."""
    out = {}
    for k, w in want.items():
        got = np.zeros_like(w, dtype=np.float64) if result is None \
            else np.asarray(result[k], np.float64)
        out[f"{k}_gap"] = float(np.max(np.abs(got - w))) if w.size else 0.0
    return out
