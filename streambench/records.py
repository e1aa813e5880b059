"""A cell's record pool, made from the seed by the configuration's record
generator, ``streambench/generators/<generator>.py``, whose
``records(n_records, config, seed)`` returns (n_records, record_words)
uint32 records, the same for the same seed."""
from __future__ import annotations

import numpy as np

from streambench import layout


def record_pool(config: dict, seed: int,
                root: str = layout.ROOT) -> np.ndarray:
    """The pool as (chunks, chunk_records, record_words) uint32: the stream
    cycles through it chunk by chunk."""
    n = int(config["pool_records"])
    chunk = int(config["chunk_records"])
    if n % chunk:
        raise ValueError(f"pool of {n} records is not whole chunks of "
                         f"{chunk}")
    gen = layout.load_module("generators", config["generator"], root)
    recs = gen.records(n, config, seed)
    return recs.reshape(n // chunk, chunk, int(config["record_words"]))
