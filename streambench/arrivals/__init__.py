"""The traffic generator: a mix file's parameters (``traffic/<mix>.json``)
-> chunk due times.

A chunk is one ingest poll of ``chunk_records`` events; its due time is when
its last event was created.  The mix's ``arrival`` names its kind, a file of
its own, ``streambench/arrivals/<kind>.py``, whose ``schedule(mix, seconds,
window_chunks, seed)`` returns the due offsets in seconds from the first due
chunk, one per chunk, for an open loop, or None for a backlog (the source
then offers whole windows until the run's time is up).  A new kind is a new
file.
"""
from __future__ import annotations

import re
from typing import Optional

import numpy as np

from streambench import layout

_KIND = re.compile(r"^[A-Za-z0-9_]+$")


def schedule(mix: dict, seconds: float, window_chunks: int, seed: int,
             root: str = layout.ROOT) -> Optional[np.ndarray]:
    kind = mix.get("arrival")
    if not isinstance(kind, str) or not _KIND.match(kind):
        raise ValueError(f"traffic arrival {kind!r} is not a kind name")
    try:
        mod = layout.load_module("arrivals", kind, root)
    except FileNotFoundError:
        raise ValueError(f"no arrival kind {kind!r} under "
                         f"streambench/arrivals/") from None
    return mod.schedule(mix, seconds, window_chunks, seed)
