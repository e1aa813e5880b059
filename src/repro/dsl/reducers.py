"""Named terminal reducers, so declarative specs can reference them.

A TOML spec cannot carry a Python closure, but the paper's Listing-1 jobs
end in well-known reductions — so reducers register under a name and a
spec says ``reduce = "carrier_delay_stats"``.  Each registration is a
*factory* returning a fresh ``(fn, init)`` pair per pipeline build (a
shared mutable ``init`` across builds would make reruns accumulate).

A reducer is ``fn(acc, chunk) -> acc``, called once per verified chunk
in stream order.  On the window engine ``chunk`` is a read-only host
(NumPy) array of the chunk's shape and dtype: the sink brings each opened
egress window to the host in one counted transfer
(:func:`repro.obs.host.to_host`) and hands the reducer its rows.  The
per-chunk oracle engine (``window_chunks=1``) passes the device array;
the built-ins call ``to_host`` on what they read, which passes NumPy
input through uncounted, so they fold either.

Built-ins:

* ``carrier_delay_stats`` — the paper's own DelayedFlights benchmark
  (§5.2): per-carrier delayed-flight counts + delay sums over packed
  uint32 records (word 0 = carrier, word 1 = delay minutes).
* ``ysb_campaign_window_counts`` — the Yahoo Streaming Benchmark's
  result: views per (window slot, campaign), summed from the partial-count
  rows of the ``ysb_window_count`` aggregate.
* ``sum`` — elementwise running sum of chunks (the 8-stage acceptance
  pipeline's terminal fold).
* ``count`` — number of chunks that reached the sink.

Register your own::

    from repro.dsl import register_reducer

    @register_reducer("my_stats")
    def _my_stats(**kw):
        def fn(acc, chunk): ...
        return fn, init
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import numpy as np

from repro.data import ysb
from repro.data.synthetic import CARRIER_WORD, DELAY_WORD
from repro.obs.host import to_host
from repro.obs.metrics import REGISTRY as _METRICS

ReducerFactory = Callable[..., Tuple[Callable, Any]]

REDUCERS: Dict[str, ReducerFactory] = {}


def register_reducer(name: str) -> Callable[[ReducerFactory],
                                            ReducerFactory]:
    """Decorator: register a ``(**kw) -> (fn, init)`` reducer factory
    under ``name`` for use in TOML specs and ``.reduce("name")``."""
    def deco(factory: ReducerFactory) -> ReducerFactory:
        REDUCERS[name] = factory
        return factory
    return deco


def resolve_reducer(name: str, **kw) -> Tuple[Callable, Any]:
    """Instantiate a registered reducer -> fresh ``(fn, init)``."""
    factory = REDUCERS.get(name)
    if factory is None:
        raise KeyError(f"unknown reducer {name!r}; registered: "
                       f"{sorted(REDUCERS)} "
                       f"(add one with @register_reducer)")
    return factory(**kw)


@register_reducer("carrier_delay_stats")
def _carrier_delay_stats(num_carriers: int = 20):
    """Per-carrier delayed count + delay-minute sum (paper §5.2)."""
    def fn(acc, chunk):
        carrier = to_host(chunk[:, CARRIER_WORD]).astype(np.int64)
        delay = to_host(chunk[:, DELAY_WORD]).astype(np.int64)
        valid = delay > 0
        acc["count"] = acc["count"] + np.bincount(
            carrier[valid], minlength=num_carriers)
        acc["sum"] = acc["sum"] + np.bincount(
            carrier[valid], weights=delay[valid], minlength=num_carriers)
        return acc
    return fn, {"count": np.zeros(num_carriers),
                "sum": np.zeros(num_carriers)}


@register_reducer("ysb_campaign_window_counts")
def _ysb_campaign_window_counts(windows: int = 5,
                                campaigns: int = ysb.CAMPAIGNS):
    """YSB's views per campaign per event-time window, exact, as
    ``{"count": (windows, campaigns) int64}``.  A partial-count row
    (``ysb_window_count``) holds the window number in words 0-1, the
    campaign in word 2 and the count in word 3; a row of count 0 is
    empty.  Window ``w`` lands in slot ``w % windows``: ``windows`` has to
    cover the stream's span of event time.  Views joined to no campaign
    add to the ``join.misses`` counter and to no campaign; every non-empty
    row adds one to ``aggregate.keys_out``."""
    keys_out = _METRICS.counter("aggregate.keys_out")
    misses = _METRICS.counter("join.misses")

    def fn(acc, chunk):
        rows = to_host(chunk)
        live = rows[:, 3] > 0
        if not live.any():
            return acc
        rows = rows[live]
        count = rows[:, 3].astype(np.int64)
        window = rows[:, 0].astype(np.uint64) \
            | (rows[:, 1].astype(np.uint64) << np.uint64(32))
        slot = (window % np.uint64(windows)).astype(np.int64)
        camp = rows[:, 2].astype(np.int64)
        hit = camp < campaigns
        keys_out.inc(len(rows))
        misses.inc(int(count[~hit].sum()))
        np.add.at(acc["count"], (slot[hit], camp[hit]), count[hit])
        return acc
    return fn, {"count": np.zeros((windows, campaigns), np.int64)}


@register_reducer("sum")
def _sum():
    """Elementwise running sum over chunks (None-seeded first fold)."""
    def fn(acc, chunk):
        return chunk if acc is None else acc + to_host(chunk)
    return fn, None


@register_reducer("count")
def _count():
    """Count of chunks that survived to the sink."""
    return (lambda acc, chunk: acc + 1), 0
