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

from repro.data.synthetic import CARRIER_WORD, DELAY_WORD
from repro.obs.host import to_host

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
