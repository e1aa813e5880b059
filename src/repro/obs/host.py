"""Counted device->host transfers.

:func:`to_host` is ``np.asarray`` for a device array, plus three
always-on registry counters:

* ``device.to_host`` — transfers made;
* ``device.to_host_bytes`` — bytes brought to the host;
* ``device.to_host_seconds`` — wall seconds from the call until the host
  array exists, which includes waiting for the device to finish the work
  the array depends on.

Every blocking materialisation on the window engine's hot path goes
through it (the verdict vector of each window sync, the built-in
reducers), so a change that batches or removes transfers shows in these
counters where the transfers happen.  They are kept apart from
``pipeline.host_syncs`` (engine rendezvous) and ``device.dispatches``
(compiled-program launches).  A NumPy array passes through uncounted:
nothing moves.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.obs.metrics import REGISTRY

_TO_HOST = REGISTRY.counter("device.to_host")
_TO_HOST_BYTES = REGISTRY.counter("device.to_host_bytes")
_TO_HOST_SECONDS = REGISTRY.counter("device.to_host_seconds")


def to_host(x) -> np.ndarray:
    """``np.asarray(x)``, counted and timed when ``x`` is a device
    array."""
    if not isinstance(x, jax.Array):
        return np.asarray(x)
    t0 = time.perf_counter()
    out = np.asarray(x)
    _TO_HOST_SECONDS.inc(time.perf_counter() - t0)
    _TO_HOST.inc()
    _TO_HOST_BYTES.inc(out.nbytes)
    return out
