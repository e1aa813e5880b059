"""A kernel's share of its HBM roofline in a traced run.

The least time the chip could take for the kernel's calls is the HBM bytes
they need over the chip's peak HBM bandwidth (``peaks.json``); the share is
that time over the kernel's summed device time.  TPU v5e publishes no peak
for 32-bit integer work on its vector unit, which is what these cipher
kernels do, so the HBM bound is the only roofline that can be stated.
"""
from __future__ import annotations

from typing import Optional

from streambench import layout


def roofline_share(run, kernel: str) -> Optional[float]:
    """Per cent; None when the traced interval holds no call of the
    kernel, or a call whose shapes its cost function does not know."""
    if run.device is None:
        return None
    mod = layout.load_module("kernels", kernel)
    lo, hi = run.interval
    events = run.device.events(lo, hi, mod.PATTERN)
    if not events:
        return None
    need = [mod.hbm_bytes(text) for _, _, text in events]
    took = sum(b - a for a, b, _ in events)
    if None in need or took <= 0:
        return None
    return 100.0 * sum(need) / run.peaks["hbm_bytes_per_s"] / took
