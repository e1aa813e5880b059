"""The device trace of a traced run: the profiler session, and its reduction
to busy time, kernel events, the costliest device operations and the idle
gaps between them.

Times are moved onto the host's ``perf_counter`` clock, the clock of the
program's spans and of the benchmark's own stamps.  Right after the
profiler starts, one ``jax.profiler.TraceAnnotation`` named :data:`MARK` is
opened at a known ``perf_counter`` reading; its start in the trace ties the
profiler's clock to ``perf_counter``.  Device operations are the events of
the ``XLA Ops`` line of every ``/device:`` plane; their text is the HLO
instruction, shapes included, which the kernel cost functions read.
"""
from __future__ import annotations

import collections
import glob
import os
import re
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from streambench import stats

MARK = "streambench.clock"
OPS_LINE = "XLA Ops"

# one HLO shape: dtype[d0,d1,...]
_SHAPE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
          "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
          "f64": 8}

Event = Tuple[float, float, str]          # (start, end, HLO text)


def shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """(dtype, dims) of an HLO instruction: its result first, then its
    operands, up to its attributes."""
    head = text.split("custom_call_target=")[0]
    head = head.split(", metadata=")[0]
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims in _SHAPE.findall(head)]


def nbytes(dtype: str, dims: Sequence[int]) -> int:
    n = _BYTES[dtype]
    for d in dims:
        n *= d
    return n


def op_kind(text: str) -> str:
    """``%chacha20_xor_rows.1 = u32[...] custom-call(...)`` ->
    ``chacha20_xor_rows``."""
    name = text.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", name)


@dataclass
class DeviceTrace:
    """Device operations per device, on the ``perf_counter`` clock."""
    devices: Dict[str, List[Event]] = field(default_factory=dict)

    def busy(self, device: str, lo: float, hi: float):
        return stats.union(stats.clip(
            ((a, b) for a, b, _ in self.devices[device]), lo, hi))

    def busy_s(self, lo: float, hi: float) -> float:
        """Seconds of [lo, hi] in which some operation ran, averaged over
        the devices."""
        if not self.devices:
            return 0.0
        return sum(stats.covered(self.busy(d, lo, hi))
                   for d in self.devices) / len(self.devices)

    def events(self, lo: float, hi: float, pattern=None) -> List[Event]:
        """Operations that start inside [lo, hi], on every device,
        optionally only those whose text matches ``pattern``."""
        return [e for evs in self.devices.values() for e in evs
                if lo <= e[0] < hi
                and (pattern is None or pattern.search(e[2]))]

    def top_ops(self, lo: float, hi: float, k: int = 10):
        """[[operation kind, seconds], ...], the k costliest in [lo, hi]."""
        tot: Dict[str, float] = collections.Counter()
        for a, b, text in self.events(lo, hi):
            tot[op_kind(text)] += b - a
        return [[n, s] for n, s in tot.most_common(k)]

    def idle_gaps(self, lo: float, hi: float,
                  spans: Sequence[Tuple[str, float, float]], k: int = 10):
        """[[host span, seconds], ...]: the time in [lo, hi] in which the
        first device ran nothing, summed by the innermost host span open
        at the time ("none" when none was), the k largest sums."""
        if not self.devices:
            return []
        dev = sorted(self.devices)[0]
        idle = stats.gaps(self.busy(dev, lo, hi), lo, hi)
        tot: Dict[str, float] = collections.Counter()
        j = 0
        segs = innermost(spans, lo, hi)
        for a, b in idle:
            while j < len(segs) and segs[j][1] <= a:
                j += 1
            i = j
            while i < len(segs) and segs[i][0] < b:
                s0, s1, name = segs[i]
                tot[name] += min(b, s1) - max(a, s0)
                i += 1
        return [[n, t] for n, t in tot.most_common(k)]


def innermost(spans: Sequence[Tuple[str, float, float]], lo: float,
              hi: float) -> List[Tuple[float, float, str]]:
    """[lo, hi] cut into pieces, each named by the innermost span (the
    latest started of those open) over it, or "none"."""
    marks = []
    for i, (_, a, b) in enumerate(spans):
        a, b = max(a, lo), min(b, hi)
        if b > a:
            marks += [(a, 1, i), (b, 0, i)]
    marks.sort()
    open_: Dict[int, float] = {}
    out: List[Tuple[float, float, str]] = []
    t = lo
    for at, starts, i in marks + [(hi, 0, -1)]:
        if at > t:
            name = spans[max(open_, key=open_.get)][0] if open_ else "none"
            out.append((t, at, name))
            t = at
        if starts:
            open_[i] = spans[i][1]
        else:
            open_.pop(i, None)
    return out


class Profile:
    """One profiler session of the traced run, written under ``logdir``."""

    def __init__(self, logdir: str):
        self.logdir = logdir
        self.mark_perf: Optional[float] = None
        self.stop_perf: Optional[float] = None

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.logdir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(MARK):
            self.mark_perf = time.perf_counter()

    def stop(self) -> None:
        import jax
        if self.stop_perf is None:
            self.stop_perf = time.perf_counter()
            jax.profiler.stop_trace()

    def read(self) -> DeviceTrace:
        """The trace, reduced to device operations on ``perf_counter``."""
        import jax
        files = sorted(glob.glob(os.path.join(
            self.logdir, "plugins", "profile", "*", "*.xplane.pb")))
        if not files:
            raise RuntimeError(f"the profiler wrote no trace under "
                               f"{self.logdir}")
        data = jax.profiler.ProfileData.from_file(files[-1])
        mark_ns = None
        devices: Dict[str, List[Event]] = {}
        for plane in data.planes:
            if plane.name.startswith("/host"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name == MARK:
                            mark_ns = e.start_ns
            elif plane.name.startswith("/device:"):
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        devices[plane.name] = [
                            (e.start_ns, e.end_ns, e.name)
                            for e in line.events]
        if mark_ns is None:
            raise RuntimeError(f"no {MARK!r} marker in the trace")
        off = self.mark_perf - mark_ns * 1e-9
        return DeviceTrace({d: [(a * 1e-9 + off, b * 1e-9 + off, n)
                                for a, b, n in evs]
                            for d, evs in devices.items()})
