"""One run of one cell: set up, stream for ``seconds``, check, report.

The system under test is the configuration's DSL job (``repro.dsl``),
compiled once to a ``Pipeline`` and driven by ``Pipeline.run``, as a user
drives it.  A run:

1. makes the record pool from the seed, builds the ``Pipeline`` (workers
   measured and admitted, edge keys established) and warms it with whole
   windows of the cell's own shapes;
2. streams the pool, cycled, through that same ``Pipeline`` as the traffic
   mix says, stamping each chunk when it is due, when the engine takes it
   from the source, and when the sink's fold of it returns;
3. compares the sink's per-carrier result over exactly the chunks offered
   with the plain reference (:mod:`streambench.reference`), and in a
   traced run of a sealed mode counts each kernel's calls in the device
   trace against the least that the configuration's guarantees need;
4. reads the cell's metrics from the stamps, the program's counters and,
   in a traced run, the program's spans and the device trace.

The sink's fold is the benchmark's: it calls the job's registered reducer
unchanged, then stamps the time.  Nothing of this module decides what a
cell measures: configurations, mixes, metrics and kernels are files found
by name (:mod:`streambench.layout`).
"""
from __future__ import annotations

import gc
import os
import resource
import shutil
import sys
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from streambench import arrivals, layout, reference
from streambench.devicetrace import DeviceTrace, Profile
from streambench.records import record_pool

WARM_WINDOWS = 3
COUNTERS = ("pipeline.host_syncs", "device.dispatches")

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = ("/jax/compilation_cache/cache_hits",
                 "/jax/compilation_cache/cache_misses")
_COMPILES: List[float] = []          # perf_counter of every program build
_CACHE: Dict[str, int] = {e: 0 for e in _CACHE_EVENTS}
_LISTENING = []                      # non-empty once the listeners are on


def _count_compiles() -> None:
    """Record every program JAX builds in this process (a compile, or a
    load from the persistent cache) and the persistent cache's hits and
    misses, once per process."""
    import jax
    if _LISTENING:
        return

    def on_event(event, **kw):
        if event in _CACHE:
            _CACHE[event] += 1
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: _COMPILES.append(time.perf_counter())
        if event == _COMPILE_EVENT else None)
    jax.monitoring.register_event_listener(on_event)
    _LISTENING.append(True)


@dataclass
class Run:
    """What one run recorded, for the metric readers.  Times are
    ``perf_counter`` seconds."""
    config: dict
    window_chunks: int
    t_process: float
    t_open: float                  # the first chunk is due
    t_end: float                   # run() returned after the drain
    due: np.ndarray                # per offered chunk
    take: np.ndarray               # the engine took it from the source
    fold: np.ndarray               # per fold, in stream order
    counters: Dict[str, float]     # program counters over the window
    peaks: dict
    spans: Optional[List[Tuple[str, float, float]]] = None
    device: Optional[DeviceTrace] = None
    interval: Optional[Tuple[float, float]] = None   # traced interval

    @property
    def offered(self) -> int:
        return len(self.due)

    @property
    def folded(self) -> int:
        return len(self.fold)

    @property
    def windows(self) -> float:
        return self.offered / self.window_chunks


@dataclass
class Outcome:
    """The result line's parts."""
    correct: bool
    attempted: int
    failed: int
    metrics: Dict[str, dict]
    device: dict
    checks: Dict[str, dict]
    breakdown: Optional[dict] = None
    run: Optional[Run] = None

    def line(self) -> dict:
        out = {"correct": self.correct, "attempted": self.attempted,
               "failed": self.failed, "metrics": self.metrics,
               "device": self.device}
        if self.breakdown is not None:
            out["breakdown"] = self.breakdown
        out["checks"] = self.checks
        return out


class _Pauses:
    """What held the host up while the window ran: the garbage collector's
    pauses, and the process's CPU time, context switches and page faults
    (``getrusage``)."""

    def __enter__(self):
        self.gc: List[Tuple[int, float, float]] = []   # (gen, start, end)
        self._t = 0.0
        self.ru0 = resource.getrusage(resource.RUSAGE_SELF)
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on_gc)
        self.ru1 = resource.getrusage(resource.RUSAGE_SELF)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.gc.append((info["generation"], self._t,
                            time.perf_counter()))

    def report(self, folds, take, t_open, t_end) -> str:
        def longest(ts):
            ts = np.concatenate([[t_open], np.asarray(ts, float)])
            if len(ts) < 2:
                return 0.0, 0.0
            i = int(np.argmax(np.diff(ts)))
            return float(ts[i + 1] - ts[i]), float(ts[i] - t_open)
        f_gap, f_at = longest(folds)
        t_gap, t_at = longest(take)
        d = [b - a for _, a, b in self.gc]
        r0, r1 = self.ru0, self.ru1
        return (f"window {t_end - t_open} s: longest gap between folds "
                f"{f_gap} s at {f_at} s, between takes {t_gap} s at {t_at} "
                f"s; gc {len(d)} pauses, {sum(d)} s, longest "
                f"{max(d, default=0.0)} s, {sum(g == 2 for g, _, _ in self.gc)}"
                f" of generation 2; cpu user {r1.ru_utime - r0.ru_utime} s "
                f"sys {r1.ru_stime - r0.ru_stime} s; context switches "
                f"{r1.ru_nvcsw - r0.ru_nvcsw} voluntary "
                f"{r1.ru_nivcsw - r0.ru_nivcsw} involuntary; major faults "
                f"{r1.ru_majflt - r0.ru_majflt}")


def kernel_calls(run: "Run", log=lambda msg: None) -> Dict[str, dict]:
    """A traced run's kernel calls against the configuration's guarantees:
    for each kernel (``streambench/kernels/<kernel>.py``), the calls that
    the windows folded inside the traced interval need and the trace does
    not hold, ``<kernel>_calls_short``, limit 0.  A sealed window that
    skipped its MAC checks or ran a hop outside the enclave kernel shows
    here."""
    lo, hi = run.interval
    windows = int(np.sum(run.fold <= hi)) // run.window_chunks
    out = {}
    for name in layout.names("kernels"):
        mod = layout.load_module("kernels", name)
        need = mod.calls_per_window(run.config) * windows
        if need:
            got = len(run.device.events(lo, hi, mod.PATTERN))
            log(f"{name}: {got} calls for {windows} windows, "
                f"{need} needed")
            out[f"{name}_calls_short"] = {"value": float(max(0, need - got)),
                                          "limit": 0.0}
    return out


def build_pipeline(config: dict, fold: Callable, seed: int):
    """The configuration's job through the DSL, compiled to a Pipeline."""
    from repro.dsl import stream
    b = stream()
    for st in config["job"]["stages"]:
        verb = getattr(b, st["kind"])
        b = verb(st["op"], name=st["name"], const=st["const"],
                 workers=int(st["workers"]), sgx=bool(st["sgx"]))
    b = b.reduce(fold, None, name="reducer").window(
        int(config["window_factor"])).seed(seed)
    return b.build(config["mode"])


def window_chunks(config: dict) -> int:
    """Chunks in one engine window: the first stage's workers times the
    window factor."""
    return int(config["job"]["stages"][0]["workers"]) \
        * int(config["window_factor"])


def _memory_peak() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks)) if peaks else 0


def run_cell(cell: layout.Cell, seed: int, seconds: float, trace: bool, *,
             peaks: dict, t_process: float, out_dir: str,
             skip_fold: Optional[Callable[[int], bool]] = None,
             log=lambda msg: print(msg, file=sys.stderr)) -> Outcome:
    """One run of ``cell`` on the devices JAX has, whatever they are (the
    caller checks them); ``peaks`` is the device kind's row of the peak
    table.  ``skip_fold(i)`` makes the sink skip the i-th fold of the
    window (the control; never set by the benchmark's own runs)."""
    import jax
    import jax.numpy as jnp
    from repro.dsl.reducers import resolve_reducer
    from repro.obs.metrics import REGISTRY
    from repro.obs.trace import Tracer

    _count_compiles()
    t0 = time.perf_counter()
    config, mix = cell.config, cell.traffic
    dev0 = jax.devices()[0]
    win = window_chunks(config)
    pool = record_pool(config, seed)
    t_pool = time.perf_counter()
    n_pool = len(pool)
    reduce_name = config["job"]["reduce"]
    reduce_args = config["job"].get("reduce_args", {})
    base_fold, _ = resolve_reducer(reduce_name, **reduce_args)
    folds: List[float] = []

    def fold(acc, chunk):
        if acc is None:
            acc = resolve_reducer(reduce_name, **reduce_args)[1]
        if skip_fold is None or not skip_fold(len(folds)):
            acc = base_fold(acc, chunk)
        folds.append(time.perf_counter())
        return acc

    pipe = build_pipeline(config, fold, seed)
    t_build = time.perf_counter()
    warm = [jnp.asarray(pool[k % n_pool]) for k in range(WARM_WINDOWS * win)]
    pipe.run(iter(warm))
    del warm
    t_warm = time.perf_counter()
    n_setup_compiles = len(_COMPILES)
    log(f"set-up: {t0 - t_process} s to the first device call, pool "
        f"{t_pool - t0} s, build {t_build - t_pool} s, warm-up "
        f"{t_warm - t_build} s; {n_setup_compiles} programs built or loaded,"
        f" cache hits {_CACHE[_CACHE_EVENTS[0]]} misses "
        f"{_CACHE[_CACHE_EVENTS[1]]}")

    offsets = arrivals.schedule(mix, seconds, win, seed)
    due: List[float] = []
    take: List[float] = []
    waits: List[Tuple[str, float, float]] = []
    puts: List[Tuple[str, float, float]] = []
    profile = Profile(os.path.join(out_dir, "profile")) if trace else None
    tracer = Tracer() if trace else None
    if trace:
        profile.start()
    t_open = 0.0

    def source():
        k = 0
        while True:
            if offsets is None:
                if k % win == 0 and time.perf_counter() - t_open >= seconds:
                    break
                d = t_open
            else:
                if k >= len(offsets):
                    break
                d = t_open + offsets[k]
                now = time.perf_counter()
                if d > now:
                    time.sleep(d - now)
                    waits.append(("source.wait", now, time.perf_counter()))
            due.append(d)
            t = time.perf_counter()
            take.append(t)
            chunk = jnp.asarray(pool[k % n_pool])
            if trace:
                puts.append(("source.put", t, time.perf_counter()))
            yield chunk
            k += 1
        if profile is not None:
            profile.stop()

    base = {c: REGISTRY.counter(c).value for c in COUNTERS}
    folds.clear()
    pauses = _Pauses()
    c0 = len(_COMPILES)
    if tracer is not None:
        t_mark = time.perf_counter()
        tracer_off = t_mark - tracer.instant("streambench.clock").start
    t_open = time.perf_counter()
    with pauses:
        result = pipe.run(source(), tracer=tracer)
    t_end = time.perf_counter()
    n_window_compiles = len(_COMPILES) - c0
    counters = {c: REGISTRY.counter(c).value - base[c] for c in COUNTERS}
    memory_peak = _memory_peak()
    log(f"set-up {t_open - t_process} s; {n_window_compiles} programs "
        f"built inside the window")
    log(pauses.report(folds, take, t_open, t_end))

    run = Run(config=config, window_chunks=win,
              t_process=t_process, t_open=t_open, t_end=t_end,
              due=np.asarray(due), take=np.asarray(take),
              fold=np.asarray(folds), counters=counters, peaks=peaks)
    breakdown = None
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": memory_peak}
    if trace:
        run.spans = [(s.name, s.start + tracer_off, s.end + tracer_off)
                     for s in tracer.spans if s.end is not None] \
            + waits + puts
        run.device = profile.read()
        shutil.rmtree(profile.logdir, ignore_errors=True)
        run.interval = (t_open, profile.stop_perf)
        lo, hi = run.interval
        device["busy_s"] = run.device.busy_s(lo, hi)
        device["window_s"] = hi - lo
        breakdown = {"device_ops": run.device.top_ops(lo, hi),
                     "idle_gaps": run.device.idle_gaps(lo, hi, run.spans)}

    # the reference, once the window has closed
    want = reference.expected(reference.per_chunk_result(config, pool),
                              [k % n_pool for k in range(run.offered)])
    checks = {k: {"value": v, "limit": 0.0}
              for k, v in reference.gaps(result, want).items()}
    checks["fold_gap"] = {"value": float(abs(run.folded - run.offered)),
                          "limit": 0.0}
    if trace:
        checks.update(kernel_calls(run, log))
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = layout.load_module("metrics", m["name"]).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    return Outcome(correct=correct, attempted=run.offered,
                   failed=max(0, run.offered - run.folded), metrics=metrics,
                   device=device, checks=checks, breakdown=breakdown,
                   run=run)
