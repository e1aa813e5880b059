"""Pipeline builder: stages + routers -> an executable secure dataflow.

Mirrors the paper's Compose description (Listing 1): a pipeline is a list
of named stages, each with an operator, a worker count, and a placement
("sgx" workers are the ones whose operator runs under the enclave
executor).  Routers between stages apply fair-queue (in) / round-robin
(out) chunk scheduling — repro.core.router.

Execution is streaming and **window-vectorized**: the unit of device work
is a window of ``window_chunks`` chunks per worker, not a chunk.  Ingress
seals whole windows with the batched AEAD fast path behind a small
prefetch/double-buffer (window N+1's seal is dispatched before window N
is handed downstream, so it overlaps downstream compute via JAX async
dispatch), with nonce-counter blocks reserved per window from the
directory.  Each stage hop runs every worker's per-window share as ONE
launch of a cached compiled open -> operator -> seal program
(:meth:`repro.core.enclave.EnclaveExecutor.run_static_window` ->
``repro.core.enclave._hop_program``), and MAC verdicts are
**deferred**: per-row verdicts stay on device and sync to host once per
hop per window (each stage, then the sink) — failed rows are dropped
there and counted as ``mac_failures`` — instead of one blocking
``bool()`` per chunk.  One loop, :meth:`Pipeline._stage_stream`, runs
every round of every hop; fault tolerance (``retry``/``chaos``) is
hooks inside that loop (retained inputs, per-share retry and failover,
replays of lost rows), not a second loop.
``window_chunks=1`` degenerates to the original per-chunk engine and is
kept as the bit-identical oracle.  Compiled programs live in shape-keyed
caches, so steady-state streaming compiles nothing.

Per-edge session keys come from a ``repro.attest.KeyDirectory``: every
stage worker is measured (repro.attest.measure), enrolled, and admitted
only if its quote verifies, and edge keys are established by the attested
handshake — the trust bootstrap the paper assumes pre-done.
``run(rekey_every_n=...)`` rotates every edge key mid-stream (epoch
ratchet); a window straddling a flip opens every row under its ingress
epoch (per-row keys — rows never cross keystreams), and
``KeyDirectory.revoke`` evicts a worker live — subsequent windows skip
it.  Per-stage counters, byte totals, and MAC failures feed the
benchmarks (paper Fig. 6/7/8); ``StageMetrics.seconds`` is measured at
window granularity around a ``block_until_ready``, so throughput numbers
time execution, not async enqueue.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, Iterator, List, Optional, \
    Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.attest.directory import (EdgeHandle, KeyDirectory,
                                    KeyDirectoryError)
from repro.attest.measure import IO_ENDPOINT, measure_stage
from repro.attest.quote import QuoteError
from repro.configs.base import SecureStreamConfig
from repro.core import router as R
from repro.core.enclave import (EnclaveExecutor, SealedChunk, SealedWindow,
                                egress, egress_window, ingress, plain_window,
                                seal_table, seal_tensors_window,
                                take_rows, uniform_runs)
from repro.kernels.enclave_map.ops import AGGREGATE_OPS, TABLE_OPS
from repro.obs.host import to_host
from repro.obs.metrics import (REGISTRY as _METRICS, dispatch_count,
                               reset_dispatch_count)  # noqa: F401 (re-export)
from repro.obs.monitor import NULL_MONITOR
from repro.obs.trace import NULL_TRACER


@dataclass
class Stage:
    """One named pipeline stage — the paper's Listing-1 unit.

    ``op`` names a statically registered operator
    (``repro.kernels.enclave_map.ops.OPS`` — the only code attestable
    under ``mode="enclave"``) or ``"custom"`` when ``fn``/``reduce_fn``
    carries a Python callable (plain/encrypted modes only).  ``workers``
    is the stage's fan-out pool size; ``sgx`` is the paper's
    ``constraint:type==sgx`` placement flag (non-sgx stages run on the
    encrypted, non-enclave path when the pipeline mode is ``enclave``).
    A stage with ``reduce_fn`` is terminal: it folds decrypted chunks at
    the trusted sink edge, seeded with ``reduce_init``.  ``reduce_fn(acc,
    chunk)`` is called once per verified chunk, in stream order; on the
    window engine ``chunk`` is a read-only host (NumPy) array of the
    chunk's shape and dtype, a row of its egress window brought to the
    host in one transfer (the ``window_chunks=1`` oracle passes the
    device array).

    A table op (``ysb_campaign_join``) reads a table that the op's rule
    derives from ``const`` (:func:`stage_table`); the pipeline seals it
    onto the device once, over an attested session of its own
    (``table/<name>``), and its digest enters the stage's measurement.  An aggregate op (``ysb_window_count``) turns each
    worker share into partial-count rows, which the merge and egress
    carry like any rows.

    Stages are usually not built by hand anymore — ``repro.dsl.stream``
    / ``repro.dsl.load_spec`` compile to this dataclass (bit-identically;
    the hand-built form is kept as the tests' parity oracle).
    """
    name: str
    op: str                              # static registry op name, or "custom"
    const: float = 0.0
    fn: Optional[Callable] = None        # custom fn (plain/encrypted only)
    workers: int = 1
    sgx: bool = True                     # paper: constraint:type==sgx
    reduce_fn: Optional[Callable] = None # terminal reduce (runs at egress)
    reduce_init: Any = None


def stage_table(st: Stage) -> Optional[np.ndarray]:
    """A table op's (64, 128) u32 table, by the op's rule from the stage's
    ``const`` (``enclave_map.TABLE_OPS``); None for any other stage."""
    return TABLE_OPS[st.op][1](st.const) if st.op in TABLE_OPS else None


def stage_measurement(st: Stage) -> bytes:
    """The measurement every worker of the stage is admitted under (a
    table op's table digest included)."""
    return measure_stage(op=st.op, const=st.const, fn=st.fn, sgx=st.sgx,
                         table=stage_table(st))


@dataclass
class StageMetrics:
    """Per-stage counters behind ``Pipeline.report()`` (paper Fig. 6-8):
    surviving chunks, payload bytes, execution seconds (measured around a
    ``block_until_ready`` at window granularity), MAC failures (dropped
    rows), and per-worker chunk counts from the round-robin fan-out."""
    chunks: int = 0
    bytes: int = 0
    seconds: float = 0.0
    mac_failures: int = 0
    # chunks handled per worker of the stage (round-robin fan-out accounting;
    # survives rescaling — scale_stage pads/keeps this list).
    per_worker: List[int] = field(default_factory=list)
    # window rounds processed and compiled-program launches attributed to
    # them (the megakernel item's per-hop regression signal: fusing this
    # stage's open->op->seal chain must DROP dispatches_per_window)
    windows: int = 0
    dispatches: int = 0

    def cover(self, workers: int) -> None:
        """Pad ``per_worker`` with zeros up to ``workers`` entries."""
        self.per_worker.extend([0] * (workers - len(self.per_worker)))

    @property
    def dispatches_per_window(self) -> Optional[float]:
        if self.windows == 0:
            return None
        return self.dispatches / self.windows

    @property
    def throughput_mbps(self) -> Optional[float]:
        """Payload MB/s over the stage's measured execution seconds.

        ``None`` means *nothing was measured yet* (no execution seconds
        recorded) — distinct from a genuine ``0.0``, which means time
        passed but no payload survived (every row MAC-failed)."""
        if self.seconds <= 0.0:
            return None
        return (self.bytes / 1e6) / self.seconds

    @property
    def mac_failure_rate(self) -> Optional[float]:
        """Fraction of rows this stage dropped to MAC failures; ``None``
        before the stage has seen any row at all."""
        seen = self.chunks + self.mac_failures
        if seen == 0:
            return None
        return self.mac_failures / seen


# One host rendezvous per hop per window (deferred-verdict sync + block on
# the hop's outputs): a two-stage job makes three per window, one per
# stage and one at the sink.  A regression back to per-chunk syncing
# shows up as this counter growing with the chunk count instead of the
# window count.  The transfers themselves (each verdict vector, each
# opened egress group) are counted apart (``device.to_host``,
# repro.obs.host).
# Registered in the process-wide metrics registry; the module-level
# functions below are the original API, kept as thin shims.
_HOST_SYNCS = _METRICS.counter("pipeline.host_syncs")


def host_sync_count() -> int:
    """Device->host synchronisation rendezvous performed by the streaming
    engine (one per hop per window).  Shim over the registered counter
    ``pipeline.host_syncs``."""
    return int(_HOST_SYNCS.value)


def reset_host_sync_count() -> None:
    """Zero the rendezvous counter (test setup)."""
    _HOST_SYNCS.reset()


# Compiled-program launches (incremented at every eager launch site:
# aead fastpath, enclave_map, eager cwmac, dist.exchange).  The engine
# reads deltas around each window round to attribute launches per stage
# hop; ``dispatch_count()``/``reset_dispatch_count()`` (re-exported above
# from repro.obs.metrics) are the process-wide shims next to
# ``host_sync_count()``.
_DISPATCHES = _METRICS.counter("device.dispatches")


def _shape_runs(xs: List[jax.Array]):
    """Consecutive same-(shape, dtype) runs of a tensor list — each run
    frames as one batched window (ragged tails get their own)."""
    return uniform_runs(xs, lambda x: (x.shape, x.dtype))


def _sync_window(outputs: List[jax.Array],
                 vec_specs: List[Tuple[Optional[jax.Array], int]],
                 tracer=NULL_TRACER, track: str = "main",
                 window: int = 0) -> np.ndarray:
    """One hop's host sync of a window: block until the hop's outputs are
    ready and materialize every deferred MAC verdict in a single counted
    transfer (:func:`repro.obs.host.to_host`).  ``vec_specs`` is [(device
    verdict vector or None, n)]; None (plain mode) counts as all-pass and
    moves nothing.  The ``sync.verdicts`` span is where device time
    surfaces on a timeline — dispatch spans upstream only measure (async)
    enqueue.  ``window`` is the first window's id, for the span."""
    _HOST_SYNCS.inc()
    with tracer.span("sync.verdicts", cat="sync", track=track,
                     rows=sum(n for _, n in vec_specs), window=window):
        if outputs:
            jax.block_until_ready(outputs)
        if all(ok is None for ok, _ in vec_specs):
            return np.ones(sum(n for _, n in vec_specs), bool)
        parts = [jnp.ones((n,), bool) if ok is None else ok
                 for ok, n in vec_specs]
        vec = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        return to_host(vec)


def _verified_rows_to_host(vals: jax.Array,
                           ok: np.ndarray) -> Optional[np.ndarray]:
    """The rows of an opened egress group whose MAC verified, in stream
    order, brought to the host in one counted transfer
    (:func:`repro.obs.host.to_host`).  A failed row never leaves the
    device: a group with any failure is first gathered down to its
    verified rows there.  None when no row verified (nothing moves)."""
    if ok.all():
        return to_host(vals)
    keep = np.flatnonzero(ok)
    if keep.size == 0:
        return None
    return to_host(jnp.take(vals, keep, axis=0))


class Pipeline:
    """An executable secure dataflow: ordered :class:`Stage` list +
    routers + per-edge attested session keys, streamed by the
    window-vectorized engine (see the module docstring for the execution
    model and its invariants — epoch-carrying chunks, directory-reserved
    nonce-counter blocks, counter continuation across ``run()`` calls).

    ``fusion`` is builder metadata from ``repro.dsl.compile``: a
    ``{"fused_from": {survivor: [absorbed stage names]}, "decisions":
    [...]}`` record of bit-exact stage merges, surfaced via
    :meth:`report` — hand-built pipelines simply leave it empty.
    """

    def __init__(self, stages: Sequence[Stage],
                 secure: SecureStreamConfig = SecureStreamConfig(),
                 seed: int = 0,
                 directory: Optional[KeyDirectory] = None,
                 window_chunks: int = 8,
                 fusion: Optional[Dict[str, Any]] = None,
                 tracer=None,
                 monitor=None,
                 retry=None,
                 chaos=None):
        self.stages = list(stages)
        self.secure = secure
        self.seed = seed
        # span tracing is strictly off by default: NULL_TRACER's span()
        # returns a shared no-op context manager, so the instrumented
        # paths cost an attribute call when tracing is disabled
        self.tracer = tracer if tracer is not None else NULL_TRACER
        # live health monitoring follows the same contract: NULL_MONITOR
        # is enabled=False, so the per-window record is one attr check
        self.monitor = monitor if monitor is not None else NULL_MONITOR
        # fault tolerance is opt-in the same way: ``retry`` is a
        # repro.ft.retry.RetryPolicy, ``chaos`` a repro.ft.chaos.ChaosPlan
        # (fault injection for tests/benchmarks).  When both are None the
        # stage loop runs none of its fault-tolerance hooks.
        self.retry = retry
        self.chaos = chaos
        self._last_ft = None        # FTContext of the most recent run
        # dispatch/window accounting for the ingress and egress hops
        # (stage hops live in StageMetrics)
        self._ingress_windows_n = 0
        self._ingress_dispatches = 0
        self._egress_windows_n = 0
        self._egress_dispatches = 0
        # worker ids whose eviction has already been audit-logged (the
        # engine records each revoked worker's first skipped dispatch once)
        self._evicted_logged: set = set()
        # DSL-compiler provenance (stage merges); never read on the hot path
        self.fusion: Dict[str, Any] = dict(fusion or {})
        # chunks per worker per window: each worker's queue of a window is
        # ONE batched device dispatch. 1 = the per-chunk oracle engine.
        self.window_chunks = max(1, int(window_chunks))
        # The directory owns every session key; passing one in (scale_stage,
        # shared trust domain) carries sessions, epoch, and revocations over.
        self.directory = directory if directory is not None \
            else KeyDirectory(seed=seed)
        self._setup_attestation()
        # edge i connects stage i-1 -> i (+ source and sink); handles pull
        # the live epoch key from the directory on every seal/open.  Plain
        # mode never touches a key, so it skips the edge handshakes
        # entirely (workers are still measured and admitted).
        self.keys: List[Optional[EdgeHandle]] = [
            self.directory.handle(f"edge{i}")
            for i in range(len(self.stages) + 1)
        ] if secure.mode != "plain" else [None] * (len(self.stages) + 1)
        self.metrics: Dict[str, StageMetrics] = {
            s.name: StageMetrics() for s in self.stages}
        # each table op's table, on the device: sealed unless plain
        self.tables: Dict[str, Any] = {s.name: self._provision_table(s)
                                       for s in self.stages
                                       if s.op in TABLE_OPS}
        self.monitor.attach(self)

    # -------------------------------------------------------- attestation

    @staticmethod
    def worker_id(stage_name: str, w: int) -> str:
        """Directory identity of worker ``w`` of a stage — the id
        ``KeyDirectory.revoke`` takes to evict it live."""
        return f"{stage_name}/w{w}"

    def _setup_attestation(self) -> None:
        """Measure + enroll every endpoint and worker, verify quotes, and
        establish per-edge session keys via the attested handshake.

        Revoked worker ids stay quarantined (they are neither re-enrolled
        nor admitted — scale_stage cannot resurrect them); existing edge
        sessions are reused so a rescale does not re-key the stream.
        """
        d = self.directory
        d.enroll("io/source", IO_ENDPOINT, allow=True)
        d.enroll("io/sink", IO_ENDPOINT, allow=True)
        for st in self.stages:
            m = stage_measurement(st)
            d.policy.allow(m)
            d.enroll(f"stage/{st.name}", m)
            for w in range(max(1, st.workers)):
                wid = self.worker_id(st.name, w)
                if d.policy.is_revoked(wid):
                    continue                     # stays evicted
                d.enroll(wid, m)
                d.admit(wid)                     # raises unless quote verifies
        self._establish_edges(range(len(self.stages) + 1))

    def _establish_edges(self, edges: Iterable[int]) -> None:
        """Attested handshake for each listed edge that has no session
        yet (edge i joins stage i-1 to stage i; the source and sink are
        its ends).  Plain mode holds no keys, so it makes none."""
        if self.secure.mode == "plain":
            return
        d = self.directory
        ends = ["io/source"] + [f"stage/{s.name}" for s in self.stages] \
            + ["io/sink"]
        for e in edges:
            if not d.has_session(f"edge{e}"):
                d.establish(f"edge{e}", ends[e], ends[e + 1], stage_id=e)

    def _provision_table(self, st: Stage):
        """The stage's table on the device.  Sealed modes seal it once
        under the key of a session between the data's owner (the source
        endpoint) and the stage, so only the stage's kernel opens it."""
        if self.secure.mode == "plain":
            return jnp.asarray(stage_table(st))
        edge = f"table/{st.name}"
        if not self.directory.has_session(edge):
            self.directory.establish(edge, "io/source", f"stage/{st.name}")
        h = self.directory.handle(edge)
        return seal_table(h.key(), h.next_counter(), stage_table(st))

    def _live_workers(self, st: Stage, ft=None,
                      pool: Optional[List[EnclaveExecutor]] = None
                      ) -> List[int]:
        """Worker indices still dispatchable.

        Full quote admission (sign + verify) happened at build/rescale;
        the only bit that can flip mid-stream is revocation, so the
        per-window check is a set lookup, not a re-attestation.  Under
        fault tolerance (``ft``) workers declared dead are left out too,
        and when that leaves none, one spare is enrolled live into
        ``pool`` as a last resort.
        """
        live = []
        for w in range(max(1, st.workers)):
            wid = self.worker_id(st.name, w)
            if self.directory.policy.is_revoked(wid):
                if wid not in self._evicted_logged:
                    self._evicted_logged.add(wid)
                    self.directory.audit.record("eviction", worker=wid,
                                                stage=st.name)
                continue
            live.append(w)
        if not live:
            # deliberately NOT RevokedWorkerError: a stage name is not a
            # worker id, and the ft supervisor revokes e.worker_id
            raise KeyDirectoryError(
                f"every worker of stage {st.name!r} is revoked or "
                f"inadmissible — nothing can process the edge")
        if ft is not None:
            live = [w for w in live if not ft.is_dead(st.name, w)] \
                or [self._ft_enroll_spare(st, pool, ft)]
        return live

    # ------------------------------------------------------------------ run

    def _executor(self, i: int, st: Stage, w: int) -> EnclaveExecutor:
        """Worker ``w`` of stage i: an executor under the stage's mode (a
        non-sgx stage of an enclave pipeline runs encrypted), holding the
        stage's inbound and outbound edge keys."""
        mode = self.secure.mode
        st_mode = mode if st.sgx else ("plain" if mode == "plain"
                                       else "encrypted")
        ex = EnclaveExecutor(st_mode, self.keys[i], self.keys[i + 1])
        ex.tracer = self.tracer
        ex.track = f"{st.name}/w{w}"
        return ex

    def _worker_pool(self, i: int, st: Stage) -> List[EnclaveExecutor]:
        """One executor per worker of stage i (paper: W identical workers
        behind the stage's inbound router, all sharing the edge keys)."""
        return [self._executor(i, st, w) for w in range(max(1, st.workers))]

    def _stage_stream(self, upstream: Iterator[SealedWindow], st: Stage,
                      pool: List[EnclaveExecutor], window_chunks: int,
                      ft=None) -> Iterator[SealedWindow]:
        """Fan a window stream across the stage's workers, a round at a
        time.  A round pulls ``len(live) * window_chunks`` rows,
        round-robins them over the live workers by rolling global row
        index (paper's Push socket: row g goes to worker g mod W, as in
        the per-chunk engine) and runs each worker's share as ONE launch
        (a device gather splits the window; a lone worker takes it
        untouched).  MAC verdicts are deferred: the round syncs to host
        ONCE (`_sync_window`), failed rows are dropped (reactive on_error
        semantics) and counted, and survivors flow on in original stream
        order.  Revocation is re-checked after the pull, so a worker
        revoked mid-stream gets no rows from the next dispatch on.

        ``ft`` (a :class:`repro.ft.recovery.FTContext`) adds the
        fault-tolerance hooks to the same round: its sealed inputs are
        retained until its verdicts are folded in, each share goes
        through :meth:`_ft_dispatch_share` (retry, failover, backup), and
        rows whose verdicts failed or were lost are re-executed
        (:meth:`_ft_replay`), so the surviving stream is bit-identical
        to the fault-free run.
        """
        m = self.metrics[st.name]
        m.cover(len(pool))
        tr = self.tracer
        # instruments resolved ONCE per stage stream, not per window
        lat = _METRICS.histogram(f"pipeline.stage.{st.name}.window_seconds")
        depth = _METRICS.gauge(f"pipeline.stage.{st.name}.queue_rows")
        # tamper and dropped-verdict faults exist only on sealed edges
        faults = None if ft is None or self.secure.mode == "plain" \
            else ft.chaos
        phase = 0                    # rolling global row index for rr
        for rnd in itertools.count():
            live = self._live_workers(st, ft, pool)
            target = len(live) * window_chunks
            parts: List[SealedWindow] = []
            got = 0
            while got < target:
                win = next(upstream, None)
                if win is None:
                    break
                parts.append(win)
                got += len(win)
            if not parts:
                return
            if ft is not None:
                # retain the sealed inputs (still under their reserved
                # nonce blocks) until this round's verdicts are folded in
                ft.buffer.retain(st.name, rnd, parts)
            depth.set(got)
            # pulling the window may itself have revoked workers upstream
            live = self._live_workers(st, ft, pool)
            L = len(live)
            d0 = _DISPATCHES.value
            t0 = time.perf_counter()
            shares = []              # (part idx, worker, row idxs, out, ok)
            lost = set()             # shares whose verdicts never arrive
            wid = parts[0].window_id
            with tr.span("stage.dispatch", cat="dispatch", track=st.name,
                         rows=got, workers=L, window=wid):
                for pi, win in enumerate(parts):
                    B = len(win)
                    for k in range(L):
                        # rows j with (phase + j) % L == k
                        idxs = list(range((k - phase) % L, B, L))
                        if not idxs:
                            continue
                        sub = win if len(idxs) == B else win.select(idxs)
                        w = live[k]
                        if ft is None:
                            shares.append((pi, w, idxs)
                                          + self._exec_share(st, pool[w], sub))
                            continue
                        if faults is not None:
                            tf = faults.tamper_for(st.name, rnd, w)
                            if tf is not None:
                                # corrupt the dispatch COPY only — the
                                # retained rows stay clean for replay
                                sub = faults.apply_tamper(tf, sub)
                        w2, out, ok = self._ft_dispatch_share(
                            st, pool, ft, rnd, w, sub, ft.next_share_id())
                        if faults is not None and faults.drop_verdict_for(
                                st.name, rnd, w) is not None:
                            lost.add(len(shares))
                        shares.append((pi, w2, idxs, out, ok))
                    phase += B
            verdicts = _sync_window(
                [d[3].words for d in shares],
                [(d[4], len(d[3])) for d in shares],
                tracer=tr, track=st.name, window=wid)
            # honest window timing: t0 -> after block_until_ready, so
            # throughput_mbps reflects execution, not async enqueue
            dt = time.perf_counter() - t0
            m.seconds += dt
            lat.observe(dt)
            m.windows += 1
            final, marks = self._fold_verdicts(st, pool, parts, shares,
                                               verdicts, lost=lost)
            if ft is not None:
                self._ft_replay(st, pool, ft, rnd, parts, live, shares,
                                lost, final, marks)
            with tr.span("stage.merge", cat="pipeline", track=st.name,
                         windows=len(parts), window=wid):
                merged = list(self._merge_outputs(parts, final, marks))
            # the round's launches: shares, retries, replays, the merge
            disp = _DISPATCHES.value - d0
            m.dispatches += disp
            mon = self.monitor
            if mon.enabled:
                wrows: Dict[int, int] = {}
                for _, w, idxs, _, _ in final:
                    wrows[w] = wrows.get(w, 0) + len(idxs)
                mon.record_window(
                    st.name, rows=got,
                    ok_rows=sum(int(v.sum()) for v in marks),
                    bytes=sum(len(p) * int(p.n_words) * 4 for p in parts),
                    seconds=dt, queue_rows=got, worker_rows=wrows,
                    min_epoch=min(min(p.epochs) for p in parts),
                    dispatches=disp)
            if ft is not None:
                # the round's verdicts are folded in: release its rows
                ft.buffer.ack(st.name, rnd)
            yield from merged

    def _fold_verdicts(self, st: Stage, pool: List[EnclaveExecutor],
                       parts: List[SealedWindow], shares, verdicts,
                       lost=(), counted=()):
        """Fold one verdict sync (the rows of ``shares`` in order) into
        the stage's metrics: a verified row counts toward ``chunks``,
        ``per_worker`` and ``bytes``; a failed row is a ``mac_failure``
        of its worker, counted on its executor and audited.  A share in
        ``lost`` (its verdicts never reached the host) counts nothing; one
        in ``counted`` (a replay of rows whose failure was counted
        already) counts only its rows that now verify.  Returns the
        shares that go on to the merge and their per-row marks."""
        m = self.metrics[st.name]
        audit = self.directory.audit
        final, marks = [], []
        off = 0
        for si, share in enumerate(shares):
            pi, w, idxs, out, _ = share
            v = verdicts[off: off + len(idxs)]
            off += len(idxs)
            if si in lost:
                continue
            final.append(share)
            marks.append(v)
            for jj, alive in enumerate(v):
                if alive:
                    m.chunks += 1
                    m.per_worker[w] += 1
                    m.bytes += int(parts[pi].n_words) * 4
                elif si not in counted:
                    m.mac_failures += 1
                    pool[w].errors += 1
                    audit.record("mac_failure", stage=st.name,
                                 worker=self.worker_id(st.name, w),
                                 row=out.counters[jj],
                                 epoch=out.epochs[jj])
        return final, marks

    @staticmethod
    def _merge_outputs(parts, dispatches, marks):
        """Reassemble each input window's surviving rows in original
        stream order.  The all-survived single-dispatch case (steady
        state) passes the worker's output through untouched; otherwise
        the host orders the surviving rows and ONE launch of a cached
        program concatenates the shares and gathers them
        (:func:`repro.core.enclave.take_rows`)."""
        for pi in range(len(parts)):
            ds = [(d, mk) for d, mk in zip(dispatches, marks)
                  if d[0] == pi]
            if not ds:
                continue
            if len(ds) == 1 and len(ds[0][0][2]) == len(parts[pi]) \
                    and bool(ds[0][1].all()):
                yield ds[0][0][3]
                continue
            outs = [d[3] for d, _ in ds]
            entries = []             # (orig row, concat pos, counter, epoch)
            pos = 0
            for (_, _, idxs, out, _), mk in ds:
                entries.extend((j, pos + jj, out.counters[jj],
                                out.epochs[jj])
                               for jj, j in enumerate(idxs) if mk[jj])
                pos += len(idxs)
            if not entries:
                continue
            entries.sort()
            words, tags = take_rows(
                [o.words for o in outs],
                None if outs[0].tags is None else [o.tags for o in outs],
                [e[1] for e in entries])
            yield SealedWindow(
                words=words, tags=tags,
                counters=[e[2] for e in entries],
                epochs=[e[3] for e in entries],
                meta=outs[0].meta, n_words=outs[0].n_words,
                window_id=parts[pi].window_id)

    # ------------------------------------------------------ fault tolerance

    def _ft_fresh_coords(self, n: int):
        """Reserve a FRESH counter block for a re-execution.

        Every retry / failover / backup / replay re-seals its rows under
        counters reserved from the INGRESS edge at the current epoch —
        the one allocator whose blocks are globally collision-free across
        every edge (mid-pipeline edges never advance the session count),
        so a re-executed share can never re-spend a (key, nonce, counter)
        triple already used on any outbound key.  Plain mode has no
        nonces: returns None (re-execution keeps original coordinates).
        """
        h0 = self.keys[0]
        if h0 is None:
            return None
        base, epoch = h0.reserve_window(n)
        return (list(range(base, base + n)), epoch)

    def _exec_share(self, st: Stage, ex: EnclaveExecutor, sub: SealedWindow,
                    coords=None):
        """One batched open->op->seal of a share: the stage's op, reading
        its table if it has one.  ``coords`` = (counters, epoch) re-seals
        under fresh ingress-reserved coordinates (a re-execution); None
        keeps the rows' own (steady state)."""
        kw = {} if coords is None else {"reseal_as": coords}
        if st.fn is not None:
            return ex.run_window(st.fn, sub, **kw)
        if st.name in self.tables:
            kw["table"] = self.tables[st.name]
        return ex.run_static_window(st.op, st.const, sub, **kw)

    def _ft_pick_survivor(self, st: Stage, ft, exclude: int,
                          prefer=None) -> Optional[int]:
        """A live, not-dead worker other than ``exclude``: the backup
        dispatcher's hint ``prefer`` when usable.  Read from the CURRENT
        worker set, not the round's, so a spare enrolled earlier in the
        round absorbs later failovers instead of enrolling more."""
        cands = [x for x in range(max(1, st.workers))
                 if x != exclude and not ft.is_dead(st.name, x)
                 and not self.directory.policy.is_revoked(
                     self.worker_id(st.name, x))]
        if prefer in cands:
            return prefer
        return cands[0] if cands else None

    def enroll_spare(self, stage_name: str) -> int:
        """Enroll + admit one spare worker for a stage, live.

        The spare takes the same attested admission path as build time
        (measure -> enroll -> quote -> verify); edge sessions are
        stage-scoped (``stage/<name>`` endpoints), so the spare joins the
        existing attested channels — ``KeyDirectory.establish`` runs only
        if an edge somehow lost its session.  Returns the new worker
        index; raises :class:`repro.attest.quote.QuoteError` if admission
        fails (including a chaos-injected handshake failure).
        """
        idx, st = next((i, s) for i, s in enumerate(self.stages)
                       if s.name == stage_name)
        d = self.directory
        w = max(1, st.workers)
        wid = self.worker_id(st.name, w)
        meas = stage_measurement(st)
        d.policy.allow(meas)
        d.enroll(wid, meas)
        d.admit(wid)                 # raises unless the quote verifies
        self._establish_edges((idx, idx + 1))
        st.workers = w + 1
        return w

    def _ft_enroll_spare(self, st: Stage, pool: List[EnclaveExecutor],
                         ft) -> int:
        """Failover's last resort when a stage has no survivors: enroll a
        spare through the live admission path and extend the worker pool.
        A rejected handshake (chaos ``enroll_fail``) is retried once with
        the next spare id; raises if no spare could be admitted."""
        for _ in range(2):
            try:
                w = self.enroll_spare(st.name)
            except QuoteError:
                ft.enroll_failures.inc()
                continue
            pool.append(self._executor(self.stages.index(st), st, w))
            self.metrics[st.name].cover(len(pool))
            return w
        raise KeyDirectoryError(
            f"stage {st.name!r} has no worker left to take a share and "
            f"no spare could be admitted")

    def _ft_dispatch_share(self, st: Stage, pool: List[EnclaveExecutor],
                           ft, rnd: int, w: int,
                           sub: SealedWindow, share_id: int):
        """Dispatch one worker share under the retry policy.

        Consults the chaos plan for crash/stall faults at this
        (stage, round, worker) hook, applies bounded retry with
        exponential backoff on the same worker, fails the share over to
        a survivor (or a live-enrolled spare) when the worker is gone,
        and races an injected straggler against a speculative backup
        copy on another worker.  EVERY re-execution re-seals under fresh
        ingress-reserved counters (:meth:`_ft_fresh_coords`).  Returns
        (final worker, out window, deferred verdict vector); raises if
        the share cannot be placed anywhere.
        """
        audit = self.directory.audit
        policy = ft.policy
        chaos = ft.chaos
        det = ft.detector(st.name)
        bdisp = ft.dispatcher(st.name, max(1, st.workers))
        bdisp.track(share_id, w)
        attempts = 0
        fresh = False
        t_start = time.perf_counter()
        while True:
            spec = None if chaos is None \
                else chaos.crash_for(st.name, rnd, w)
            dead = ft.is_dead(st.name, w)
            out = ok = dt = None
            if not dead and (spec is None or spec.when == "after"):
                coords = self._ft_fresh_coords(len(sub)) if fresh else None
                t0 = time.perf_counter()
                out, ok = self._exec_share(st, pool[w], sub, coords)
                dt = time.perf_counter() - t0
            if spec is not None:
                # the fault fires exactly once: one worker_failed per
                # injected crash, regardless of how many shares it costs
                ft.worker_failures.inc()
                audit.record("worker_failed", stage=st.name,
                             worker=self.worker_id(st.name, w),
                             reason="crash", fatal=spec.fatal, round=rnd)
                if spec.fatal:
                    ft.mark_dead(st.name, w)
            if spec is not None or dead:
                # the share (or its result) is lost
                attempts += 1
                alive = not ft.is_dead(st.name, w)
                within = attempts < policy.max_attempts and (
                    policy.deadline_s is None
                    or time.perf_counter() - t_start < policy.deadline_s)
                if alive and within:
                    ft.retries.inc()
                    audit.record("share_retried", stage=st.name,
                                 worker=self.worker_id(st.name, w),
                                 attempt=attempts, round=rnd)
                    policy.sleep(policy.backoff(attempts))
                    fresh = True
                    continue
                if not policy.failover:
                    raise KeyDirectoryError(
                        f"share of stage {st.name!r} lost worker "
                        f"{self.worker_id(st.name, w)} and failover is "
                        f"disabled by the retry policy")
                w2 = self._ft_pick_survivor(st, ft, exclude=w)
                if w2 is None and policy.enroll_spare:
                    w2 = self._ft_enroll_spare(st, pool, ft)
                if w2 is None:
                    raise KeyDirectoryError(
                        f"share of stage {st.name!r} has no survivor to "
                        f"fail over to and the retry policy enrolls no "
                        f"spare")
                ft.failovers.inc()
                audit.record("share_failover", stage=st.name,
                             worker=self.worker_id(st.name, w),
                             to=self.worker_id(st.name, w2),
                             reason="crash", round=rnd)
                bdisp.track(share_id, w2)
                w = w2
                attempts = 0
                fresh = True
                continue
            # success path: race an injected stall against the cutoff
            stall = None if chaos is None \
                else chaos.stall_for(st.name, rnd, w)
            if stall is not None:
                dt += stall.seconds      # the straggler's observed time
                if dt > policy.timeout_for(det):
                    ft.worker_failures.inc()
                    audit.record("worker_failed", stage=st.name,
                                 worker=self.worker_id(st.name, w),
                                 reason="stall", round=rnd)
                    hint = bdisp.reissue(share_id)
                    w2 = self._ft_pick_survivor(st, ft, exclude=w,
                                                prefer=hint)
                    if w2 is not None:
                        # speculative backup wins; the original result
                        # arrives late and deduplicates
                        ft.backups.inc()
                        audit.record("share_failover", stage=st.name,
                                     worker=self.worker_id(st.name, w),
                                     to=self.worker_id(st.name, w2),
                                     reason="backup", round=rnd)
                        coords = self._ft_fresh_coords(len(sub))
                        t0 = time.perf_counter()
                        out2, ok2 = self._exec_share(st, pool[w2], sub,
                                                     coords)
                        det.observe(time.perf_counter() - t0)
                        bdisp.track(share_id, w2)
                        bdisp.complete(share_id)   # backup completes...
                        bdisp.complete(share_id)   # ...original is a dup
                        return w2, out2, ok2
                    # nobody to back up on: keep the slow result
            det.observe(dt)
            bdisp.complete(share_id)
            return w, out, ok

    def _ft_replay(self, st: Stage, pool: List[EnclaveExecutor], ft,
                   rnd: int, parts: List[SealedWindow], live: List[int],
                   shares, lost, final, marks) -> None:
        """Re-execute from the round's retained clean parts every share
        whose verdicts were dropped and, when the policy says so, every
        MAC-failed row, each re-sealed under fresh ingress coordinates;
        the replays share ONE extra verdict sync, and are appended to
        ``final``/``marks``.  A MAC-failed row that fails again is not
        counted twice; a verdict-dropped row is, on its first failure."""
        replays = []                 # (part idx, worker, row idxs, reason)
        kept = iter(marks)
        for si, (pi, w, idxs, _, _) in enumerate(shares):
            if si in lost:
                # the host never saw this share's verdicts: every row is
                # unverified -> replay the whole share
                replays.append((pi, w, list(idxs), "verdict_dropped"))
                continue
            v = next(kept)
            if ft.policy.replay_mac_failures and not v.all():
                replays.append((pi, w, [j for jj, j in enumerate(idxs)
                                        if not v[jj]], "mac_failure"))
        if not replays:
            return
        audit = self.directory.audit
        rd = []
        for pi, w, row_js, reason in replays:
            sub = parts[pi].select(row_js)
            coords = self._ft_fresh_coords(len(sub))
            wr = w if not ft.is_dead(st.name, w) else live[0]
            rd.append((pi, wr, row_js)
                      + self._exec_share(st, pool[wr], sub, coords))
            ft.replays.inc()
            audit.record("window_replayed", stage=st.name,
                         worker=self.worker_id(st.name, wr),
                         rows=len(row_js), reason=reason, round=rnd)
        rv = _sync_window([d[3].words for d in rd],
                          [(d[4], len(d[3])) for d in rd],
                          tracer=self.tracer, track=st.name,
                          window=parts[0].window_id)
        rfinal, rmarks = self._fold_verdicts(
            st, pool, parts, rd, rv,
            counted={ri for ri, r in enumerate(replays)
                     if r[3] == "mac_failure"})
        final.extend(rfinal)
        marks.extend(rmarks)

    def _ingress_stream(self, source: Iterable[jax.Array], mode: str,
                        rekey_every_n: Optional[int],
                        window: int) -> Iterator[SealedWindow]:
        """Seal source tensors window-at-a-time with a prefetch
        double-buffer: window N+1's (async) batched seal is dispatched
        BEFORE window N is handed downstream, so sealing overlaps
        downstream compute via JAX async dispatch.

        Each window reserves its nonce-counter blocks from the directory's
        managed per-edge counter (``EdgeHandle.reserve_window`` — the same
        discipline as ``secure_exchange``'s W^2 block), NOT a per-run
        enumerate: a second ``run()`` on the same pipeline (or a
        ``scale_stage`` continuation, which deliberately keeps the
        sessions) continues the count instead of resealing fresh plaintext
        under already-used (key, nonce) pairs.  ``rekey_every_n`` keeps
        its per-chunk cadence: a window is sealed as consecutive
        (epoch, shape)-uniform groups, each in one ``seal_many`` program,
        with ``advance_epoch`` firing between groups exactly where the
        per-chunk engine would have fired it — so rotation resets the
        managed counter, counters stay epoch-local, and chunks sealed just
        before a flip carry their epoch and drain under the old key.

        Windows are numbered 0, 1, 2, ... per call: every
        :class:`SealedWindow` of a window carries its ``window_id``, and
        the ``ingress.fill`` span (pulling its chunks from the source)
        and ``ingress.seal`` span carry it as ``window=``.
        """
        it = iter(source)
        n_plain = 0
        tr = self.tracer
        mon = self.monitor
        buffered = _METRICS.gauge("pipeline.ingress.buffered_rows")
        prev: Optional[List[SealedWindow]] = None
        for wid in itertools.count():
            t_fill = tr.now()
            xs = list(itertools.islice(it, window))
            if not xs:
                break
            tr.complete("ingress.fill", t_fill, cat="source",
                        track="ingress", rows=len(xs), window=wid)
            d0 = _DISPATCHES.value
            t0 = time.perf_counter()
            with tr.span("ingress.seal", cat="dispatch", track="ingress",
                         rows=len(xs), window=wid):
                if mode == "plain":
                    cur = [plain_window(range(n_plain + j,
                                              n_plain + j + len(sub)), sub)
                           for j, sub in _shape_runs(xs)]
                    n_plain += len(xs)
                else:
                    cur = self._seal_ingress_window(xs, rekey_every_n)
            for w in cur:
                w.window_id = wid
            buffered.set(len(xs))
            disp = _DISPATCHES.value - d0
            self._ingress_windows_n += 1
            self._ingress_dispatches += disp
            if mon.enabled:
                mon.record_window(
                    "ingress", rows=len(xs),
                    bytes=sum(len(w) * int(w.n_words) * 4 for w in cur),
                    seconds=time.perf_counter() - t0, queue_rows=len(xs),
                    dispatches=disp)
            if prev is not None:
                yield from prev
            prev = cur
        if prev is not None:
            yield from prev
        buffered.set(0)

    def _seal_ingress_window(self, xs: List[jax.Array],
                             rekey: Optional[int]) -> List[SealedWindow]:
        """One sealed ingress window: (epoch, shape)-grouped batched seals
        over directory-reserved counter blocks."""
        h0 = self.keys[0]
        wins: List[SealedWindow] = []
        i = 0
        while i < len(xs):
            sess = self.directory.session(h0.edge)
            if rekey and sess.chunks >= rekey:
                self.tracer.instant("rekey", cat="security",
                                    track="ingress",
                                    epoch=self.directory.advance_epoch())
                sess = self.directory.session(h0.edge)
            room = len(xs) - i if not rekey else max(1, rekey - sess.chunks)
            group = xs[i:i + room]
            for _, sub in _shape_runs(group):
                base, epoch = h0.reserve_window(len(sub))
                wins.append(seal_tensors_window(
                    h0, range(base, base + len(sub)), sub, epoch=epoch))
            i += len(group)
        return wins

    def _clamp_window_for_rekey(self, wc: int, rekey_every_n: int) -> int:
        """Largest safe window factor for this rekey cadence.

        Chunks open under the epoch they were ingressed in, so the
        directory's ``epoch_history`` must cover the deepest possible
        in-flight lag.  The windowed engine buffers up to one window per
        stage, two ingress windows (the prefetch double-buffer), and one
        egress window; ``window_chunks=1`` dispatches to the per-chunk
        oracle engine, whose in-flight depth is only one window per stage
        (+1 being ingressed) — exactly the seed engine's bound, so a
        combination is rejected up front only if the seed engine would
        also have rejected it; otherwise the window is silently clamped
        to the safe size (down to the oracle if need be).
        """
        S = sum(max(1, s.workers) for s in self.stages)
        w0 = max(1, self.stages[0].workers) if self.stages else 1
        wl = max(1, self.stages[-1].workers) if self.stages else 1
        hist = self.directory.epoch_history

        seed_in_flight = S + 1              # the per-chunk oracle's depth
        seed_lag = -(-seed_in_flight // rekey_every_n) + 1
        if seed_lag > hist:
            raise ValueError(
                f"rekey_every_n={rekey_every_n} can rotate "
                f"{seed_lag} epochs while up to {seed_in_flight} chunks "
                f"are in flight, but KeyDirectory(epoch_history="
                f"{hist}) would prune keys "
                f"still needed to drain — raise epoch_history or "
                f"rekey_every_n")

        def lag(w: int) -> int:
            in_flight = (S + 2 * w0 + wl) * w + 1
            return -(-in_flight // rekey_every_n) + 1

        while wc > 1 and lag(wc) > hist:
            wc -= 1
        return wc

    def run(self, source: Iterable[jax.Array],
            on_result: Optional[Callable] = None,
            rekey_every_n: Optional[int] = None,
            window_chunks: Optional[int] = None,
            tracer=None, monitor=None,
            retry=None, chaos=None) -> Any:
        """Stream source tensors through all stages; returns the terminal
        reduce value (if the last stage reduces) or the last chunk.

        ``rekey_every_n``: rotate every edge session key after each N
        source chunks (KeyDirectory.advance_epoch) — mid-stream, without
        draining the pipeline.  Chunks open under the epoch they were
        ingressed in (windows straddling a flip use per-row keys), and the
        window factor is clamped so the directory's ``epoch_history``
        always covers the deepest in-flight lag (rejected up front if even
        the per-chunk engine could drain past history).

        ``window_chunks`` overrides the pipeline's window factor for this
        run; 1 is the per-chunk oracle engine.

        ``tracer``: a :class:`repro.obs.trace.Tracer` for this run only —
        per-window spans (ingress seal, per-worker open->op->seal,
        verdict syncs, merges, reduce folds) land on it, exportable as
        Chrome-trace JSON.  Defaults to the pipeline's own tracer
        (:data:`NULL_TRACER` unless one was passed at construction), so
        tracing is strictly opt-in and no-op-cheap when off.

        ``monitor``: a :class:`repro.obs.monitor.PipelineMonitor` for
        this run only — per-window sliding health (and any attached
        watchdogs) update live while the run streams.  Defaults to the
        pipeline's own monitor (:data:`NULL_MONITOR` unless one was
        passed at construction); a monitored run reads only host-side
        metadata, so output stays bit-identical to an unmonitored run.

        ``retry``: a :class:`repro.ft.retry.RetryPolicy` enabling
        per-share retry/backoff, failover, and replay-based recovery for
        this run only (requires the window engine, ``window_chunks>=2``).

        ``chaos``: a :class:`repro.ft.chaos.ChaosPlan` — seeded fault
        injection consulted at every engine hook point; implies FT with
        the default policy if ``retry`` is not also given.  The plan's
        ``enroll_fail`` faults are wired through the directory's
        admission interceptor for the duration of the run.
        """
        prev_tracer = self.tracer
        prev_monitor = self.monitor
        prev_retry = self.retry
        prev_chaos = self.chaos
        prev_icpt = self.directory.admission_interceptor
        if tracer is not None:
            self.tracer = tracer
        if monitor is not None:
            self.monitor = monitor
            monitor.attach(self)
        if retry is not None:
            self.retry = retry
        if chaos is not None:
            self.chaos = chaos
        if self.chaos is not None:
            self.directory.admission_interceptor = self.chaos.enroll_failure
        try:
            with self.tracer.span("pipeline.run", mode=self.secure.mode,
                                  stages=len(self.stages)):
                return self._run_impl(source, on_result, rekey_every_n,
                                      window_chunks)
        finally:
            self.tracer = prev_tracer
            self.monitor = prev_monitor
            self.retry = prev_retry
            self.chaos = prev_chaos
            self.directory.admission_interceptor = prev_icpt

    def _run_impl(self, source: Iterable[jax.Array],
                  on_result: Optional[Callable],
                  rekey_every_n: Optional[int],
                  window_chunks: Optional[int]) -> Any:
        mode = self.secure.mode
        wc = self.window_chunks if window_chunks is None \
            else max(1, int(window_chunks))
        if rekey_every_n and mode != "plain":
            wc = self._clamp_window_for_rekey(wc, rekey_every_n)
        ft = None
        if self.retry is not None or self.chaos is not None:
            from repro.ft.recovery import FTContext
            from repro.ft.retry import RetryPolicy
            ft = FTContext(policy=self.retry if self.retry is not None
                           else RetryPolicy(), chaos=self.chaos)
        self._last_ft = ft
        if wc == 1:
            if ft is not None:
                raise ValueError(
                    "fault tolerance (retry/chaos) needs the "
                    "window-vectorized engine (window_chunks >= 2); the "
                    "window factor resolved to 1 — if rekey_every_n "
                    "clamped it, build the pipeline with a "
                    "KeyDirectory(epoch_history=...) large enough for "
                    "the window/rekey combination")
            whole = [s.name for s in self.stages
                     if s.op in TABLE_OPS or s.op in AGGREGATE_OPS]
            if whole:
                raise ValueError(
                    f"stages {whole} join a table or combine rows, which "
                    f"only the window engine runs (window_chunks >= 2)")
            # the per-chunk oracle engine: scalar seal/open per chunk
            # with a blocking verdict sync per chunk (the seed engine,
            # kept as the degenerate case / bitwise oracle)
            return self._run_chunked(source, on_result, rekey_every_n)
        w0 = max(1, self.stages[0].workers) if self.stages else 1
        stream: Iterator[SealedWindow] = self._ingress_stream(
            source, mode, rekey_every_n, w0 * wc)

        # compose map/filter stages up to the terminal reduce (if any)
        reduce_idx = next((i for i, s in enumerate(self.stages)
                           if s.reduce_fn is not None), None)
        end = len(self.stages) if reduce_idx is None else reduce_idx
        for i, st in enumerate(self.stages[:end]):
            stream = self._stage_stream(stream, st,
                                        self._worker_pool(i, st), wc, ft)
        sink_w = max(1, self.stages[end - 1].workers) if end else 1
        egress_rows = sink_w * wc

        if reduce_idx is not None:
            # terminal reduce: decrypt at the sink edge (trusted
            # subscriber), a window at a time, and fold in stream order;
            # the reduce swallows the stream.
            st = self.stages[reduce_idx]
            m = self.metrics[st.name]
            audit = self.directory.audit
            egress_lat = _METRICS.histogram("pipeline.egress.window_seconds")
            tr = self.tracer
            reduce_state: Any = None
            reduce_started = False
            for groups, verdicts, dt in self._egress_windows(
                    stream, mode, self.keys[reduce_idx], egress_rows):
                egress_lat.observe(dt)
                t0 = time.perf_counter()
                with tr.span("reduce.fold", cat="pipeline", track="sink",
                             rows=len(verdicts),
                             window=groups[0][0].window_id):
                    off = 0
                    for win, vals in groups:
                        ok = verdicts[off:off + len(win)]
                        host = _verified_rows_to_host(vals, ok)
                        k = 0                 # next verified row of `host`
                        for j in range(len(win)):
                            if not ok[j]:
                                m.mac_failures += 1
                                audit.record(
                                    "mac_failure", stage=st.name,
                                    worker="io/sink",
                                    row=win.counters[j],
                                    epoch=win.epochs[j])
                                continue
                            if not reduce_started:
                                reduce_state = st.reduce_init
                                reduce_started = True
                            x = host[k]
                            k += 1
                            with tr.span("reduce.fn", cat="pipeline",
                                         track="sink",
                                         window=win.window_id):
                                reduce_state = st.reduce_fn(reduce_state, x)
                            m.chunks += 1
                            m.bytes += int(win.n_words) * 4
                        off += len(win)
                m.seconds += dt + (time.perf_counter() - t0)
            return reduce_state if reduce_started else None

        final = None
        audit = self.directory.audit
        egress_lat = _METRICS.histogram("pipeline.egress.window_seconds")
        for groups, verdicts, dt in self._egress_windows(
                stream, mode, self.keys[len(self.stages)], egress_rows):
            egress_lat.observe(dt)
            off = 0
            for win, vals in groups:
                for j in range(len(win)):
                    final = vals[j]
                    if not verdicts[off + j]:
                        audit.record("mac_failure", stage="egress",
                                     worker="io/sink",
                                     row=win.counters[j],
                                     epoch=win.epochs[j])
                    elif on_result is not None:
                        on_result(vals[j])
                off += len(win)
        return final

    def _egress_windows(self, stream: Iterator[SealedWindow], mode: str,
                        key, window: int):
        """Open the terminal stream a window at a time (batched
        ``open_many`` per framing-uniform window, ONE deferred-verdict
        host sync per window).  Yields ([(window, opened tensor batch)],
        verdicts, seconds) — ``seconds`` spans dispatch through the
        blocking sync, so sink timing is honest."""
        parts: List[SealedWindow] = []
        got = 0
        for win in stream:
            parts.append(win)
            got += len(win)
            if got >= window:
                yield self._open_egress(parts, mode, key)
                parts, got = [], 0
        if parts:
            yield self._open_egress(parts, mode, key)

    def _open_egress(self, parts: List[SealedWindow], mode: str, key):
        d0 = _DISPATCHES.value
        t0 = time.perf_counter()
        groups = []
        specs = []
        wid = parts[0].window_id
        with self.tracer.span("egress.open", cat="dispatch", track="sink",
                              rows=sum(len(w) for w in parts), window=wid):
            for win in parts:
                vals, ok = egress_window(mode, key, win)
                groups.append((win, vals))
                specs.append((ok, len(win)))
        verdicts = _sync_window([v for _, v in groups], specs,
                                tracer=self.tracer, track="sink",
                                window=wid)
        dt = time.perf_counter() - t0
        disp = _DISPATCHES.value - d0
        self._egress_windows_n += 1
        self._egress_dispatches += disp
        mon = self.monitor
        if mon.enabled:
            rows = sum(len(w) for w in parts)
            mon.record_window(
                "egress", rows=rows, ok_rows=int(verdicts.sum()),
                bytes=sum(len(w) * int(w.n_words) * 4 for w in parts),
                seconds=dt, dispatches=disp)
        return groups, verdicts, dt

    # ------------------------------------- per-chunk oracle (window_chunks=1)

    def _ingress_stream_chunked(self, source: Iterable[jax.Array],
                                mode: str, rekey_every_n: Optional[int]
                                ) -> Iterator[SealedChunk]:
        """Scalar per-chunk ingress (the oracle engine): one eager seal
        and one managed counter per chunk, rekey checked per chunk."""
        n_plain = 0
        for x in source:
            if mode == "plain":
                yield ingress(mode, None, n_plain, x)
                n_plain += 1
                continue
            h0 = self.keys[0]
            if rekey_every_n and \
                    self.directory.session(h0.edge).chunks >= rekey_every_n:
                self.tracer.instant("rekey", cat="security",
                                    track="ingress",
                                    epoch=self.directory.advance_epoch())
            yield ingress(mode, h0, h0.next_counter(), x)

    def _stage_stream_chunked(self, upstream: Iterator[SealedChunk],
                              st: Stage, pool: List[EnclaveExecutor]
                              ) -> Iterator[SealedChunk]:
        """The per-chunk oracle: scalar open->op->seal per chunk with a
        blocking ``bool(ok)`` host sync per chunk — round-robin dispatch
        over the pool, fair-queue merge of the worker sub-streams."""
        m = self.metrics[st.name]
        m.cover(len(pool))
        tr = self.tracer
        mon = self.monitor
        audit = self.directory.audit
        lat = _METRICS.histogram(f"pipeline.stage.{st.name}.window_seconds")
        while True:
            live = self._live_workers(st)
            window = list(itertools.islice(upstream, len(live)))
            if not window:
                return
            worker_outs: List[List[SealedChunk]] = []
            for k, queue in enumerate(R.round_robin(window, len(live))):
                w = live[k]
                outs: List[SealedChunk] = []
                for chunk in queue:
                    d0 = _DISPATCHES.value
                    t0 = time.perf_counter()
                    with tr.span("stage.chunk", cat="dispatch",
                                 track=f"{st.name}/w{w}",
                                 row=chunk.counter):
                        if st.fn is not None:
                            out = pool[w].run(st.fn, chunk)
                        else:
                            out = pool[w].run_static(st.op, st.const, chunk)
                    if pool[w].mode != "plain":
                        _HOST_SYNCS.inc()      # the scalar bool(ok) sync
                    dt = time.perf_counter() - t0
                    m.seconds += dt
                    lat.observe(dt)            # the oracle's window IS a chunk
                    m.windows += 1
                    disp = _DISPATCHES.value - d0
                    m.dispatches += disp
                    if mon.enabled:
                        mon.record_window(
                            st.name, rows=1,
                            ok_rows=0 if out is None else 1,
                            bytes=0 if out is None
                            else int(chunk.n_words) * 4,
                            seconds=dt, queue_rows=len(window),
                            worker_rows={w: 1}, min_epoch=chunk.epoch,
                            dispatches=disp)
                    if out is None:
                        m.mac_failures += 1
                        audit.record("mac_failure", stage=st.name,
                                     worker=self.worker_id(st.name, w),
                                     row=chunk.counter, epoch=chunk.epoch)
                        continue
                    m.chunks += 1
                    m.per_worker[w] += 1
                    m.bytes += int(chunk.n_words) * 4
                    outs.append(out)
                worker_outs.append(outs)
            yield from R.fair_queue(worker_outs)

    def _run_chunked(self, source: Iterable[jax.Array],
                     on_result: Optional[Callable],
                     rekey_every_n: Optional[int]) -> Any:
        """The original streaming engine, chunk by chunk (the
        ``window_chunks=1`` degenerate case)."""
        mode = self.secure.mode
        audit = self.directory.audit
        stream: Iterator[SealedChunk] = self._ingress_stream_chunked(
            source, mode, rekey_every_n)
        reduce_idx = next((i for i, s in enumerate(self.stages)
                           if s.reduce_fn is not None), None)
        end = len(self.stages) if reduce_idx is None else reduce_idx
        for i in range(end):
            st = self.stages[i]
            stream = self._stage_stream_chunked(stream, st,
                                                self._worker_pool(i, st))

        if reduce_idx is not None:
            st = self.stages[reduce_idx]
            m = self.metrics[st.name]
            reduce_state: Any = None
            reduce_started = False
            for chunk in stream:
                t0 = time.perf_counter()
                val, ok = egress(mode, self.keys[reduce_idx], chunk)
                if mode != "plain":
                    _HOST_SYNCS.inc()
                if not bool(ok):
                    m.mac_failures += 1
                    audit.record("mac_failure", stage=st.name,
                                 worker="io/sink", row=chunk.counter,
                                 epoch=chunk.epoch)
                    continue
                if not reduce_started:
                    reduce_state = st.reduce_init
                    reduce_started = True
                reduce_state = st.reduce_fn(reduce_state, val)
                m.chunks += 1
                m.bytes += int(chunk.n_words) * 4
                m.seconds += time.perf_counter() - t0
            return reduce_state if reduce_started else None

        final = None
        for chunk in stream:
            result, ok = egress(mode, self.keys[len(self.stages)], chunk)
            if mode != "plain":
                _HOST_SYNCS.inc()
            final = result
            if not bool(ok):
                audit.record("mac_failure", stage="egress",
                             worker="io/sink", row=chunk.counter,
                             epoch=chunk.epoch)
            elif on_result is not None:
                on_result(result)
        return final

    # ------------------------------------------------------------- elastic

    def scale_stage(self, name: str, workers: int) -> "Pipeline":
        """Elastic scaling: change a stage's worker count (paper §5.5).

        The KeyDirectory (sessions, epoch, revocations), the seed, AND the
        accumulated StageMetrics carry forward, so throughput/error
        reports stay continuous across rescale events and the stream is
        not re-keyed (the paper's live-reconfiguration experiment reports
        one unbroken trajectory).  New workers are admitted only if their
        quote verifies against the stage's measurement; revoked ids stay
        quarantined — scale-up cannot resurrect an evicted worker.
        """
        stages = [
            Stage(**{**s.__dict__, "workers": workers}) if s.name == name
            else s for s in self.stages
        ]
        p = Pipeline(stages, self.secure, seed=self.seed,
                     directory=self.directory,
                     window_chunks=self.window_chunks,
                     fusion=self.fusion,
                     tracer=None if self.tracer is NULL_TRACER
                     else self.tracer,
                     monitor=None if self.monitor is NULL_MONITOR
                     else self.monitor)
        p._evicted_logged = self._evicted_logged
        # ingress/egress hop accounting continues across the rescale,
        # like the per-stage metrics below
        p._ingress_windows_n = self._ingress_windows_n
        p._ingress_dispatches = self._ingress_dispatches
        p._egress_windows_n = self._egress_windows_n
        p._egress_dispatches = self._egress_dispatches
        for sname, m in self.metrics.items():
            p.metrics[sname] = dataclasses.replace(
                m, per_worker=list(m.per_worker))
            if sname == name:
                p.metrics[sname].cover(workers)
        return p

    def report(self) -> Dict[str, Dict[str, Any]]:
        """Per-stage metrics dict (chunks, bytes, seconds, MB/s, MAC
        failures, per-worker counts).  Stages the DSL compiler merged
        carry a ``fused_from`` list, and a top-level ``"fusion"`` entry
        logs every fusion decision (taken or declined) — both absent for
        hand-built pipelines, whose report shape is unchanged."""
        fused_from = self.fusion.get("fused_from", {})
        out: Dict[str, Dict[str, Any]] = {
            name: {"chunks": m.chunks, "bytes": m.bytes,
                   "seconds": round(m.seconds, 4),
                   # None = nothing measured yet (distinct from a true 0.0)
                   "throughput_mbps": None if m.throughput_mbps is None
                   else round(m.throughput_mbps, 2),
                   "mac_failures": m.mac_failures,
                   "mac_failure_rate": None if m.mac_failure_rate is None
                   else round(m.mac_failure_rate, 4),
                   "per_worker": list(m.per_worker),
                   "windows": m.windows,
                   "dispatches": m.dispatches,
                   "dispatches_per_window":
                   None if m.dispatches_per_window is None
                   else round(m.dispatches_per_window, 4),
                   **({"fused_from": list(fused_from[name])}
                      if name in fused_from else {})}
            for name, m in self.metrics.items()
        }
        if self.fusion.get("decisions"):
            out["fusion"] = {"decisions": list(self.fusion["decisions"])}
        out["audit"] = self.directory.audit.summary()
        out["dispatch"] = {
            "total": self._ingress_dispatches + self._egress_dispatches
            + sum(m.dispatches for m in self.metrics.values()),
            "ingress": {"windows": self._ingress_windows_n,
                        "dispatches": self._ingress_dispatches},
            "egress": {"windows": self._egress_windows_n,
                       "dispatches": self._egress_dispatches},
        }
        return out
