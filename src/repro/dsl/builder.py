"""Fluent pipeline builder: the paper's "few lines of code" claim, typed.

The paper's usability pitch (Listing 1 + Listing 2) is that a secure
pipeline is *declared*, not assembled: named stages with worker counts and
``constraint:type==sgx``, composed with RxLua ``map/filter/reduce``.  This
module is that surface for the window-vectorized engine::

    from repro.dsl import stream

    out = (stream(source)
           .map("identity", name="sgx_mapper", workers=4, sgx=True)
           .filter("delay_filter_u32", const=15, name="sgx_filter",
                   workers=4, sgx=True)
           .reduce("carrier_delay_stats", name="reducer")
           .run(mode="enclave", rekey_every_n=1024))

A stage that combines rows is ``.aggregate(op, ...)``, with the same
keywords as ``.map``: the Yahoo Streaming Benchmark's campaign query is
``.filter("ysb_view_filter")`` -> ``.map("ysb_campaign_join",
const=<table seed>)`` -> ``.aggregate("ysb_window_count", const=10000)``
-> ``.reduce("ysb_campaign_window_counts")``.

Builders are immutable: every combinator returns a new
:class:`StreamBuilder` (exactly like :class:`repro.core.observable
.Observable`, whose :class:`~repro.core.observable.Op` nodes this module
reuses — the DSL and the Observable layer share one op-chain vocabulary).
``.run``/``.build`` hand the chain to :mod:`repro.dsl.compile`, which
validates eagerly, fuses adjacent fusable stages (fewer seal/open hops),
and emits a plain :class:`repro.core.pipeline.Pipeline` — the DSL adds
**zero** runtime machinery on the streaming hot path, which is why
``pipeline.dsl`` benches at parity with the hand-built engine.

``.as_observable()`` lowers the same chain onto a plaintext
:class:`~repro.core.observable.Observable` — a pure-jnp oracle with
identical per-chunk semantics, used by tests and docs.
"""
from __future__ import annotations

from typing import Any, Callable, Iterable, Optional, Tuple, Union

import jax.numpy as jnp

from repro.core.observable import Observable, Op, describe_ops
from repro.kernels.enclave_map.ops import TABLE_OPS


class StreamBuilder:
    """An immutable, lazily-compiled chain of named secure stages."""

    def __init__(self, source: Optional[Iterable] = None,
                 ops: Tuple[Op, ...] = (),
                 settings: Optional[dict] = None):
        self._source = source
        self._ops = tuple(ops)
        self._settings = dict(settings or {})
        #: the last Pipeline compiled by .build()/.run() (report access)
        self.pipeline = None

    # ------------------------------------------------------------- plumbing

    def _with(self, op: Op) -> "StreamBuilder":
        return StreamBuilder(self._source, self._ops + (op,), self._settings)

    def _with_settings(self, **kw) -> "StreamBuilder":
        return StreamBuilder(self._source, self._ops,
                             {**self._settings, **kw})

    @staticmethod
    def _stage_meta(kind: str, op, name: Optional[str], const: float,
                    workers: int, sgx: bool, n: int) -> dict:
        opname = op if isinstance(op, str) else getattr(op, "__name__", kind)
        return {"name": name or f"{kind}{n}_{opname}",
                "op": op if isinstance(op, str) else None,
                "const": const, "workers": workers, "sgx": sgx,
                "pinned": False}

    # ---------------------------------------------------------- combinators

    def map(self, op: Union[str, Callable], *, name: Optional[str] = None,
            const: float = 0.0, workers: int = 1,
            sgx: bool = True) -> "StreamBuilder":
        """Add a map stage.  ``op`` is a registered static operator name
        (runs fused in-enclave under ``mode="enclave"``) or a Python
        callable (attestable only outside the enclave — rejected eagerly
        by the compiler when ``sgx=True`` under enclave mode).  ``sgx``
        is the paper's ``constraint:type==sgx`` placement flag."""
        fn = None if isinstance(op, str) else op
        meta = self._stage_meta("map", op, name, const, workers, sgx,
                                len(self._ops))
        return self._with(Op("map", fn, meta=meta))

    def filter(self, op: Union[str, Callable], *,
               name: Optional[str] = None, const: float = 0.0,
               workers: int = 1, sgx: bool = True) -> "StreamBuilder":
        """Add a filter stage.  Filters are *dense* on this engine (the
        operator rewrites records in place — e.g. ``delay_filter_u32``
        zeroes non-delayed records); accelerator dataflow cannot drop
        rows dynamically, matching :meth:`Observable.filter` semantics."""
        fn = None if isinstance(op, str) else op
        meta = self._stage_meta("filter", op, name, const, workers, sgx,
                                len(self._ops))
        return self._with(Op("filter", fn, meta=meta))

    def aggregate(self, op: str, *, name: Optional[str] = None,
                  const: float = 0.0, workers: int = 1,
                  sgx: bool = True) -> "StreamBuilder":
        """Add an aggregate stage: a registered op that combines rows
        (``repro.kernels.enclave_map.AGGREGATE_OPS``, such as YSB's
        ``ysb_window_count``).  Each worker share of a window becomes
        partial-aggregate rows, sealed like any rows; the terminal reduce
        adds them up.  Same keywords as :meth:`map`."""
        meta = self._stage_meta("aggregate", op, name, const, workers, sgx,
                                len(self._ops))
        return self._with(Op("aggregate", None, meta=meta))

    def reduce(self, fn: Union[str, Callable], init: Any = None, *,
               name: str = "reduce") -> "StreamBuilder":
        """Terminal reduce: folds decrypted chunks at the trusted
        subscriber (sink edge).  ``fn`` is a callable ``(acc, chunk) ->
        acc`` with ``init``, or the name of a registered reducer
        (:func:`repro.dsl.reducers.register_reducer`) so TOML specs can
        reference it declaratively."""
        meta = {"name": name, "reducer": fn if isinstance(fn, str) else None,
                "workers": 1, "sgx": True, "op": None, "const": 0.0,
                "pinned": False}
        f = None if isinstance(fn, str) else fn
        return self._with(Op("reduce", f, init=init, meta=meta))

    # ------------------------------------------------------------- settings

    def secure(self, mode: str) -> "StreamBuilder":
        """Set the wire/compute security mode (paper Fig. 6):
        ``plain`` | ``encrypted`` | ``enclave``."""
        return self._with_settings(mode=mode)

    def scale(self, stage: str, workers: int) -> "StreamBuilder":
        """Set a named stage's worker count (paper §5.5 elasticity,
        declared pre-build; a *live* rescale of a running pipeline is
        ``Pipeline.scale_stage``).  Scaling pins the stage: the fusion
        planner will not absorb an explicitly scaled stage."""
        found = False
        ops = []
        for o in self._ops:
            if o.meta.get("name") == stage:
                found = True
                meta = {**o.meta, "workers": int(workers), "pinned": True}
                ops.append(Op(o.kind, o.fn, o.init, meta))
            else:
                ops.append(o)
        if not found:
            known = [o.meta.get("name") for o in self._ops]
            raise KeyError(f"scale: no stage named {stage!r} "
                           f"(stages: {known})")
        return StreamBuilder(self._source, tuple(ops), self._settings)

    def window(self, window_chunks: int) -> "StreamBuilder":
        """Set the engine's window factor (chunks per worker per batched
        dispatch; 1 = the per-chunk oracle engine)."""
        return self._with_settings(window_chunks=int(window_chunks))

    def seed(self, seed: int) -> "StreamBuilder":
        """Set the KeyDirectory seed used when no directory is passed."""
        return self._with_settings(seed=int(seed))

    def directory(self, directory) -> "StreamBuilder":
        """Use an existing :class:`repro.attest.KeyDirectory` (shared
        trust domain: sessions, epoch, and revocations carry over)."""
        return self._with_settings(directory=directory)

    def fuse(self, enabled: bool = True) -> "StreamBuilder":
        """Enable/disable stage fusion (default on; fusion is only
        applied where it is bit-exact, see :mod:`repro.dsl.compile`)."""
        return self._with_settings(fuse=bool(enabled))

    def trace(self, tracer=None) -> "StreamBuilder":
        """Attach a :class:`repro.obs.trace.Tracer` to the compiled
        pipeline (a fresh one when ``tracer`` is None).  Per-window spans
        — ingress seals, per-worker open->op->seal, verdict syncs, merges,
        reduce folds — land on it; export with
        ``builder.tracer.export_chrome("trace.json")`` after a run.
        Tracing stays strictly off (zero-cost no-ops) unless this is
        called or a tracer is passed to ``Pipeline.run``."""
        from repro.obs.trace import Tracer
        return self._with_settings(
            tracer=tracer if tracer is not None else Tracer())

    @property
    def tracer(self):
        """The tracer attached via :meth:`trace` (None when untraced)."""
        return self._settings.get("tracer")

    def monitor(self, monitor=None) -> "StreamBuilder":
        """Attach a :class:`repro.obs.monitor.PipelineMonitor` (a fresh
        one when ``monitor`` is None) to the compiled pipeline.  Sliding
        per-stage health (windows/s, MB/s, p50/p95 latency, queue depth,
        worker skew, mac-failure rate, epoch lag) updates once per
        window while :meth:`run` streams; read it live via
        ``builder.health_monitor.snapshot()`` or serve it with
        ``repro.obs.export.serve_metrics``.  Monitoring stays strictly
        off (zero-cost no-ops) unless this is called or a monitor is
        passed to ``Pipeline.run``."""
        from repro.obs.monitor import PipelineMonitor
        return self._with_settings(
            monitor=monitor if monitor is not None else PipelineMonitor())

    @property
    def health_monitor(self):
        """The monitor attached via :meth:`monitor` (None when
        unmonitored)."""
        return self._settings.get("monitor")

    def retry(self, policy=None) -> "StreamBuilder":
        """Attach a :class:`repro.ft.retry.RetryPolicy` (the default
        policy when ``policy`` is None): per-share retry with bounded
        exponential backoff, failover to survivors (or a live-enrolled
        spare), speculative backup dispatch against stragglers, and
        replay of MAC-failed rows from the retained ingress window —
        every re-execution re-sealed under fresh directory-reserved
        counters, so recovery never reuses a (key, nonce, counter)
        triple and output stays bit-identical.  Requires the window
        engine (``window_chunks >= 2``)."""
        from repro.ft.retry import RetryPolicy
        return self._with_settings(
            retry=policy if policy is not None else RetryPolicy())

    @property
    def retry_policy(self):
        """The policy attached via :meth:`retry` (None when FT is off)."""
        return self._settings.get("retry")

    def chaos(self, plan) -> "StreamBuilder":
        """Attach a :class:`repro.ft.chaos.ChaosPlan`: seeded fault
        injection (worker crashes, stalls, tampered shares, dropped
        verdict syncs, enrollment failures) consulted at every engine
        hook point.  Implies :meth:`retry` with the default policy if no
        policy was attached.  Faults are deterministic per plan — the
        chaos harness's replayability contract."""
        return self._with_settings(chaos=plan)

    @property
    def chaos_plan(self):
        """The plan attached via :meth:`chaos` (None when chaos is off)."""
        return self._settings.get("chaos")

    # ------------------------------------------------------------ lowering

    def build(self, mode: Optional[str] = None, *,
              rekey_every_n: Optional[int] = None):
        """Validate + fuse + compile the chain to a
        :class:`repro.core.pipeline.Pipeline` (stored as
        ``self.pipeline``).  ``rekey_every_n`` here is only used for the
        eager rekey-vs-epoch-history check; pass it to
        :meth:`Pipeline.run` (or :meth:`run`) to actually rotate."""
        from repro.dsl.compile import compile_pipeline
        s = self._settings
        if rekey_every_n is None:
            rekey_every_n = s.get("rekey_every_n")   # spec-declared cadence
        self.pipeline = compile_pipeline(
            self._ops,
            mode=mode or s.get("mode", "enclave"),
            seed=s.get("seed", 0),
            directory=s.get("directory"),
            window_chunks=s.get("window_chunks", 8),
            fuse=s.get("fuse", True),
            rekey_every_n=rekey_every_n,
            tracer=s.get("tracer"),
            monitor=s.get("monitor"),
            retry=s.get("retry"),
            chaos=s.get("chaos"))
        return self.pipeline

    def run(self, source: Optional[Iterable] = None, *,
            mode: Optional[str] = None, on_result: Optional[Callable] = None,
            rekey_every_n: Optional[int] = None,
            window_chunks: Optional[int] = None) -> Any:
        """Compile and stream: returns the terminal reduce value (or the
        last chunk for reduce-less chains).  The source may come from
        ``stream(source)`` or be passed here; chunks are coerced with
        ``jnp.asarray`` so plain numpy iterators work."""
        src = source if source is not None else self._source
        if src is None:
            raise ValueError("no source: pass one to stream(...) or run(...)")
        if rekey_every_n is None:
            rekey_every_n = self._settings.get("rekey_every_n")
        p = self.build(mode, rekey_every_n=rekey_every_n)
        return p.run((jnp.asarray(c) for c in src), on_result=on_result,
                     rekey_every_n=rekey_every_n,
                     window_chunks=window_chunks)

    def report(self) -> dict:
        """Per-stage metrics of the last compiled pipeline — including
        the ``fused_from`` / ``fusion`` entries recording what the
        compiler merged (see ``Pipeline.report``)."""
        if self.pipeline is None:
            raise RuntimeError("nothing compiled yet — call run()/build()")
        return self.pipeline.report()

    # --------------------------------------------------------- introspection

    def describe(self) -> str:
        """One-line chain summary, same format as
        :meth:`Observable.describe` (shared op vocabulary)."""
        return describe_ops(self._ops)

    @property
    def ops(self) -> Tuple[Op, ...]:
        return self._ops

    def as_observable(self, source: Optional[Iterable] = None) -> Observable:
        """Lower the chain onto a plaintext :class:`Observable`: each
        static stage becomes a pure-jnp map with the same record
        semantics as the secure engine (dense filters included), custom
        fns pass through, the terminal reduce folds in stream order.
        Bit-identical to ``mode="plain"`` — the DSL's cleartext oracle —
        except for an aggregate stage, which combines the rows of each
        chunk here and of each worker share there: its partial rows
        differ, and what the terminal reduce adds up from them agrees.
        """
        from repro.core.enclave import _apply_static_f32
        from repro.dsl.reducers import resolve_reducer
        src = source if source is not None else self._source
        if src is None:
            raise ValueError("as_observable needs a source")
        obs = Observable.from_chunks(src)
        for o in self._ops:
            if o.kind in ("map", "filter", "aggregate"):
                if o.fn is not None:
                    obs = obs.map(o.fn)
                else:
                    op, const = o.meta["op"], o.meta["const"]
                    table = TABLE_OPS[op][1](const) if op in TABLE_OPS \
                        else None
                    obs = obs.map(
                        lambda c, _op=op, _k=const, _t=table:
                        _apply_static_f32(_op, _k, c, _t))
            elif o.kind == "reduce":
                fn, init = (o.fn, o.init) if o.fn is not None \
                    else resolve_reducer(o.meta["reducer"])
                obs = obs.reduce(lambda acc, c, m, _f=fn: _f(acc, c),
                                 init=init)
        return obs


def stream(source: Optional[Iterable] = None) -> StreamBuilder:
    """Entry point of the fluent DSL: ``stream(chunks).map(...).run()``.
    ``source`` is any iterable of same-shape tensors/arrays (may also be
    supplied later to :meth:`StreamBuilder.run`)."""
    return StreamBuilder(source)
