"""DSL compiler: validate eagerly, fuse where bit-exact, emit a Pipeline.

The builder (:mod:`repro.dsl.builder`) and the spec loader
(:mod:`repro.dsl.spec`) both land here.  Three jobs:

**Eager validation** — everything the engine would only discover
mid-stream is rejected at compile time, before any data is sealed:
unknown static operator names (with the registry listed), Python
closures placed ``sgx=True`` under ``mode="enclave"`` (the paper's
no-dynamic-linking rule — the engine raises this lazily per window; the
DSL raises it before the first chunk), duplicate stage names, non-positive
worker counts, unresolvable named reducers, and ``rekey_every_n``
cadences that even the per-chunk oracle engine could not drain within the
directory's ``epoch_history`` (the same up-front rejection
``Pipeline.run`` performs, surfaced at build).

**Fusion** — adjacent ``map``/``filter`` stages are merged into a single
stage when the op registry guarantees the composition is *bit-exact*.
Today that means identity absorption: ``identity`` is an exact u32
passthrough in every mode, so ``identity ∘ f == f`` to the bit and the
absorbed stage's seal/open hop disappears.  Float compositions
(``scale_f32 ∘ scale_f32`` etc.) are deliberately NOT fused —
``(x·a)·b != x·(a·b)`` under f32 rounding, and the DSL's contract is
bit-identity with the unfused hand-built pipeline.  Every decision,
taken or declined, is recorded and surfaces in ``Pipeline.report()``
(``fusion`` entry + per-stage ``fused_from``).  Stages pinned by
``.scale()`` or carrying an explicit worker pool (``workers > 1``) are
never absorbed — fusion must not discard declared fan-out.

**Emission** — the output is a plain :class:`repro.core.pipeline
.Pipeline`; the DSL contributes nothing to the streaming hot path.
"""
from __future__ import annotations

import copy
from typing import Any, Dict, List, Optional, Sequence, Tuple

from repro.configs.base import SecureStreamConfig
from repro.core.observable import Op
from repro.core.pipeline import Pipeline, Stage
from repro.kernels.enclave_map.ops import AGGREGATE_OPS, OPS, TABLE_OPS

MODES = ("plain", "encrypted", "enclave")


class DSLValidationError(ValueError):
    """A pipeline description rejected at compile time (build, not run)."""


# ------------------------------------------------------------------ validate


def _stage_dicts(ops: Sequence[Op]) -> List[dict]:
    """Normalize builder Ops into flat stage descriptors."""
    out = []
    for o in ops:
        d = dict(o.meta)
        d["kind"] = o.kind
        d["fn"] = o.fn
        d["init"] = o.init
        out.append(d)
    return out


def validate(ops: Sequence[Op], mode: str) -> List[dict]:
    """Eager structural validation; returns normalized stage dicts."""
    if mode not in MODES:
        raise DSLValidationError(
            f"unknown mode {mode!r}; expected one of {MODES}")
    stages = _stage_dicts(ops)
    if not stages:
        raise DSLValidationError("empty pipeline: add map/filter/reduce "
                                 "stages before build()/run()")
    reduces = [i for i, s in enumerate(stages) if s["kind"] == "reduce"]
    if len(reduces) > 1:
        raise DSLValidationError("at most one reduce stage (it is terminal)")
    if reduces and reduces[0] != len(stages) - 1:
        raise DSLValidationError(
            f"reduce must be the terminal stage, found it at position "
            f"{reduces[0]} of {len(stages)}")
    seen = set()
    for s in stages:
        name = s["name"]
        if name in seen:
            raise DSLValidationError(
                f"duplicate stage name {name!r} — stage names are worker-id "
                f"prefixes and must be unique")
        seen.add(name)
        if int(s["workers"]) < 1:
            raise DSLValidationError(
                f"stage {name!r}: workers must be >= 1, got {s['workers']}")
        if s["kind"] == "reduce":
            if s["fn"] is None:
                from repro.dsl.reducers import resolve_reducer
                resolve_reducer(s["reducer"])       # raises with known names
            continue
        if s["fn"] is None:
            _check_static_op(s)
        elif mode == "enclave" and s["sgx"]:
            raise DSLValidationError(
                f"stage {name!r}: a Python closure cannot run sgx=True "
                f"under mode='enclave' — only registered static operators "
                f"are attestable (the paper's no-dynamic-linking rule). "
                f"Use a registry op, or mark the stage sgx=False to run "
                f"it on the encrypted (non-enclave) path.")
    return stages


def _check_static_op(s: dict) -> None:
    """A registered op, under the verb that fits it: an aggregate op
    only under ``.aggregate``, and nothing else there; a table op's
    ``const`` is its table seed, a whole number; an aggregate op's is
    the window in ms, below 2^16 (the kernel divides 64-bit times by it
    in 16-bit limbs)."""
    name, op, kind = s["name"], s["op"], s["kind"]
    if op not in OPS and op not in TABLE_OPS:
        raise DSLValidationError(
            f"stage {name!r}: unknown static op {op!r}; registered ops: "
            f"{sorted(set(OPS) | set(TABLE_OPS))}")
    if (kind == "aggregate") != (op in AGGREGATE_OPS):
        raise DSLValidationError(
            f"stage {name!r}: {op!r} under .{kind}(): ops that combine "
            f"rows ({sorted(AGGREGATE_OPS)}) go under .aggregate(), and "
            f"only they do")
    const = float(s["const"])
    if op in TABLE_OPS and not (const.is_integer()
                                and 0 <= const < 2 ** 32):
        raise DSLValidationError(
            f"stage {name!r}: {op!r} takes a table seed, a whole number "
            f"in [0, 2^32), got {s['const']!r}")
    if op in AGGREGATE_OPS and not (const.is_integer()
                                    and 0 < const < 2 ** 16):
        raise DSLValidationError(
            f"stage {name!r}: {op!r} takes a window in ms, a whole "
            f"number in [1, 2^16), got {s['const']!r}")


# ------------------------------------------------------------------- fusion


def _is_identity(s: dict) -> bool:
    return s["kind"] in ("map", "filter") and s["fn"] is None \
        and s["op"] == "identity"


def _absorbable(s: dict) -> bool:
    # an explicitly requested worker pool is part of the declared
    # topology — absorbing the stage would silently discard its fan-out
    return _is_identity(s) and not s.get("pinned") \
        and int(s["workers"]) == 1


_F32_OPS = ("scale_f32", "relu_f32", "square_f32", "threshold_mask")


def plan_fusion(stages: List[dict], enabled: bool
                ) -> Tuple[List[dict], Dict[str, List[str]], List[str]]:
    """-> (surviving stages, {survivor: [absorbed...]}, decision log).

    Only bit-exact merges are taken (identity absorption); everything
    considered is logged either way so ``report()`` shows the plan.
    """
    decisions: List[str] = []
    fused_from: Dict[str, List[str]] = {}
    prefix = [s for s in stages if s["kind"] != "reduce"]
    tail = [s for s in stages if s["kind"] == "reduce"]
    if not enabled:
        if len(prefix) > 1:
            decisions.append("fusion disabled (.fuse(False))")
        return stages, fused_from, decisions

    for s in prefix:
        if _is_identity(s) and s.get("pinned"):
            decisions.append(
                f"kept '{s['name']}': identity stage pinned by .scale()")
        elif _is_identity(s) and int(s["workers"]) > 1:
            decisions.append(
                f"kept '{s['name']}': identity stage has a worker pool "
                f"(workers={s['workers']}) — absorbing it would discard "
                f"the declared fan-out")

    survivors: List[dict] = []
    pending: List[str] = []
    for s in prefix:
        if _absorbable(s):
            pending.append(s["name"])
            continue
        if pending:
            fused_from.setdefault(s["name"], []).extend(pending)
            pending = []
        survivors.append(s)
    if pending:                       # trailing identities, or all-identity
        if survivors:
            fused_from.setdefault(survivors[-1]["name"], []).extend(pending)
        else:
            last = next(s for s in reversed(prefix)
                        if s["name"] == pending[-1])
            survivors.append(last)
            if pending[:-1]:
                fused_from[last["name"]] = pending[:-1]

    for host, absorbed in fused_from.items():
        decisions.append(
            f"fused {absorbed} into '{host}': identity is an exact u32 "
            f"passthrough (identity∘f == f bit-exact; "
            f"{len(absorbed)} seal/open hop(s) removed)")
    for a, b in zip(survivors, survivors[1:]):
        # identity survivors were already logged above with their real
        # keep-reason (pinned / worker pool) — identity∘f IS bit-exact
        if a["fn"] is None and b["fn"] is None \
                and not _is_identity(a) and not _is_identity(b):
            why = "f32 composition reorders rounding" \
                if a["op"] in _F32_OPS and b["op"] in _F32_OPS \
                else "the composed semantics are not registered"
            decisions.append(
                f"kept '{a['name']}'|'{b['name']}' separate: no bit-exact "
                f"fused kernel for {a['op']}∘{b['op']} in the op registry "
                f"({why})")
    return survivors + tail, fused_from, decisions


# ----------------------------------------------------------------- emission


def _to_stage(s: dict) -> Stage:
    if s["kind"] == "reduce":
        if s["fn"] is not None:
            # deep-copy the caller's init per build: builders are shared
            # and every reducer in this repo rebinds acc keys in place,
            # so a shared init would make a second run start from the
            # first run's totals (the registry path is factory-fresh
            # already)
            fn, init = s["fn"], copy.deepcopy(s["init"])
        else:
            from repro.dsl.reducers import resolve_reducer
            fn, init = resolve_reducer(s["reducer"])
        return Stage(s["name"], op="custom", reduce_fn=fn, reduce_init=init,
                     workers=int(s["workers"]), sgx=bool(s["sgx"]))
    if s["fn"] is not None:
        return Stage(s["name"], op="custom", fn=s["fn"],
                     workers=int(s["workers"]), sgx=bool(s["sgx"]))
    return Stage(s["name"], op=s["op"], const=float(s["const"]),
                 workers=int(s["workers"]), sgx=bool(s["sgx"]))


def compile_pipeline(ops: Sequence[Op], *, mode: str = "enclave",
                     seed: int = 0, directory=None, window_chunks: int = 8,
                     fuse: bool = True,
                     rekey_every_n: Optional[int] = None,
                     tracer=None, monitor=None,
                     retry=None, chaos=None) -> Pipeline:
    """Validate, fuse, and emit a :class:`Pipeline` from a DSL op chain.

    ``rekey_every_n`` (when known at build time, e.g. from a spec file)
    triggers the eager cadence-vs-``epoch_history`` rejection the engine
    would otherwise raise at ``run()``.  ``tracer`` (from
    ``StreamBuilder.trace``) and ``monitor`` (from
    ``StreamBuilder.monitor``) are attached to the emitted pipeline;
    None keeps each at its zero-cost disabled default.  ``retry`` (from
    ``StreamBuilder.retry``) and ``chaos`` (from ``StreamBuilder.chaos``)
    enable the fault-tolerant engine the same way.
    """
    stage_dicts = validate(ops, mode)
    fused, fused_from, decisions = plan_fusion(stage_dicts, fuse)
    kw: Dict[str, Any] = {}
    if directory is not None:
        kw["directory"] = directory
    if tracer is not None:
        kw["tracer"] = tracer
    if monitor is not None:
        kw["monitor"] = monitor
    if retry is not None:
        kw["retry"] = retry
    if chaos is not None:
        kw["chaos"] = chaos
    p = Pipeline([_to_stage(s) for s in fused],
                 SecureStreamConfig(mode=mode),
                 seed=seed, window_chunks=window_chunks,
                 fusion={"fused_from": fused_from, "decisions": decisions},
                 **kw)
    if rekey_every_n and mode != "plain":
        # the same guard Pipeline.run applies — surfaced at build time
        p._clamp_window_for_rekey(p.window_chunks, int(rekey_every_n))
    return p
