"""repro.dsl: fluent builder + TOML spec loader, compiled to the engine.

The acceptance contract: the DelayedFlights pipeline expressed in <= 12
lines via the fluent DSL AND via a TOML spec, both bit-identical to the
hand-built ``Pipeline([Stage(...)])`` oracle in all three security modes
— including under ``rekey_every_n=3`` with a mid-stream revocation — and
structurally zero-overhead (the compiler emits the same Stage list the
hand-built form uses).  Plus: eager validation, bit-exact-only fusion
with reported decisions, and the spec-loader surface.
"""
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.attest.directory import KeyDirectory
from repro.configs.base import SecureStreamConfig
from repro.core import Pipeline, Stage
from repro.core.observable import describe_ops
from repro.data.synthetic import CARRIER_WORD, DELAY_WORD, flight_chunks
from repro.dsl import (DSLValidationError, SpecError, load_spec,
                       register_reducer, stream)

N_RECORDS, CHUNK = 1024, 64          # 16 chunks of 64 records (4 KiB each)


def _src(seed=1):
    return (jnp.asarray(c) for c in
            flight_chunks(N_RECORDS, CHUNK, seed=seed))


def _manual_reduce():
    """The pre-DSL hand-built reducer, kept verbatim as the oracle."""
    def reduce_fn(acc, chunk):
        carrier = np.asarray(chunk[:, CARRIER_WORD]).astype(np.int64)
        delay = np.asarray(chunk[:, DELAY_WORD]).astype(np.int64)
        valid = delay > 0
        acc["count"] = acc["count"] + np.bincount(carrier[valid],
                                                  minlength=20)
        acc["sum"] = acc["sum"] + np.bincount(
            carrier[valid], weights=delay[valid], minlength=20)
        return acc
    return reduce_fn, {"count": np.zeros(20), "sum": np.zeros(20)}


def _manual_pipeline(mode: str, workers: int = 2) -> Pipeline:
    """The pre-DSL construction (the parity oracle the DSL must match)."""
    fn, init = _manual_reduce()
    return Pipeline(
        [Stage("sgx_mapper", op="identity", workers=workers, sgx=True),
         Stage("sgx_filter", op="delay_filter_u32", const=15,
               workers=workers, sgx=True),
         Stage("reducer", op="custom", reduce_fn=fn, reduce_init=init)],
        SecureStreamConfig(mode=mode))


# The acceptance artifact: the whole job in <= 12 lines, fluent form.
FLUENT_FORM = """\
result = (stream(source)
          .map("identity", name="sgx_mapper", workers=2, sgx=True)
          .filter("delay_filter_u32", const=15, name="sgx_filter",
                  workers=2, sgx=True)
          .reduce("carrier_delay_stats", name="reducer")
          .run(mode=mode))
"""

# ... and the declarative TOML form (paper Listing 1 shape), 12 lines.
TOML_FORM = """\
mode = "MODE"
[stage.sgx_mapper]
op = "identity"
workers = 2
constraint = "sgx"
[stage.sgx_filter]
op = "delay_filter_u32"
const = 15
workers = 2
constraint = "sgx"
[stage.reducer]
reduce = "carrier_delay_stats"
"""


def _assert_same(a, b):
    assert np.array_equal(a["count"], b["count"])
    assert np.array_equal(a["sum"], b["sum"])


# ------------------------------------------------------- acceptance parity


@pytest.mark.parametrize("mode", ["plain", "encrypted", "enclave"])
def test_fluent_and_toml_bit_identical_to_manual(mode):
    """Both <= 12-line forms, bit-identical to the hand-built oracle."""
    assert len(FLUENT_FORM.strip().splitlines()) <= 12
    assert len(TOML_FORM.strip().splitlines()) <= 12

    oracle = _manual_pipeline(mode).run(_src())

    ns = {"stream": stream, "source": _src(), "mode": mode}
    exec(FLUENT_FORM, ns)                      # the documented snippet
    _assert_same(ns["result"], oracle)

    spec_out = load_spec(TOML_FORM.replace("MODE", mode)).run(_src())
    _assert_same(spec_out, oracle)


@pytest.mark.parametrize("mode", ["plain", "encrypted", "enclave"])
def test_parity_under_rekey_and_mid_stream_revocation(mode):
    """rekey_every_n=3 + a live revocation of a filter worker mid-stream:
    DSL-compiled and hand-built pipelines stay bit-identical."""
    def run(p):
        def source():
            for i, c in enumerate(flight_chunks(N_RECORDS, CHUNK, seed=1)):
                if i == 6:
                    p.directory.revoke(Pipeline.worker_id("sgx_filter", 1))
                yield jnp.asarray(c)
        return p.run(source(), rekey_every_n=3)

    manual = run(_manual_pipeline(mode))
    sb = (stream()
          .map("identity", name="sgx_mapper", workers=2, sgx=True)
          .filter("delay_filter_u32", const=15, name="sgx_filter",
                  workers=2, sgx=True)
          .reduce("carrier_delay_stats", name="reducer"))
    dsl = run(sb.build(mode))
    _assert_same(dsl, manual)
    # the revoked worker stopped receiving rows on the DSL pipeline too
    rep = sb.report()["sgx_filter"]
    assert rep["per_worker"][1] < rep["per_worker"][0]


def test_example_spec_file_loads_and_matches():
    """examples/flight_delay.toml is live documentation: it must load and
    agree with the fluent form."""
    path = os.path.join(os.path.dirname(__file__), "..", "examples",
                        "flight_delay.toml")
    sb = load_spec(path)
    out = sb.run(_src(), mode="encrypted")
    _assert_same(out, _manual_pipeline("encrypted").run(_src()))


def test_dsl_is_structurally_zero_overhead():
    """The compiler emits the same Stage list the hand-built form uses
    (modulo bit-exact fusion): with fusion off, stage tuples are equal —
    there is no DSL wrapper anywhere near the hot path."""
    sb = (stream()
          .map("identity", name="sgx_mapper", workers=2, sgx=True)
          .filter("delay_filter_u32", const=15, name="sgx_filter",
                  workers=2, sgx=True)
          .reduce("carrier_delay_stats", name="reducer").fuse(False))
    p = sb.build("encrypted")
    assert type(p) is Pipeline
    sig = [(s.name, s.op, s.const, s.workers, s.sgx) for s in p.stages]
    assert sig == [(s.name, s.op, s.const, s.workers, s.sgx)
                   for s in _manual_pipeline("encrypted").stages]


# ------------------------------------------------------------------ fusion


def test_identity_fusion_removes_a_hop_and_is_reported():
    sb = (stream()
          .map("identity", name="m")
          .filter("delay_filter_u32", const=15, name="f")
          .reduce("carrier_delay_stats", name="r"))
    p = sb.build("encrypted")
    assert [s.name for s in p.stages] == ["f", "r"]      # m absorbed
    rep = p.report()
    assert rep["f"]["fused_from"] == ["m"]
    assert any("fused" in d for d in rep["fusion"]["decisions"])
    # fusion survives a live rescale
    p2 = p.scale_stage("f", 3)
    assert p2.report()["f"]["fused_from"] == ["m"]


def test_fusion_declines_non_bit_exact_compositions():
    """scale∘scale is NOT fused (f32 rounding reorders); the declined
    decision is still reported."""
    sb = (stream().map("scale_f32", const=2.0, name="a")
          .map("scale_f32", const=3.0, name="b"))
    p = sb.build("encrypted")
    assert [s.name for s in p.stages] == ["a", "b"]
    assert any("kept 'a'|'b'" in d for d in p.fusion["decisions"])


def test_trailing_and_all_identity_chains():
    p = (stream().map("scale_f32", const=2.0, name="a")
         .map("identity", name="tail")).build("encrypted")
    assert [s.name for s in p.stages] == ["a"]
    assert p.fusion["fused_from"] == {"a": ["tail"]}
    p = (stream().map("identity", name="i0")
         .map("identity", name="i1")).build("encrypted")
    assert [s.name for s in p.stages] == ["i1"]
    assert p.fusion["fused_from"] == {"i1": ["i0"]}


def test_scale_pins_a_stage_against_fusion():
    sb = (stream().map("identity", name="m")
          .filter("delay_filter_u32", const=15, name="f")
          .scale("m", 4))
    p = sb.build("encrypted")
    assert [s.name for s in p.stages] == ["m", "f"]
    assert p.stages[0].workers == 4
    assert any("pinned" in d for d in p.fusion["decisions"])
    with pytest.raises(KeyError):
        stream().map("identity", name="m").scale("nope", 2)


def test_fused_output_matches_unfused():
    base = (stream()
            .map("identity", name="m")
            .filter("delay_filter_u32", const=15, name="f")
            .reduce("carrier_delay_stats", name="r"))
    fused, unfused = base, base.fuse(False)
    assert len(fused.build("encrypted").stages) \
        < len(unfused.build("encrypted").stages)
    _assert_same(fused.run(_src(), mode="encrypted"),
                 unfused.run(_src(), mode="encrypted"))


def test_worker_pool_identity_is_not_absorbed():
    """Fusion must not discard declared fan-out: an identity stage with
    an explicit worker pool survives, with the decision logged."""
    p = (stream().map("identity", name="m", workers=2)
         .filter("delay_filter_u32", const=15, name="f")).build("encrypted")
    assert [s.name for s in p.stages] == ["m", "f"]
    assert p.stages[0].workers == 2
    assert any("worker pool" in d for d in p.fusion["decisions"])
    # and the decline log never claims identity∘f is not bit-exact
    assert not any("identity∘" in d and "no bit-exact" in d
                   for d in p.fusion["decisions"])


def test_shared_builder_reruns_do_not_accumulate_reduce_state():
    """A mutable init passed to .reduce() must be copied per build:
    running a shared builder twice gives identical totals."""
    fn, init = _manual_reduce()
    sb = (stream().filter("delay_filter_u32", const=15, name="f")
          .reduce(fn, init, name="r"))
    first = sb.run(_src(), mode="plain")
    second = sb.run(_src(), mode="plain")
    _assert_same(first, second)


# -------------------------------------------------------- eager validation


def test_unknown_op_rejected_at_build():
    with pytest.raises(DSLValidationError, match="registered ops"):
        stream().map("not_an_op").build("encrypted")


def test_closure_under_enclave_rejected_eagerly_unless_unconstrained():
    sb = stream().map(lambda x: x * 2, name="c")
    with pytest.raises(DSLValidationError, match="no-dynamic-linking"):
        sb.build("enclave")
    # sgx=False runs on the encrypted (non-enclave) path: allowed
    out = (stream().map(lambda x: x * 2.0, name="c", sgx=False)
           .build("enclave")
           .run(iter([jnp.ones(64, jnp.float32)])))
    assert np.allclose(np.asarray(out), 2.0)


def test_structural_validation():
    with pytest.raises(DSLValidationError, match="empty pipeline"):
        stream().build("plain")
    with pytest.raises(DSLValidationError, match="terminal"):
        (stream().reduce("sum", name="r")
         .map("identity", name="m")).build("plain")
    with pytest.raises(DSLValidationError, match="duplicate"):
        (stream().map("identity", name="x")
         .map("identity", name="x")).build("plain")
    with pytest.raises(DSLValidationError, match="workers"):
        stream().map("identity", workers=0).build("plain")
    with pytest.raises(KeyError, match="unknown reducer"):
        stream().map("identity").reduce("nope").build("plain")
    with pytest.raises(DSLValidationError, match="unknown mode"):
        stream().map("identity").build("tls")


def test_rekey_cadence_rejected_at_build_not_midstream():
    """The rekey-vs-epoch-history guard fires at build() — before any
    chunk is sealed — with the engine's own error message."""
    sb = (stream().map("scale_f32", const=2.0, name="s")
          .directory(KeyDirectory(epoch_history=1)))
    with pytest.raises(ValueError, match="epoch_history"):
        sb.build("encrypted", rekey_every_n=1)


# ------------------------------------------------------------- spec loader


def test_spec_dict_and_array_forms_and_count_alias():
    doc = {"mode": "plain",
           "stage": [{"name": "f", "op": "delay_filter_u32", "const": 15,
                      "count": 2, "constraint": "type==sgx"},
                     {"name": "r", "reduce": "carrier_delay_stats"}]}
    sb = load_spec(doc)
    p = sb.build()
    assert p.stages[0].workers == 2 and p.stages[0].sgx
    _assert_same(sb.run(_src()),
                 load_spec(TOML_FORM.replace("MODE", "plain")).run(_src()))


def test_spec_local_reducers_and_errors():
    out = load_spec(
        {"mode": "plain",
         "stage": [{"name": "r", "reduce": "n_chunks"}]},
        reducers={"n_chunks": ((lambda acc, c: acc + 1), 0)},
    ).run(_src())
    assert out == N_RECORDS // CHUNK

    with pytest.raises(SpecError, match="no stages"):
        load_spec({"mode": "plain"})
    with pytest.raises(SpecError, match="'op'.*or a 'reduce'|needs"):
        load_spec({"stage": [{"name": "x"}]})
    with pytest.raises(SpecError, match="missing a name"):
        load_spec({"stage": [{"op": "identity"}]})
    with pytest.raises(SpecError, match="cannot parse"):
        load_spec("stage = ???\n")


def test_spec_rejects_unknown_keys():
    """A typo'd key must fail the load, not run with a silent default."""
    with pytest.raises(SpecError, match="unknown key 'conts'"):
        load_spec({"stage": [{"name": "f", "op": "delay_filter_u32",
                              "conts": 15}]})
    with pytest.raises(SpecError, match="unknown key 'worker'"):
        load_spec({"stage": [{"name": "f", "op": "identity",
                              "worker": 2}]})
    with pytest.raises(SpecError, match="unknown top-level key"):
        load_spec({"mod": "plain",
                   "stage": [{"name": "f", "op": "identity"}]})
    with pytest.raises(SpecError, match=r"unknown \[pipeline\] key"):
        load_spec({"pipeline": {"mode": "plain", "rekey": 3},
                   "stage": [{"name": "f", "op": "identity"}]})


def test_mini_toml_parser_subset():
    """The TOML subset specs use — comments, both quote styles, ints,
    floats, tables and arrays of tables — loads through ``load_spec``."""
    sb = load_spec("""
    # comment
    name = "x"            # trailing comment
    window_chunks = 3
    [pipeline]
    mode = 'plain'
    [[stage]]
    name = "f"
    op = 'delay_filter_u32'
    const = 1.5
    count = 2
    [[stage]]
    name = "r"
    reduce = "carrier_delay_stats"
    """)
    p = sb.build()
    assert p.secure.mode == "plain" and p.window_chunks == 3
    assert [s.name for s in p.stages] == ["f", "r"]
    assert p.stages[0].const == 1.5 and p.stages[0].workers == 2
    with pytest.raises(SpecError, match="unknown top-level key 'flag'"):
        load_spec("flag = true\n[[stage]]\nname = 'f'\nop = 'identity'\n")


def test_registered_reducer_roundtrip():
    @register_reducer("test_dsl_total_delay")
    def _total(**kw):
        def fn(acc, chunk):
            return acc + int(np.asarray(chunk[:, DELAY_WORD]).sum())
        return fn, 0
    out = (stream(_src()).reduce("test_dsl_total_delay").run(mode="plain"))
    assert out > 0


# -------------------------------------------------- observable interop


def test_as_observable_matches_plain_mode():
    """The DSL chain lowered onto the plaintext Observable layer is the
    cleartext oracle: identical result to mode='plain'."""
    sb = (stream()
          .map("identity", name="m")
          .filter("delay_filter_u32", const=15, name="f")
          .reduce("carrier_delay_stats", name="r"))
    _assert_same(sb.as_observable(_src()).subscribe(),
                 sb.run(_src(), mode="plain"))


def test_shared_describe_vocabulary():
    sb = (stream().map("identity", name="m", workers=4)
          .filter("delay_filter_u32", const=15, name="f"))
    d = sb.describe()
    assert "map(identity)[w=4,sgx]" in d and "filter(delay_filter_u32)" in d
    assert describe_ops(sb.ops) == d
    assert "map" in sb.as_observable(_src()).describe()
