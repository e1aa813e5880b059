"""Tests for the §Perf optimizations: hierarchical causal attention,
per-arch sharding rules, FSDP expert-weight specs, O(log n) MAC ladder."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_run_config
from repro.dist.meshctx import MeshContext
from repro.launch.mesh import make_mesh
from repro.models.flash import flash_attention
from repro.models.hier_attn import hier_causal_attention


@pytest.mark.parametrize("S,base", [(256, 64), (512, 128), (512, 64)])
def test_hier_attention_matches_flash(S, base):
    B, H, D = 2, 2, 16
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.float32)
    ref = flash_attention(q, k, v, True, 64, 64)
    out = hier_causal_attention(q, k, v, base=base, q_chunk=64, kv_chunk=64)
    assert float(jnp.abs(out - ref).max()) < 1e-5


def test_hier_attention_halves_hlo_flops():
    from repro.launch.hloanalysis import analyze
    B, S, H, D = 1, 512, 1, 16
    sds = jax.ShapeDtypeStruct((B, S, H, D), jnp.float32)
    c1 = jax.jit(lambda q, k, v: flash_attention(q, k, v, True, 64, 64)) \
        .lower(sds, sds, sds).compile()
    c2 = jax.jit(lambda q, k, v: hier_causal_attention(
        q, k, v, base=64, q_chunk=64, kv_chunk=64)) \
        .lower(sds, sds, sds).compile()
    a1 = analyze(c1.as_text())
    a2 = analyze(c2.as_text())
    # theoretical: 0.5 + O(base/S); allow generous slack
    assert a2.flops < 0.65 * a1.flops, (a1.flops, a2.flops)


def test_per_arch_sharding_rules_applied():
    # llama: pure-DP rules
    run = get_run_config("llama3.2-1b", "train_4k")
    rules = run.sharding.lookup()
    assert rules["heads"] == () and rules["mlp"] == ()
    assert rules["batch"] == ("pod", "data", "model")
    # kimi: FSDP experts + SP residual
    run = get_run_config("kimi-k2-1t-a32b", "train_4k")
    rules = run.sharding.lookup()
    assert rules["moe_ff"] == ("data",)
    assert rules["seq_res"] == ("model",)
    # granite: SP residual, gelu MLP
    run = get_run_config("granite-34b", "train_4k")
    assert run.sharding.lookup()["seq_res"] == ("model",)
    assert run.model.mlp_type == "gelu"


def test_fsdp_expert_weight_specs():
    """kimi expert weights must be sharded over BOTH axes at rest."""
    from repro.models.moe import moe_template
    from repro.models.layers import shardings_from_template
    run = get_run_config("kimi-k2-1t-a32b", "train_4k")
    mesh = make_mesh((1, 1), ("data", "model"))
    ctx = MeshContext(mesh=mesh, rules=run.sharding.lookup())
    sh = shardings_from_template(moe_template(run.model), ctx)
    assert sh["wg"].spec == P("model", None, "data")
    assert sh["wd"].spec == P("model", "data", None)


def test_r_powers_log_doubling_correct():
    from repro.crypto.cwmac import mulmod, r_powers
    p = (1 << 31) - 1
    r = 123456789
    ps = np.asarray(r_powers(jnp.uint32(r), 37))
    want = [pow(r, e, p) for e in range(37, 0, -1)]
    assert list(ps) == want


@pytest.mark.parametrize("n", [1, 2, 37, 128, 4096])
def test_r_powers_batch_square_and_multiply_correct(n):
    """The batched powers (every MAC program's power table, one rolled
    loop over the exponents' bits) equal r^e mod p, row by row."""
    from repro.crypto.cwmac import r_powers_batch
    p = (1 << 31) - 1
    rs = [1, 2, 123456789, p - 2]
    ps = np.asarray(r_powers_batch(jnp.asarray(rs, jnp.uint32), n))
    assert ps.shape == (len(rs), n)
    for row, r in zip(ps, rs):
        assert list(row) == [pow(r, e, p) for e in range(n, 0, -1)]


def test_mlp_gelu_vs_swiglu_param_difference():
    import dataclasses
    from repro.configs import get_model_config
    m = get_model_config("granite-34b")
    m_swiglu = dataclasses.replace(m, mlp_type="swiglu")
    extra = m_swiglu.param_count() - m.param_count()
    assert extra == m.num_layers * m.d_model * m.d_ff  # exactly one matrix
