"""Compile rehearsal: the window engine's Pallas kernels, compiled for a
described TPU v5e (no chip attached) at the shapes one DelayedFlights
window dispatches — chunk 1024 records, 2 workers per stage, window
factor 8 (16 chunks, 16 384 records per window) — and the YSB job's join
and count at a worker's share and at a whole window.

Nothing runs: a compile that passes says the TPU compiler (Mosaic)
accepts the block specs and the kernel fits VMEM, not that it is fast or
correct.  The topology is described inside a fixture, never at import.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from repro.kernels.chacha20.chacha20 import chacha20_xor_rows
from repro.kernels.cwmac.cwmac import mac_partials_batch
from repro.kernels.enclave_map.enclave_map import (AGGREGATE_BLOCK_ROWS,
                                                   campaign_join_rows,
                                                   enclave_apply_rows)

# read off the engine (encrypted + enclave modes, one window):
# seal/open rows = B chunks x (1024 blocks + the MAC-key block), padded to
# 256-row tiles: B=8 per worker share, B=16 per ingress/egress window
CHACHA_ROWS = (8448, 16640)
# CW-MAC limbs: 2 keys x B rows of 2 x 16 384 words, 4096-limb tiles
MAC_BATCHES = (16, 32)
MAC_LIMBS, MAC_TILE = 32768, 4096
# enclave hop: one worker's share, 8 chunks x 1024 records, a row each
ENCLAVE_ROWS = 8192
BLOCK_ROWS = 256
# the YSB join and count: a worker's share, and a whole engine window
YSB_ROWS = (8192, 16384)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def u32(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda *shape: jax.ShapeDtypeStruct(shape, jnp.uint32,
                                               sharding=one_chip)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("rows", CHACHA_ROWS)
def test_chacha20_xor_rows_compiles_for_v5e(u32, rows):
    fn = functools.partial(chacha20_xor_rows, block_rows=BLOCK_ROWS,
                           interpret=False)
    text = _compiled_text(fn, u32(rows, 8), u32(rows, 3), u32(rows),
                          u32(rows, 16))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("batch", MAC_BATCHES)
def test_mac_partials_batch_compiles_for_v5e(u32, batch):
    fn = functools.partial(mac_partials_batch, tile=MAC_TILE,
                           interpret=False)
    text = _compiled_text(fn, u32(batch, MAC_LIMBS), u32(batch, MAC_TILE))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("op", ["identity", "delay_filter_u32",
                                "ysb_view_filter"])
def test_enclave_apply_rows_compiles_for_v5e(u32, op):
    fn = functools.partial(enclave_apply_rows, op=op, const=15.0,
                           block_rows=BLOCK_ROWS, interpret=False)
    R = ENCLAVE_ROWS
    text = _compiled_text(fn, u32(R, 8), u32(R, 8), u32(R, 3), u32(R),
                          u32(R, 16))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows", YSB_ROWS)
def test_campaign_join_rows_compiles_for_v5e(u32, rows):
    fn = functools.partial(campaign_join_rows, block_rows=BLOCK_ROWS,
                           interpret=False)
    text = _compiled_text(fn, u32(rows, 8), u32(rows, 8), u32(rows, 3),
                          u32(rows), u32(rows, 16), u32(64, 128),
                          u32(1, 16))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows", YSB_ROWS)
def test_ysb_window_count_compiles_for_v5e(u32, rows):
    """The count reads every row of the call: its output is one block,
    and its opened rows stay in VMEM until the last grid step."""
    fn = functools.partial(enclave_apply_rows, op="ysb_window_count",
                           const=10000.0, block_rows=AGGREGATE_BLOCK_ROWS,
                           interpret=False)
    text = _compiled_text(fn, u32(rows, 8), u32(rows, 8), u32(rows, 3),
                          u32(rows), u32(rows, 16))
    assert "tpu_custom_call" in text


def test_sealed_keyed_route_seals_per_shard_on_v5e_2x2(topo, monkeypatch):
    """The sealed shuffle on a 4-chip mesh: every shard runs its own cipher
    and MAC kernels, and only the one packed all_to_all crosses chips —
    no all-gather pulls the mailbox onto one chip."""
    from repro.attest.directory import ephemeral_edge_key
    from repro.dist.collectives import keyed_route
    from repro.kernels.chacha20 import ops as chacha_ops
    from repro.kernels.cwmac import ops as cwmac_ops
    from repro.launch.mesh import make_mesh
    for mod in (chacha_ops, cwmac_ops):   # this process's backend is CPU
        monkeypatch.setattr(mod, "interpret_mode", lambda: False)
    mesh = make_mesh((4,), ("model",), devices=topo.devices)
    rows = NamedSharding(mesh, P("model"))
    key = ephemeral_edge_key("shuffle", seed=0)
    x = jax.ShapeDtypeStruct((4, 1024, 16), jnp.uint32, sharding=rows)
    carriers = jax.ShapeDtypeStruct((4, 1024), jnp.uint32, sharding=rows)
    text = _compiled_text(
        lambda a, k: keyed_route(a, k, mesh, "model", key=key, step=0,
                                 hash_keys=False), x, carriers)
    assert "tpu_custom_call" in text
    assert len(re.findall(r"all-to-all", text)) == 1
    assert "all-gather" not in text


@pytest.mark.parametrize("op,rows", [("delay_filter_u32", ENCLAVE_ROWS),
                                     ("ysb_campaign_join", YSB_ROWS[0]),
                                     ("ysb_window_count", YSB_ROWS[0])])
def test_hop_program_keeps_each_kernel_name_for_v5e(u32, op, rows,
                                                    monkeypatch):
    """One worker share's hop program — the ciphertext MAC check, the
    fused enclave kernel and the re-tag in one compiled program — keeps
    each kernel as its own instruction, under its own name and with the
    operand shapes of a standalone call: the names and shapes the chip
    benchmark's device-trace readers match."""
    from repro.core import enclave
    from repro.kernels.cwmac import ops as cwmac_ops
    from repro.kernels.enclave_map import ops as enclave_ops
    for mod in (cwmac_ops, enclave_ops):  # this process's backend is CPU
        monkeypatch.setattr(mod, "interpret_mode", lambda: False)
    B, n = 8, rows * 16 // 8
    table = op in enclave_ops.TABLE_OPS
    impl = enclave_ops.enclave_join_rows if table \
        else enclave_ops.enclave_map_rows
    body = functools.partial(enclave._enclave_hop, op, 10000.0, impl)
    text = _compiled_text(body, u32(B, n), u32(B, 2), u32(8), u32(8),
                          u32(B, 3), None,
                          (u32(64, 128), u32(1, 16)) if table else None)
    kernel = "campaign_join_rows" if table else "enclave_apply_rows"
    calls = re.findall(rf"^\s*%{kernel}(?:\.\d+)? = (u32\[[0-9,]+\]).*?"
                       r"operand_layout_constraints=\{([^=]*)\}, ", text,
                       re.M)
    assert len(calls) == 1, kernel
    out, operands = calls[0]
    shapes = re.findall(r"u32\[([0-9,]+)\]", operands)
    cols = [8, 8, 3, 1, 3, 1, 16] + ([128, 16] if table else [])
    assert out == f"u32[{rows},16]"
    assert shapes[:7] == [f"{rows},{c}" for c in cols[:7]]
    assert shapes[7:] == (["64,128", "1,16"] if table else [])
    # the inbound check and the outbound re-tag: one MAC kernel each
    assert len(re.findall(r"^\s*%mac_partials_batch(?:\.\d+)? = ", text,
                          re.M)) == 2
