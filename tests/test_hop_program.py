"""The window engine's hop program: every worker share of a stage hop is
one launch of a cached compiled program (``EnclaveExecutor.
run_static_window``), and the share gather and the merge are cached
programs too.

The reference here is the eager sequence the program replaced: each
device op launched on its own, kept below as a test-only helper."""
from dataclasses import replace

import jax.numpy as jnp
import numpy as np
import pytest

from repro.attest.directory import KeyDirectory
from repro.attest.measure import IO_ENDPOINT
from repro.configs.base import SecureStreamConfig
from repro.core import enclave
from repro.core.enclave import (EnclaveExecutor, seal_table,
                                seal_tensors_window, plain_window,
                                window_to_chunks)
from repro.core.pipeline import Pipeline, Stage
from repro.crypto import aead, chacha20
from repro.crypto.keys import NonceExhaustedError, StageKey
from repro.data import ysb
from repro.kernels.enclave_map import ops
from repro.kernels.enclave_map.enclave_map import OPS, TABLE_OPS
from repro.obs.metrics import REGISTRY

B, N_WORDS = 4, 64                  # 4 chunks of 4 16-word records
TABLE_SEED = 2016
KINDS = ("single_epoch", "mixed_epoch", "reseal_as", "table_op",
         "aggregate_op", "tampered_row")
ROW_WISE = ("single_epoch", "mixed_epoch", "tampered_row")


def _directory():
    d = KeyDirectory(seed=11, epoch_history=8)
    for e in ("a", "b", "c"):
        d.enroll(e, IO_ENDPOINT, allow=True)
    d.establish("in", "a", "b")
    d.establish("out", "b", "c")
    d.establish("table", "a", "b")
    return d


def _chunks(kind, seed=0):
    """B chunks of u32 words: YSB events for the table and aggregate
    ops, random words for the row-wise filter."""
    rng = np.random.default_rng(seed)
    if kind not in ("table_op", "aggregate_op"):
        return [jnp.asarray(rng.integers(0, 40, N_WORDS, dtype=np.uint32))
                for _ in range(B)]
    ev = rng.integers(0, 2 ** 32, (B * N_WORDS // 16, 16), dtype=np.uint32)
    ad = rng.integers(0, ysb.CAMPAIGNS * ysb.ADS_PER_CAMPAIGN, len(ev))
    ev[:, ysb.AD_WORDS] = ysb.campaign_ads(TABLE_SEED)[ad]
    ev[:, ysb.CAMPAIGN_WORD] = ad // ysb.ADS_PER_CAMPAIGN
    ev[:, ysb.EVENT_TYPE_WORD] = rng.integers(0, 4, len(ev))
    ev[:, ysb.TIME_LO_WORD] = rng.integers(0, 30000, len(ev))
    ev[:, ysb.TIME_HI_WORD] = 0
    return [jnp.asarray(c.reshape(-1)) for c in np.split(ev, B)]


def _op(kind):
    return {"table_op": ("ysb_campaign_join", TABLE_SEED),
            "aggregate_op": ("ysb_window_count", 10000.0)}.get(
                kind, ("delay_filter_u32", 15.0))


def _window(mode, kind, d, seed=0, base=0):
    """A window sealed on edge ``in`` (framed, in plain mode); mixed-epoch
    windows take their second half after an epoch flip."""
    xs = _chunks(kind, seed)
    if mode == "plain":
        return plain_window(range(base, base + B), xs)
    h = d.handle("in")
    if kind != "mixed_epoch":
        win = seal_tensors_window(h, range(base, base + B), xs)
    else:
        w0 = seal_tensors_window(h, range(base, base + B // 2),
                                 xs[:B // 2])
        d.advance_epoch()
        w1 = seal_tensors_window(h, range(base, base + B // 2),
                                 xs[B // 2:])
        win = replace(w0, words=jnp.concatenate([w0.words, w1.words]),
                      tags=jnp.concatenate([w0.tags, w1.tags]),
                      counters=w0.counters + w1.counters,
                      epochs=w0.epochs + w1.epochs)
        assert set(win.epochs) == {0, 1}
    if kind == "tampered_row":
        win = replace(win, words=win.words.at[1, 0].add(np.uint32(1)))
    return win


def _table(mode, d):
    plain = ysb.campaign_table(TABLE_SEED)
    if mode == "plain":
        return jnp.asarray(plain)
    h = d.handle("table")
    return seal_table(h.key(), h.next_counter(), plain)


def _eager_params(key, win):
    """(keys, nonces) as the engine resolved them before: a Python loop
    of scalar nonces, one upload each for keys and nonces."""
    ks = [enclave._key_at(key, e) for e in win.epochs]
    if len(set(win.epochs)) == 1:
        keys = jnp.asarray(ks[0].key)
    else:
        keys = jnp.asarray(np.stack([np.asarray(k.key) for k in ks]))
    return keys, jnp.asarray(np.stack([np.asarray(k.nonce(c))
                                       for k, c in zip(ks, win.counters)]))


def _eager_hop(ex, op, const, win, reseal_as=None, table=None):
    """The eager sequence that ``run_static_window`` replaced: every
    device op (MAC-key derivation, MAC, block and coordinate expansion,
    kernel, slice, re-tag) launched on its own."""
    if ex.mode == "plain":
        return replace(win, words=enclave._apply_static_words(
            op, const, win.words, table)), None
    out_view, out_ctrs, out_epochs = enclave._reseal_coords(win, reseal_as)
    keys_in, nonces_in = _eager_params(ex.key_in, win)
    keys_out, nonces_out = _eager_params(ex.key_out, out_view)
    if ex.mode == "encrypted":
        pt, ok = aead.open_many(keys_in, nonces_in, win.words, win.tags)
        words = enclave._apply_static_words(op, const, pt, table)
        ct, tags = aead.seal_many(keys_out, nonces_out, words)
        return replace(win, words=ct, tags=tags, counters=out_ctrs,
                       epochs=out_epochs), ok
    n_blocks = (win.n_words + 15) // 16
    mk_in = aead.mac_keys_rows(keys_in, nonces_in)
    ok = jnp.all(aead.mac2_words(win.words, mk_in) == win.tags, axis=-1)
    rows = enclave._blocks_batch(win.words).reshape(-1, 16)
    row_nonces = jnp.repeat(nonces_in, n_blocks, axis=0)
    row_ctrs = jnp.tile(jnp.arange(1, n_blocks + 1, dtype=jnp.uint32),
                        len(win))
    per_row = lambda k: k if k.ndim == 1 else jnp.repeat(k, n_blocks, 0)
    kw = {} if reseal_as is None \
        else {"nonces_out": jnp.repeat(nonces_out, n_blocks, axis=0)}
    if table is not None:
        out = ops.enclave_join_rows(per_row(keys_in), per_row(keys_out),
                                    row_nonces, row_ctrs, rows, table.words,
                                    table.key_block, op=op, **kw)
    else:
        out = ops.enclave_map_rows(per_row(keys_in), per_row(keys_out),
                                   row_nonces, row_ctrs, rows, op=op,
                                   const=const, **kw)
    out_words = out.reshape(len(win), -1)[:, :win.n_words]
    mk_out = aead.mac_keys_rows(keys_out, nonces_out)
    return replace(win, words=out_words,
                   tags=aead.mac2_words(out_words, mk_out),
                   counters=out_ctrs, epochs=out_epochs), ok


def _kernel_ref(ex, op, const, win, plain_table):
    """The kernels' reference semantics for an enclave hop: decrypt every
    block row, apply the op in the clear, re-encrypt (``ref.py``)."""
    n_blocks = (win.n_words + 15) // 16
    keys_in, nonces = _eager_params(ex.key_in, win)
    keys_out, _ = _eager_params(ex.key_out, win)
    R = len(win) * n_blocks
    rows_of = lambda k: jnp.broadcast_to(k, (R, 8)) if k.ndim == 1 \
        else jnp.repeat(k, n_blocks, axis=0)
    row_nonces = jnp.repeat(nonces, n_blocks, axis=0)
    row_ctrs = jnp.tile(jnp.arange(1, n_blocks + 1, dtype=jnp.uint32),
                        len(win))
    rows = enclave._blocks_batch(win.words).reshape(-1, 16)
    pt = rows ^ chacha20.chacha20_block_rows(rows_of(keys_in), row_nonces,
                                             row_ctrs)
    y = TABLE_OPS[op][0](pt, jnp.asarray(plain_table)) \
        if op in TABLE_OPS else OPS[op](pt, const)
    ct = y ^ chacha20.chacha20_block_rows(rows_of(keys_out), row_nonces,
                                          row_ctrs)
    return ct.reshape(len(win), -1)[:, :win.n_words]


def _same(a, b):
    return np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("mode", ["plain", "encrypted", "enclave"])
def test_hop_program_equals_the_eager_sequence(mode, kind):
    d = _directory()
    win = _window(mode, kind, d)
    op, const = _op(kind)
    ex = EnclaveExecutor(mode, d.handle("in"), d.handle("out"))
    kw = {}
    if kind == "reseal_as":
        base, epoch = d.handle("in").reserve_window(B)
        kw["reseal_as"] = (list(range(base + 100, base + 100 + B)), epoch)
    if kind == "table_op":
        kw["table"] = _table(mode, d)
    d0 = REGISTRY.counter("device.dispatches").value
    got, ok = ex.run_static_window(op, const, win, **kw)
    assert REGISTRY.counter("device.dispatches").value - d0 == 1
    want, ok_want = _eager_hop(ex, op, const, win, **kw)

    assert _same(got.words, want.words)
    assert got.counters == want.counters and got.epochs == want.epochs
    if mode == "plain":
        assert ok is None and got.tags is None
    else:
        assert _same(got.tags, want.tags) and _same(ok, ok_want)
        assert list(np.asarray(ok)) == [kind != "tampered_row" or b != 1
                                        for b in range(B)]
    if mode != "plain" and kind == "reseal_as":
        assert got.counters == kw["reseal_as"][0]
        assert got.epochs == [kw["reseal_as"][1]] * B
    if mode == "enclave" and kind in ("table_op", "aggregate_op"):
        # a whole share's aggregate has no per-chunk form: the kernels'
        # reference semantics instead
        assert _same(got.words, _kernel_ref(
            ex, op, const, win, ysb.campaign_table(TABLE_SEED)))
    if kind in ROW_WISE:
        # the per-chunk engine, chunk by chunk
        for b, chunk in enumerate(window_to_chunks(win)):
            one = ex.run_static(op, const, chunk)
            if one is None:
                assert mode != "plain" and not bool(ok[b])
                continue
            assert _same(one.blocks.reshape(-1)[:win.n_words],
                         got.words[b])
            if mode != "plain":
                assert _same(one.tag, got.tags[b])


def test_a_steady_window_and_a_rekey_reuse_the_hop_program():
    """A second same-shape window, and a window after an epoch flip, hit
    the cache: keys and nonces are arguments of the program, never its
    constants, so jit itself traces nothing new either."""
    d = _directory()
    ex = EnclaveExecutor("enclave", d.handle("in"), d.handle("out"))
    op, const = _op("single_epoch")
    compiles = REGISTRY.counter("enclave.hop_programs.compiles")
    hits = REGISTRY.counter("enclave.hop_programs.hits")
    ex.run_static_window(op, const, _window("enclave", "single_epoch", d))
    prog = enclave._hop_program("enclave", op, const, (B, N_WORDS), 1, 1)
    c0, h0 = compiles.value, hits.value
    ex.run_static_window(op, const, _window("enclave", "single_epoch", d,
                                            seed=1, base=B))
    d.advance_epoch()
    win = _window("enclave", "single_epoch", d, seed=2)
    assert set(win.epochs) == {d.epoch} != {0}
    out, ok = ex.run_static_window(op, const, win)
    assert bool(np.asarray(ok).all()) and out.epochs == win.epochs
    assert compiles.value == c0
    assert hits.value == h0 + 2
    assert prog._cache_size() == 1


def test_merge_of_two_workers_keeps_stream_order_and_drops_a_failed_row():
    """Two workers split a window even/odd; a tampered row fails its MAC.
    The merge drops it and keeps the others in stream order, with one
    gather launch per share and one for the merge."""
    p = Pipeline([Stage("s", op="scale_f32", const=2.0, workers=2)],
                 SecureStreamConfig(mode="encrypted"), window_chunks=4)
    h0, h1 = p.keys[0], p.keys[1]
    xs = [jnp.full((16,), float(i + 1), jnp.float32) for i in range(8)]
    win = seal_tensors_window(h0, range(8), xs)
    win = replace(win, words=win.words.at[5, 0].add(np.uint32(1)))
    st = p.stages[0]
    gathers = REGISTRY.counter("device.dispatches.stage.gather")
    g0 = gathers.value
    outs = list(p._stage_stream(iter([win]), st, p._worker_pool(0, st), 4))
    assert gathers.value - g0 == 2 + 1
    assert len(outs) == 1 and outs[0].counters == [0, 1, 2, 3, 4, 6, 7]
    assert p.metrics["s"].mac_failures == 1
    assert p.metrics["s"].dispatches == 2 + 2 + 1   # gathers, hops, merge
    y, ok = enclave.egress_window("encrypted", h1, outs[0])
    assert bool(np.asarray(ok).all())
    assert _same(y[:, 0], [2.0 * (c + 1) for c in outs[0].counters])


def test_select_uploads_a_share_index_once():
    d = _directory()
    win = _window("encrypted", "single_epoch", d)
    n0 = len(enclave._INDEX_CACHE)
    a = win.select([0, 2])
    b = win.select([0, 2])
    assert len(enclave._INDEX_CACHE) <= n0 + 1
    assert enclave.device_index([0, 2]) is enclave.device_index((0, 2))
    assert _same(a.words, win.words[jnp.asarray([0, 2])])
    assert _same(a.tags, b.tags) and a.counters == [0, 2]


def test_vectorised_nonces_equal_the_scalar_nonces():
    k = StageKey(key=np.arange(8, dtype=np.uint32), stage_id=3)
    ctrs = [0, 1, 2 ** 32 - 1, 2 ** 32, 2 ** 33 + 5, 2 ** 64 - 1]
    assert _same(k.nonces(ctrs), np.stack([k.nonce(c) for c in ctrs]))
    for bad in ([5, 2 ** 64], [-1, 3]):
        with pytest.raises(NonceExhaustedError):
            k.nonces(bad)
    assert k.nonces([]).shape == (0, 3)
