"""Batched AEAD fast path (ISSUE 2): seal_many/open_many parity with the
scalar path on RFC 7539-derived vectors, Pallas-vs-jnp oracle checks,
batched tamper detection, the shape-keyed compile cache, and the
single-collective secure_exchange."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.attest.directory import ephemeral_edge_key
from repro.crypto import aead, chacha20, cwmac
from repro.launch.mesh import make_mesh

rng = np.random.default_rng(7)

# RFC 7539 §2.3.2 test-vector key/nonce (word-little-endian, as in
# test_kernels.test_chacha20_rfc7539_block)
RFC_KEY = jnp.asarray(np.array(
    [0x03020100, 0x07060504, 0x0b0a0908, 0x0f0e0d0c,
     0x13121110, 0x17161514, 0x1b1a1918, 0x1f1e1d1c], dtype=np.uint32))
RFC_NONCE = jnp.asarray(np.array([0x09000000, 0x4a000000, 0x00000000],
                                 dtype=np.uint32))


def _u32(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, 2 ** 32, shape, dtype=np.uint32))


# ------------------------------------------------------------ scalar fusion


def test_scalar_seal_single_pass_matches_two_pass_construction():
    """The fused seal (one chacha20 pass over counters 0..N) must equal the
    legacy construction: encrypt at counter0=1 + MAC keys from block 0."""
    pt = _u32(100, seed=1)
    ct, tag = aead.seal(RFC_KEY, RFC_NONCE, pt)
    ct_ref = chacha20.encrypt_words(RFC_KEY, RFC_NONCE, pt, counter0=1)
    r1, s1, r2, s2 = aead.derive_mac_keys(RFC_KEY, RFC_NONCE)
    tag_ref = cwmac.mac2(ct_ref, r1, s1, r2, s2)
    assert bool((ct == ct_ref).all()) and bool((tag == tag_ref).all())
    pt2, ok = aead.open_(RFC_KEY, RFC_NONCE, ct, tag)
    assert bool(ok) and bool((pt2 == pt).all())


def test_scalar_seal_keystream_is_rfc7539_block1():
    """Sealing zeros exposes the keystream: words 0..15 must be the RFC
    7539 §2.3.2 counter-1 block."""
    ct, _ = aead.seal(RFC_KEY, RFC_NONCE, jnp.zeros((16,), jnp.uint32))
    expected = np.array([0xe4e7f110, 0x15593bd1, 0x1fdd0f50, 0xc47120a3,
                         0xc7f4d1c7, 0x0368c033, 0x9aaa2204, 0x4e6cd4c3,
                         0x466482d2, 0x09aa9f07, 0x05d7c214, 0xa2028bd9,
                         0xd19c12b5, 0xb94e16de, 0xe883d0cb, 0x4e3c50a2],
                        dtype=np.uint32)
    assert np.array_equal(np.asarray(ct), expected)


# ------------------------------------------------------- batched vs scalar


@pytest.mark.parametrize("backend", ["pallas", "jnp"])
@pytest.mark.parametrize("B,n", [(1, 16), (4, 100), (9, 33)])
def test_seal_many_matches_vmap_seal(backend, B, n):
    """seal_many == vmap(seal) item-wise, RFC key among the batch nonces."""
    nonces = _u32((B, 3), seed=2).at[0].set(RFC_NONCE)
    words = _u32((B, n), seed=3)
    ct_b, tag_b = aead.seal_many(RFC_KEY, nonces, words, backend=backend)
    ct_v, tag_v = jax.vmap(aead.seal, in_axes=(None, 0, 0))(
        RFC_KEY, nonces, words)
    assert bool((ct_b == ct_v).all()) and bool((tag_b == tag_v).all())
    pt, ok = aead.open_many(RFC_KEY, nonces, ct_b, tag_b, backend=backend)
    assert bool(ok.all()) and bool((pt == words).all())


def test_seal_many_per_item_keys():
    B, n = 5, 40
    keys = _u32((B, 8), seed=4)
    nonces = _u32((B, 3), seed=5)
    words = _u32((B, n), seed=6)
    ct_b, tag_b = aead.seal_many(keys, nonces, words)
    ct_v, tag_v = jax.vmap(aead.seal)(keys, nonces, words)
    assert bool((ct_b == ct_v).all()) and bool((tag_b == tag_v).all())


def test_seal_many_backends_agree():
    """Pallas kernel path vs pure-jnp oracle on the same batch."""
    B, n = 4, 130
    nonces, words = _u32((B, 3), seed=8), _u32((B, n), seed=9)
    out_p = aead.seal_many(RFC_KEY, nonces, words, backend="pallas")
    out_j = aead.seal_many(RFC_KEY, nonces, words, backend="jnp")
    for a, b in zip(out_p, out_j):
        assert bool((a == b).all())


def test_seal_many_shape_validation():
    with pytest.raises(ValueError):
        aead.seal_many(RFC_KEY, _u32((2, 3)), _u32(16))
    with pytest.raises(ValueError):
        aead.seal_many(RFC_KEY, _u32((3, 3)), _u32((2, 16)))
    with pytest.raises(ValueError):
        aead.seal_many(_u32((4, 8)), _u32((2, 3)), _u32((2, 16)))
    with pytest.raises(ValueError):  # non-u32 payloads must be bitcast first
        aead.seal_many(RFC_KEY, _u32((2, 3)),
                       jnp.zeros((2, 16), jnp.int32))
    with pytest.raises(ValueError):  # typo'd backend must not fall through
        aead.seal_many(RFC_KEY, _u32((2, 3)), _u32((2, 16)),
                       backend="pallsa")


# ----------------------------------------------------------- cwmac batched


def test_cwmac_batch_matches_scalar_and_host_reference():
    B, n = 6, 77
    words = np.random.default_rng(10).integers(0, 2 ** 32, (B, n),
                                               dtype=np.uint32)
    r = np.random.default_rng(11).integers(1, 2 ** 31 - 1, B,
                                           dtype=np.uint32)
    s = np.random.default_rng(12).integers(0, 2 ** 31 - 1, B,
                                           dtype=np.uint32)
    got = cwmac.mac_batch(jnp.asarray(words), jnp.asarray(r), jnp.asarray(s))
    for b in range(B):
        want = cwmac.mac_reference(words[b], int(r[b]), int(s[b]))
        assert int(got[b]) == want
        assert int(got[b]) == int(cwmac.mac(jnp.asarray(words[b]),
                                            jnp.uint32(r[b]),
                                            jnp.uint32(s[b])))


@pytest.mark.parametrize("B,n", [(2, 50), (5, 1024), (3, 17)])
def test_cwmac_pallas_batch_matches_jnp_oracle(B, n):
    from repro.kernels.cwmac import ops as mac_ops
    words = _u32((B, n), seed=13)
    r1, s1 = _u32(B, 14) & np.uint32(0x7FFFFFFE), _u32(B, 15) & np.uint32(
        0x7FFFFFFE)
    r2, s2 = _u32(B, 16) & np.uint32(0x7FFFFFFE), _u32(B, 17) & np.uint32(
        0x7FFFFFFE)
    t_kernel = mac_ops.mac2_batch(words, r1, s1, r2, s2)
    t_jnp = cwmac.mac2_batch(words, r1, s1, r2, s2)
    assert bool((t_kernel == t_jnp).all())


# ------------------------------------------------------------------ tamper


def test_open_many_tamper_detection_is_per_item():
    B, n = 6, 64
    nonces, words = _u32((B, 3), seed=18), _u32((B, n), seed=19)
    ct, tags = aead.seal_many(RFC_KEY, nonces, words)
    bad_ct = ct.at[2, 10].set(ct[2, 10] ^ np.uint32(4))
    bad_tags = tags.at[4, 0].set(tags[4, 0] ^ np.uint32(1))
    _, ok = aead.open_many(RFC_KEY, nonces, bad_ct, tags)
    assert [bool(v) for v in ok] == [True, True, False, True, True, True]
    _, ok2 = aead.open_many(RFC_KEY, nonces, ct, bad_tags)
    assert [bool(v) for v in ok2] == [True, True, True, True, False, True]
    # wrong nonce on one item
    _, ok3 = aead.open_many(RFC_KEY, nonces.at[1, 1].add(np.uint32(1)),
                            ct, tags)
    assert not bool(ok3[1]) and bool(ok3[0])


# ----------------------------------------------------------- compile cache


def test_compile_cache_hits_on_round_two():
    """Round 1 of a fresh (B, n) shape compiles; round 2 must be a pure
    cache hit (no new program)."""
    aead.reset_fastpath_cache()
    nonces, words = _u32((3, 3), seed=20), _u32((3, 48), seed=21)
    aead.seal_many(RFC_KEY, nonces, words)
    s1 = aead.fastpath_stats()
    assert s1["compiles"] == 1 and s1["hits"] == 0
    aead.seal_many(RFC_KEY, nonces, words)
    s2 = aead.fastpath_stats()
    assert s2["compiles"] == 1 and s2["hits"] == 1
    # a different shape is a new program ...
    aead.seal_many(RFC_KEY, nonces, _u32((3, 49), seed=22))
    assert aead.fastpath_stats()["compiles"] == 2
    # ... and open has its own entry, also hit on round 2
    ct, tags = aead.seal_many(RFC_KEY, nonces, words)
    aead.open_many(RFC_KEY, nonces, ct, tags)
    c = aead.fastpath_stats()["compiles"]
    aead.open_many(RFC_KEY, nonces, ct, tags)
    assert aead.fastpath_stats()["compiles"] == c


# ------------------------------------------------- batch framing + channel


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "uint32", "int32"])
def test_tensor_batch_framing_matches_scalar(dtype):
    shape = (4, 5, 3)
    if dtype in ("float32", "bfloat16"):
        x = jax.random.normal(jax.random.key(0), shape).astype(dtype)
    else:
        x = jax.random.randint(jax.random.key(0), shape, 0, 999).astype(dtype)
    wb, meta = aead.tensor_to_words_batch(x)
    for b in range(shape[0]):
        ws, _ = aead.tensor_to_words(x[b])
        assert bool((wb[b] == ws).all())
    x2 = aead.words_to_tensor_batch(wb, meta)
    assert x2.dtype == x.dtype and bool((x2 == x).all())


def test_protect_many_roundtrip_and_cross_key_rejection():
    from repro.core.secure_channel import protect_many, unprotect_many
    keys = [ephemeral_edge_key(f"edge{i}", seed=3, stage_id=i)
            for i in range(3)]
    steps = [10, 11, 12]
    xs = jax.random.normal(jax.random.key(1), (3, 4, 6), jnp.bfloat16)
    cts, tags, meta = protect_many(keys, steps, xs)
    ys, ok = unprotect_many(keys, steps, cts, tags, meta)
    assert bool(ok.all()) and bool((ys == xs).all())
    # swapping two edge keys must fail exactly those items
    _, ok2 = unprotect_many([keys[1], keys[0], keys[2]], steps, cts, tags,
                            meta)
    assert [bool(v) for v in ok2] == [False, False, True]


# --------------------------------------------- single-collective exchange


def test_secure_exchange_issues_one_collective_per_round():
    from repro.dist import collectives
    mesh = make_mesh((1,), ("model",))
    x = jax.random.normal(jax.random.key(3), (1, 1, 16, 4), jnp.float32)
    key = ephemeral_edge_key("shuffle", seed=0)
    c0 = collectives.exchange_call_count()
    y, ok = collectives.secure_exchange(x, mesh, "model", key=key, step=5)
    assert collectives.exchange_call_count() - c0 == 1
    # the traced round itself holds exactly one all_to_all (the compiled
    # program drops it on a 1-device axis, so count it in the jaxpr)
    jaxpr = jax.make_jaxpr(lambda a: collectives.secure_exchange(
        a, mesh, "model", key=key, step=5))(x)
    assert len(re.findall(r"\ball_to_all\[", str(jaxpr))) == 1
    assert bool(ok.all())
    assert float(jnp.abs(y - jnp.swapaxes(x, 0, 1)).max()) == 0.0


def test_sealed_ppermute_packed_payload_roundtrip():
    """ct + tag ride one packed ppermute payload; roundtrip is exact."""
    from jax.sharding import PartitionSpec as P
    from repro.core.secure_channel import sealed_ppermute
    from repro.dist.compat import shard_map
    mesh = make_mesh((1,), ("stage",))
    key = ephemeral_edge_key("pp-edge", seed=2, stage_id=1)
    x = jnp.arange(1 * 32, dtype=jnp.uint32).reshape(1, 32)

    def body(xb):  # local (1, 32)
        y, ok = sealed_ppermute(key, 3, xb[0], "stage", [(0, 0)])
        return y[None], ok.reshape(1)

    y, ok = shard_map(body, mesh=mesh, in_specs=P("stage"),
                      out_specs=(P("stage"), P("stage")),
                      check_vma=False)(x)
    assert bool(ok.all()) and bool((y == x).all())


def test_route_nonce_cache_reuses_host_arrays():
    from repro.dist.collectives import _route_nonces
    a = _route_nonces(4, 9)
    b = _route_nonces(4, 9)
    assert a is b                      # cached jnp array, not rebuilt
    c = _route_nonces(4, 10)
    assert not np.array_equal(np.asarray(a), np.asarray(c))
    # counter layout unchanged: (step*W + src)*W + dst, little word first
    W, step = 4, 9
    flat = np.asarray(a).reshape(W, W, 3)
    for src in range(W):
        for dst in range(W):
            cnt = (step * W + src) * W + dst
            assert flat[src, dst, 1] == cnt & 0xFFFFFFFF
            assert flat[src, dst, 0] == 0
