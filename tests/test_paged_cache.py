"""BlockAllocator + device pool ops."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.paged_cache import (BlockAllocator, OutOfBlocksError,
                                    copy_blocks, gather_kv, make_kv_pool,
                                    write_decode_kv, write_prefill_kv)


def test_alloc_free_refcount():
    a = BlockAllocator(8, 4)
    ids, _ = a.allocate_prompt(list(range(9)))     # 2 full + 1 partial
    assert len(ids) == 3 and a.num_free == 5
    a.free_sequence(ids)
    assert a.num_free == 8


def test_prefix_reuse_and_cow():
    a = BlockAllocator(16, 4)
    p = list(range(8))
    ids1, r1 = a.allocate_prompt(p + [100])
    ids2, r2 = a.allocate_prompt(p + [200])
    assert r1 == 0 and r2 == 2                     # two full blocks shared
    assert ids1[:2] == ids2[:2] and ids1[2] != ids2[2]
    # exact-multiple prompt: shared tail is full; append allocates fresh blk
    ids3, r3 = a.allocate_prompt(p)
    assert r3 == 2 and len(ids3) == 2
    ids3b, copied = a.append_slot(ids3, 8)
    assert len(ids3b) == 3 and copied is None


def test_out_of_blocks():
    a = BlockAllocator(2, 4, watermark_frac=0.0)
    with pytest.raises(OutOfBlocksError):
        a.allocate_prompt(list(range(100)))


def test_watermark_admission():
    a = BlockAllocator(10, 4)
    assert a.can_allocate(9)
    assert not a.can_allocate(10)


def test_pool_roundtrip_nonsequential_blocks():
    kp, _ = make_kv_pool(1, 8, 4, 2, 8, dtype=jnp.float32)
    bt = jnp.array([[5, 1], [7, 0]], jnp.int32)
    k = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 2, 8))
    kp = write_prefill_kv(kp, 0, k, bt, jnp.array([8, 6]))
    g = gather_kv(kp, 0, bt, 8)
    np.testing.assert_allclose(g[0], k[0])
    np.testing.assert_allclose(g[1, :6], k[1, :6])
    np.testing.assert_allclose(g[1, 6:], 0)


def test_decode_write_targets_correct_slot():
    kp, _ = make_kv_pool(2, 4, 4, 1, 4, dtype=jnp.float32)
    bt = jnp.array([[2, 3]], jnp.int32)
    kn = jnp.ones((1, 1, 4))
    kp = write_decode_kv(kp, 1, kn, bt, jnp.array([5]))
    assert float(kp[1, 3, :, 1].sum()) == 4.0       # block 3, offset 1
    assert float(kp.sum()) == 4.0                   # nothing else written


def test_register_full_block_and_grow_prefill_reuse():
    """Register-on-write: a block content-addressed after allocation is
    discoverable by both ``allocate_prompt`` and the continuation-chunk
    ``grow_prefill``; freeing the last reference unregisters it."""
    a = BlockAllocator(16, 4)
    p = list(range(12))
    ids, _ = a.allocate_prompt(p[:5])              # 1 hashed full + tail
    # the chunk that fills blocks 1 and 2 registers them afterwards
    ids, reused = a.grow_prefill(ids, 5, 7, p)
    assert reused == 0 and len(ids) == 3
    a.register_full_block(ids[1], p[:8])
    a.register_full_block(ids[2], p[:12])
    # re-registering / hash collisions are no-ops
    a.register_full_block(ids[1], p[:8])
    b_ids, r = a.allocate_prompt(p)                # whole prompt: 3 shared
    assert r == 3 and b_ids == ids[:3]
    # continuation growth also finds them
    c_ids, _ = a.allocate_prompt(p[:4])
    c_ids, r = a.grow_prefill(c_ids, 4, 8, p)
    assert r == 2 and c_ids == ids[:3]
    # a partially-covered tail block is never shared
    d_ids, _ = a.allocate_prompt(p[:4])
    d_ids, r = a.grow_prefill(d_ids, 4, 6, p)      # covers block 1, half 2
    assert r == 1 and d_ids[1] == ids[1] and d_ids[2] != ids[2]
    a.free_sequence(b_ids)
    a.free_sequence(c_ids)
    a.free_sequence(d_ids)
    a.free_sequence(ids)                           # last ref: hashes popped
    e_ids, r = a.allocate_prompt(p)
    assert r == 0


def test_gather_kv_bounded_matches_full_gather_on_live_prefix():
    """The bounded gather returns the full gather's bytes on every live
    position and zeros past the walked pages (bf16 and int8 pools)."""
    from repro.core.kv_quant import (KVCache, kv_gather, kv_gather_bounded,
                                     make_kv_pool_quant)
    rng = np.random.default_rng(0)
    L, NB, BS, KV, D, MB = 2, 10, 4, 2, 8, 5
    bt = jnp.asarray(rng.permutation(NB)[:MB][None], jnp.int32)
    kp, vp = make_kv_pool(L, NB, BS, KV, D, jnp.float32)
    kp = jnp.asarray(rng.normal(size=kp.shape), jnp.float32)
    vp = jnp.asarray(rng.normal(size=vp.shape), jnp.float32)
    cache = KVCache(kp, vp)
    total = 9                                      # 3 live pages of 5
    live = -(-total // BS)
    for li in range(L):
        kb, vb = kv_gather_bounded(cache, li, bt, MB * BS, live,
                                   jnp.float32)
        kf, vf = kv_gather(cache, li, bt, MB * BS, jnp.float32)
        np.testing.assert_array_equal(np.asarray(kb[:, :live * BS]),
                                      np.asarray(kf[:, :live * BS]))
        assert not np.any(np.asarray(kb[:, live * BS:]))
        np.testing.assert_array_equal(np.asarray(vb[:, :live * BS]),
                                      np.asarray(vf[:, :live * BS]))
    kq, vq, ks, vs = make_kv_pool_quant(L, NB, BS, KV, D)
    kq = jnp.asarray(rng.integers(-127, 128, kq.shape), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.01, 0.1, ks.shape), jnp.float32)
    qcache = KVCache(kq, kq, ks, ks)
    kb, _ = kv_gather_bounded(qcache, 1, bt, MB * BS, live, jnp.float32)
    kf, _ = kv_gather(qcache, 1, bt, MB * BS, jnp.float32)
    np.testing.assert_array_equal(np.asarray(kb[:, :live * BS]),
                                  np.asarray(kf[:, :live * BS]))
    assert not np.any(np.asarray(kb[:, live * BS:]))


def _old_write_decode(pool, layer, k_new, bt, positions):
    """Decode write into the token-major ``[L, NB, BS, KV, D]`` pool the
    cache had before its pages became head-major (the reference)."""
    bs = pool.shape[2]
    valid = positions >= 0
    pos = jnp.maximum(positions, 0)
    blk = jnp.take_along_axis(bt, (pos // bs)[:, None], axis=1)[:, 0]
    blk = jnp.where(valid, blk, pool.shape[1])
    return pool.at[layer, blk, pos % bs].set(k_new.astype(pool.dtype),
                                             mode="drop")


def _old_write_prefill(pool, layer, k, bt, ctx_lens, pos_offset):
    """Prefill write into the token-major pool through its flattened
    layer slice, as before."""
    B, S = k.shape[:2]
    L, NB, BS = pool.shape[:3]
    pos = pos_offset + jnp.arange(S)
    blk = bt[:, pos // BS]
    valid = pos[None, :] < ctx_lens[:, None]
    flat = jnp.where(valid, blk * BS + (pos % BS)[None, :], NB * BS)
    lp = pool[layer].reshape(NB * BS, *pool.shape[3:])
    lp = lp.at[flat.reshape(-1)].set(
        k.reshape(B * S, *k.shape[2:]).astype(pool.dtype), mode="drop")
    return pool.at[layer].set(lp.reshape(pool.shape[1:]))


@pytest.mark.parametrize("case,layer,off", [
    ("decode", 0, 0), ("decode", -1, -1), ("decode-inactive", -1, -1),
    ("prefill", 0, 0), ("prefill", -1, -1), ("cow", -1, -1)])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_head_major_writes_equal_token_major_transposed(case, layer, off,
                                                        dtype):
    """The dense pool's head-major ``[L, NB, KV, BS, D]`` writes equal the
    token-major pool's, transposed, bit for bit: layers 0 and L-1,
    in-block offsets 0 and BS-1, an inactive slot (position -1: dropped),
    a prompt chunk starting mid-block, and a CoW block copy."""
    L, NB, BS, KV, D, MB = 3, 16, 8, 2, 16, 4
    layer, off = layer % L, off % BS
    rng = np.random.default_rng(6)
    old = jnp.asarray(rng.normal(size=(L, NB, BS, KV, D)), dtype)
    new = jnp.swapaxes(old, 2, 3)
    bt = jnp.asarray(rng.permutation(NB)[:3 * MB].reshape(3, MB), jnp.int32)
    if case.startswith("decode"):
        k = jnp.asarray(rng.normal(size=(3, KV, D)), jnp.float32)
        pos = jnp.asarray([2 * BS + off, off, BS + off], jnp.int32)
        if case == "decode-inactive":
            pos = pos.at[1].set(-1)
        o = _old_write_decode(old, layer, k, bt, pos)
        n = write_decode_kv(new, jnp.int32(layer), k, bt, pos)
    elif case == "prefill":
        S = 2 * BS
        k = jnp.asarray(rng.normal(size=(3, S, KV, D)), jnp.float32)
        ctx = jnp.asarray([off + S, off + 5, off + BS], jnp.int32)
        o = _old_write_prefill(old, layer, k, bt, ctx, off)
        n = write_prefill_kv(new, jnp.int32(layer), k, bt, ctx,
                             pos_offset=jnp.int32(off))
    else:
        src = jnp.asarray([int(bt[0, 1]), int(bt[1, 0])], jnp.int32)
        dst = jnp.asarray([int(bt[2, 3]), int(bt[2, 2])], jnp.int32)
        o = copy_blocks(jnp.copy(old), src, dst)
        n = copy_blocks(new, src, dst)
    assert not np.array_equal(np.asarray(o, np.float32),
                              np.asarray(old, np.float32))
    np.testing.assert_array_equal(np.asarray(n, np.float32),
                                  np.asarray(jnp.swapaxes(o, 2, 3),
                                             np.float32))
