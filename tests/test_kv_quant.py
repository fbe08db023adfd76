"""Quantized paged KV cache: roundtrip error bounds, quantize-on-write
pool ops, CoW/fork scale carriage, the in-kernel-dequant paged-attention
kernel, and end-to-end int8-vs-bf16 serving parity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_reduced
from repro.core.kv_quant import (copy_blocks_quant,
                                 dequantize_blocks, gather_kv_quant,
                                 make_kv_pool_quant, normalize_kv_cache_dtype,
                                 quantize_blocks, write_decode_kv_quant,
                                 write_prefill_kv_quant)
from repro.core.paged_cache import BlockAllocator
from repro.models import transformer as T
from repro.serving import LLM, SamplingParams

KEY = jax.random.PRNGKey(0)


# ------------------------------------------------------------ roundtrip

def test_roundtrip_error_bounded_by_half_scale():
    """Property (random sweep): for any live value, |x - dq(q(x))| <=
    scale/2 with scale = amax/127 per (block, head)."""
    rng = np.random.default_rng(0)
    for trial in range(25):
        BS, KV, D = (int(rng.integers(1, 17)), int(rng.integers(1, 5)),
                     int(rng.integers(1, 33)))
        mag = 10.0 ** rng.uniform(-3, 3)
        x = jnp.asarray(rng.normal(size=(4, KV, BS, D)) * mag, jnp.float32)
        live = jnp.asarray(rng.random((4, BS)) < 0.8)
        q, scales = quantize_blocks(x, live)
        deq = dequantize_blocks(q, scales)
        err = jnp.abs(jnp.where(live[:, None, :, None], x, 0.0) - deq)
        # worst live element per (block, head) vs that head's scale bound
        bound = (scales / 2 * (1 + 1e-5))[:, :, None, None]
        assert bool(jnp.all(err <= bound)), f"trial {trial}"
        # dead slots quantize to exactly 0
        assert bool(jnp.all(jnp.where(live[:, None, :, None], 0, deq) == 0))


def test_roundtrip_exact_on_int8_grid():
    """Values already on the int8 grid (n * amax/127) survive exactly."""
    rng = np.random.default_rng(1)
    amax = 3.7
    n = rng.integers(-127, 128, size=(2, 8, 2, 16))
    n.flat[0] = 127                          # pin the amax so scale is known
    n = n.transpose(0, 2, 1, 3)              # head-major pages [2, KV, BS, D]
    x = jnp.asarray(n * (amax / 127.0), jnp.float32)
    live = jnp.ones((2, 8), bool)
    q, scales = quantize_blocks(x, live)
    np.testing.assert_allclose(np.asarray(scales), amax / 127.0, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(q).astype(np.int64), n)
    np.testing.assert_allclose(np.asarray(dequantize_blocks(q, scales)),
                               np.asarray(x), rtol=1e-6)


# ------------------------------------------------------------ pool writes

def test_prefill_write_gather_roundtrip():
    """write_prefill_kv_quant + gather_kv_quant reproduces the prompt K
    within the per-block scale bound; junk beyond ctx_len never leaks."""
    L, NB, BS, KV, D = 1, 8, 4, 2, 8
    kq, vq, ks, vs = make_kv_pool_quant(L, NB, BS, KV, D)
    del vq, vs
    bt = jnp.asarray([[3, 5, 1], [2, 6, 0]], jnp.int32)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (2, 10, KV, D))
    ctx = jnp.asarray([10, 6])
    kq, ks = write_prefill_kv_quant(kq, ks, 0, k, bt, ctx)
    g = gather_kv_quant(kq, ks, 0, bt, 10)
    for b, n in enumerate([10, 6]):
        ref = np.asarray(k[b, :n], np.float32)
        err = np.abs(np.asarray(g[b, :n]) - ref)
        # bound: half the per-block scale of the block each token is in
        sc = np.asarray(ks[0])[np.asarray(bt[b])]          # [3, KV]
        bound = sc[np.arange(n) // BS] / 2 * (1 + 1e-5)    # [n, KV]
        assert (err <= bound[:, :, None]).all()
        # beyond ctx_len the masked write produced exact zeros
        assert (np.asarray(g[b, n:]) == 0).all()


def test_prefill_chunked_boundary_merge():
    """A pos_offset write into a half-filled block merges the existing
    live prefix instead of zeroing it (the chunked-prefill boundary)."""
    L, NB, BS, KV, D = 1, 4, 4, 1, 4
    kq, vq, ks, vs = make_kv_pool_quant(L, NB, BS, KV, D)
    del vq, vs
    bt = jnp.asarray([[1, 2]], jnp.int32)
    k = jax.random.normal(jax.random.fold_in(KEY, 2), (1, 6, KV, D))
    ctx = jnp.asarray([6])
    # chunk 1: positions 0..1 (half of block 0); chunk 2: positions 2..5
    kq, ks = write_prefill_kv_quant(kq, ks, 0, k[:, :2], bt, ctx)
    kq, ks = write_prefill_kv_quant(kq, ks, 0, k[:, 2:], bt, ctx,
                                    pos_offset=2)
    g = gather_kv_quant(kq, ks, 0, bt, 6)
    sc = float(np.asarray(ks[0]).max())
    # the merge requantizes the prefix once, so allow 2 half-steps
    np.testing.assert_allclose(np.asarray(g[0]), np.asarray(k[0], np.float32),
                               atol=sc * 1.01)


def test_decode_write_appends_and_rescales():
    """Token-by-token decode writes keep every earlier token in the block
    within the (possibly grown) scale bound; inactive slots are dropped."""
    L, NB, BS, KV, D = 1, 4, 4, 2, 8
    kq, vq, ks, vs = make_kv_pool_quant(L, NB, BS, KV, D)
    del vq, vs
    bt = jnp.asarray([[1, 3], [2, 0]], jnp.int32)
    rng = np.random.default_rng(3)
    toks = jnp.asarray(rng.normal(size=(6, 2, KV, D)) *
                       (1 + np.arange(6))[:, None, None, None], jnp.float32)
    for t in range(6):
        pos = jnp.asarray([t, -1])               # seq 1 inactive throughout
        kq, ks = write_decode_kv_quant(kq, ks, 0, toks[t], bt, pos)
    g = gather_kv_quant(kq, ks, 0, bt, 6)
    sc = np.asarray(ks[0])[np.asarray(bt[0])]                  # [2, KV]
    for t in range(6):
        err = np.abs(np.asarray(g[0, t]) - np.asarray(toks[t, 0], np.float32))
        # growth requantization: <= 1 full step of the block's final scale
        assert (err <= sc[t // BS][:, None] * 1.01).all(), t
    # the inactive sequence's blocks were never touched
    assert (np.asarray(kq[0])[np.asarray(bt[1])] == 0).all()


def test_cow_fork_carries_scales():
    """CoW after a fork copies the scale row with the value block — the
    fork dequantizes its shared prefix identically."""
    bs = 4
    a = BlockAllocator(16, bs)
    ids, _ = a.allocate_prompt(list(range(6)))      # 1 full + 1 partial
    L, NB, KV, D = 2, 16, 1, 8
    kq, vq, ks, vs = make_kv_pool_quant(L, NB, bs, KV, D)
    del vq, vs
    bt = jnp.asarray([ids + [0] * (4 - len(ids))], jnp.int32)
    k = jax.random.normal(jax.random.fold_in(KEY, 4), (1, 6, KV, D))
    for layer in range(L):
        kq, ks = write_prefill_kv_quant(kq, ks, layer, k, bt,
                                        jnp.asarray([6]))
    before = np.asarray(gather_kv_quant(kq, ks, 1, bt, 6))
    fork = a.fork_sequence(ids)
    grown, cow = a.grow(fork, 6, 1)
    src, dst = cow
    assert src == ids[-1] and dst == grown[-1]
    kq, ks = copy_blocks_quant(kq, ks, jnp.asarray([src], jnp.int32),
                               jnp.asarray([dst], jnp.int32))
    bt_fork = jnp.asarray([grown + [0] * (4 - len(grown))], jnp.int32)
    after = np.asarray(gather_kv_quant(kq, ks, 1, bt_fork, 6))
    np.testing.assert_array_equal(before, after)
    # scale rows really moved (the tail block's scale is non-trivial)
    np.testing.assert_array_equal(np.asarray(ks[:, dst]),
                                  np.asarray(ks[:, src]))
    assert float(np.abs(np.asarray(ks[:, dst])).max()) > 0


# ------------------------------------------------- head-major layout

def _old_quantize(x, live):
    """The token-major ``[..., BS, KV, D]`` quantizer the pool had before
    its pages became head-major: the reference for the layout tests."""
    xf = jnp.where(live[..., None, None], x.astype(jnp.float32), 0.0)
    amax = jnp.max(jnp.abs(xf), axis=(-3, -1))
    scales = jnp.maximum(amax, 1e-20) / 127.0
    q = jnp.clip(jnp.round(xf / scales[..., None, :, None]), -127.0, 127.0)
    return q.astype(jnp.int8), scales


def _old_dequantize(q, scales):
    return q.astype(jnp.float32) * scales[..., None, :, None]


def _old_write_decode(values, scales, layer, k_new, bt, positions):
    """Decode write into a token-major ``[L, NB, BS, KV, D]`` pool,
    through the layer slice as before."""
    NB, bs = values.shape[1], values.shape[2]
    valid = positions >= 0
    pos = jnp.maximum(positions, 0)
    blk = jnp.take_along_axis(bt, (pos // bs)[:, None], axis=1)[:, 0]
    off = pos % bs
    lp, ls = values[layer], scales[layer]
    old = _old_dequantize(lp[blk], ls[blk])
    slot = jnp.arange(bs)[None, :]
    buf = jnp.where((slot < off[:, None])[..., None, None], old, 0.0)
    buf = jnp.where((slot == off[:, None])[..., None, None],
                    k_new[:, None].astype(jnp.float32), buf)
    q, sc = _old_quantize(buf, slot <= off[:, None])
    tgt = jnp.where(valid, blk, NB)
    lp = lp.at[tgt].set(q, mode="drop")
    ls = ls.at[tgt].set(sc, mode="drop")
    return values.at[layer].set(lp), scales.at[layer].set(ls)


def _old_write_prefill(values, scales, layer, k, bt, ctx_lens, pos_offset):
    """Prefill (chunk) write into a token-major pool, as before."""
    B, S, KV, D = k.shape
    NB, bs = values.shape[1], values.shape[2]
    nb = -(-S // bs) + 1
    j0 = pos_offset // bs
    lead = pos_offset - j0 * bs
    buf = jnp.zeros((B, nb * bs, KV, D), jnp.float32)
    buf = jax.lax.dynamic_update_slice(buf, k.astype(jnp.float32),
                                       (0, lead, 0, 0))
    buf = buf.reshape(B, nb, bs, KV, D)
    pos = (j0 * bs + jnp.arange(nb * bs)).reshape(nb, bs)
    live = (pos[None] >= pos_offset) & (pos[None] < ctx_lens[:, None, None])
    lp, ls = values[layer], scales[layer]
    btp = jnp.concatenate([bt, jnp.full((B, nb), NB, bt.dtype)], axis=1)
    blk = jax.lax.dynamic_slice_in_dim(btp, j0, nb, axis=1)
    safe0 = jnp.minimum(blk[:, 0], NB - 1)
    old = _old_dequantize(lp[safe0], ls[safe0])
    old_live = ((jnp.arange(bs)[None] < lead)
                & (pos[0][None] < ctx_lens[:, None]))
    buf = buf.at[:, 0].add(jnp.where(old_live[..., None, None], old, 0.0))
    live = live.at[:, 0].set(live[:, 0] | old_live)
    q, sc = _old_quantize(buf, live)
    tgt = jnp.where(live.any(-1), blk, NB)
    lp = lp.at[tgt].set(q, mode="drop")
    ls = ls.at[tgt].set(sc, mode="drop")
    return values.at[layer].set(lp), scales.at[layer].set(ls)


@pytest.mark.parametrize("case,layer,off", [
    ("decode", 0, 0), ("decode", -1, 0), ("decode", 0, -1),
    ("decode", -1, -1), ("decode-inactive", -1, -1),
    ("prefill", 0, 0), ("prefill", -1, -1), ("prefill-chunk", -1, -1),
    ("cow", -1, -1)])
def test_head_major_writes_equal_token_major_transposed(case, layer, off):
    """The head-major ``[L, NB, KV, BS, D]`` writes give int8 values and
    f32 scales bitwise equal to the token-major ``[L, NB, BS, KV, D]``
    pool's, transposed: layers 0 and L-1, in-block offsets 0 and BS-1,
    an inactive slot (position -1: write dropped), a chunk boundary that
    merges a live prefix, and a CoW block copy."""
    L, NB, BS, KV, D, MB = 3, 16, 8, 2, 16, 4
    layer, off = layer % L, off % BS
    rng = np.random.default_rng(5)
    # a pool with live contents in every block, token-major
    old_v, old_s = _old_quantize(
        jnp.asarray(rng.normal(size=(L, NB, BS, KV, D)), jnp.float32),
        jnp.ones((L, NB, BS), bool))

    def head_major(v):
        return jnp.swapaxes(v, 2, 3)
    new_v = head_major(old_v)
    bt = jnp.asarray(rng.permutation(NB)[:3 * MB].reshape(3, MB), jnp.int32)
    if case.startswith("decode"):
        k = jnp.asarray(rng.normal(size=(3, KV, D)) * 3, jnp.float32)
        pos = jnp.asarray([2 * BS + off, off, BS + off], jnp.int32)
        if case == "decode-inactive":
            pos = pos.at[1].set(-1)
        ov, os_ = _old_write_decode(old_v, old_s, layer, k, bt, pos)
        nv, ns = write_decode_kv_quant(new_v, old_s, jnp.int32(layer), k,
                                       bt, pos)
    elif case.startswith("prefill"):
        S = 2 * BS
        start = off if case == "prefill-chunk" else 0
        k = jnp.asarray(rng.normal(size=(3, S, KV, D)), jnp.float32)
        ctx = jnp.asarray([start + S, start + 5, start + BS], jnp.int32)
        ov, os_ = _old_write_prefill(old_v, old_s, layer, k, bt, ctx, start)
        nv, ns = write_prefill_kv_quant(new_v, old_s, jnp.int32(layer), k,
                                        bt, ctx, pos_offset=jnp.int32(start))
    else:
        src = jnp.asarray([int(bt[0, 1]), int(bt[1, 0])], jnp.int32)
        dst = jnp.asarray([int(bt[2, 3]), int(bt[2, 2])], jnp.int32)
        ov, os_ = copy_blocks_quant(jnp.copy(old_v), jnp.copy(old_s), src,
                                    dst)
        nv, ns = copy_blocks_quant(new_v, jnp.copy(old_s), src, dst)
    assert not np.array_equal(np.asarray(ov), np.asarray(old_v))
    np.testing.assert_array_equal(np.asarray(nv), np.asarray(head_major(ov)))
    np.testing.assert_array_equal(np.asarray(ns), np.asarray(os_))


# ------------------------------------------------------------ kernel

@pytest.mark.parametrize("use_alibi,walk", [
    pytest.param(False, False, id="False"),
    pytest.param(True, False, id="True"),
    # head dim 128: the manual live-page walk, 16 x 32 KB int8 pages per
    # block against a 20-entry table, at the last layer, over its edge
    # lengths, with garbage table entries past the live pages
    pytest.param(False, True, id="walk-edges"),
    pytest.param(True, True, id="walk-alibi-window"),
])
def test_paged_attention_quant_kernel_matches_ref(use_alibi, walk):
    """Interpret-mode Pallas kernel (in-register dequant) == dequantizing
    XLA reference."""
    from repro.core.alibi import alibi_slopes
    from repro.kernels.paged_attention_quant import paged_attention_quant
    from repro.kernels.ref import paged_attention_quant_ref
    slopes = alibi_slopes(8) if use_alibi else None
    if not walk:
        B, H, KV, D, NB, BS, MB = 3, 8, 2, 16, 16, 8, 4
        q = jax.random.normal(jax.random.fold_in(KEY, 5), (B, H, D),
                              jnp.float32)
        kraw = jax.random.normal(jax.random.fold_in(KEY, 6),
                                 (1, NB, KV, BS, D))
        vraw = jax.random.normal(jax.random.fold_in(KEY, 7),
                                 (1, NB, KV, BS, D))
        full = jnp.ones((1, NB, BS), bool)
        kq, ks = quantize_blocks(kraw, full)
        vq, vs = quantize_blocks(vraw, full)
        bt = jnp.asarray(np.random.default_rng(0).permutation(NB)[:B * MB]
                         .reshape(B, MB), jnp.int32)
        sl = jnp.asarray([17, 8, 30], jnp.int32)
        out = paged_attention_quant(q, kq, ks, vq, vs, 0, bt, sl, slopes,
                                    interpret=True)
        ref = paged_attention_quant_ref(q, kq, ks, vq, vs, 0, bt, sl,
                                        alibi_slopes=slopes)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)
        return
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels.paged_attention import pages_per_block
    B, H, KV, D, BS, MB, L = 7, 8, 2, 128, 128, 20, 2
    NB = B * MB + 2
    P = pages_per_block(KV, BS, D, 1, MB)
    assert 1 < P < MB and MB % P
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.bfloat16)
    kq = jnp.asarray(rng.integers(-127, 128, (L, NB, KV, BS, D)), jnp.int8)
    vq = jnp.asarray(rng.integers(-127, 128, (L, NB, KV, BS, D)), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.001, 0.02, (L, NB, KV)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.001, 0.02, (L, NB, KV)), jnp.float32)
    bt = rng.permutation(NB)[:B * MB].reshape(B, MB).astype(np.int32)
    # no tokens, one whole block and a token past it, a full table, one
    # token, one block less a token, the second block's last page
    sl = np.asarray([0, P * BS, P * BS + 1, MB * BS, 1, P * BS - 1,
                     MB * BS - 5], np.int32)
    live = -(-sl // BS)
    junk = np.where(np.arange(MB)[None] >= live[:, None],
                    np.where(np.arange(B) % 2 == 0, NB + 1000, -5)[:, None],
                    bt)
    win = 300 if use_alibi else 0
    out = paged_attention_quant(
        q, kq, ks, vq, vs, L - 1, jnp.asarray(junk), jnp.asarray(sl), slopes,
        sliding_window=win,
        interpret=pltpu.InterpretParams(out_of_bounds_reads="raise"))
    ref = paged_attention_quant_ref(q, kq, ks, vq, vs, L - 1,
                                    jnp.asarray(bt), jnp.asarray(sl),
                                    alibi_slopes=slopes, sliding_window=win)
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    np.testing.assert_allclose(out[sl > 0], ref[sl > 0], atol=2e-2)
    assert not out[sl == 0].any()       # a slot with no tokens: zeros


# ------------------------------------------------------------ end to end

def _generate(kv_cache_dtype, prompts, *, max_horizon=8, temperature=0.0,
              max_tokens=12, num_blocks=64):
    llm = LLM.load("qwen1.5-0.5b", reduced=True,
                   kv_cache_dtype=kv_cache_dtype, max_horizon=max_horizon,
                   max_slots=3, num_blocks=num_blocks, max_blocks_per_seq=8,
                   prefill_bucket=16, overrides={"num_layers": 2})
    res = llm.generate(prompts, SamplingParams(temperature=temperature,
                                               max_tokens=max_tokens))
    return [o.token_ids for o in res], llm


def _prompts(n, seed=0, lo=4, hi=20):
    rng = np.random.default_rng(seed)
    return [list(rng.integers(1, 200, int(rng.integers(lo, hi))))
            for _ in range(n)]


def test_int8_greedy_parity_with_bf16():
    """Acceptance: greedy generations through the int8 KV cache match the
    bf16 oracle token-for-token on the reduced config (the quantization
    error is far below the reduced model's logit margins)."""
    prompts = _prompts(5, seed=11)
    o_bf16, llm_bf = _generate("bf16", prompts)
    o_int8, llm_i8 = _generate("int8", prompts)
    assert o_bf16 == o_int8
    # and the memory win is real: >= 1.8x fewer KV pool bytes
    ratio = (llm_bf.engine.runner.kv_pool_bytes()
             / llm_i8.engine.runner.kv_pool_bytes())
    assert ratio >= 1.8, ratio


def test_int8_horizon_matches_per_token_bitwise():
    """Within int8 mode a multi-token megastep horizon and one decode
    dispatch per token (``max_horizon=1``) remain bitwise-identical
    (same quantize-on-write ops, same sampling streams), including under
    temperature sampling."""
    prompts = _prompts(4, seed=7)
    for temp in (0.0, 0.9):
        one, _ = _generate("int8", prompts, max_horizon=1, temperature=temp)
        fus, _ = _generate("int8", prompts, temperature=temp)
        assert one == fus, f"temperature={temp}"


def test_int8_preemption_recompute_parity():
    """Recompute-style preemption refills fresh blocks (overwritten
    scales) — a block-starved int8 run matches a roomy one."""
    prompts = _prompts(4, seed=11, lo=17, hi=30)
    roomy, _ = _generate("int8", prompts, max_tokens=32, num_blocks=256)
    tight, llm = _generate("int8", prompts, max_tokens=32, num_blocks=9)
    assert llm.engine.metrics["preemptions"] > 0
    assert roomy == tight


def test_int8_rejects_sliding_window_archs():
    with pytest.raises(ValueError, match="sliding"):
        T.make_decode_state(get_reduced("h2o-danube-3-4b"), 2, 8, 2,
                            kv_cache_dtype="int8")


def test_int8_rejects_attention_free_archs():
    """No silent no-op: an SSM model has no paged KV cache, so asking for
    int8 KV must fail loudly instead of quietly quantizing nothing."""
    with pytest.raises(ValueError, match="no attention KV cache"):
        T.make_decode_state(get_reduced("falcon-mamba-7b"), 2, 8, 2,
                            kv_cache_dtype="int8")


def test_kv_cache_dtype_validation():
    assert normalize_kv_cache_dtype(None) == "bf16"
    assert normalize_kv_cache_dtype("bfloat16") == "bf16"
    assert normalize_kv_cache_dtype("int8") == "int8"
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        LLM.load("qwen1.5-0.5b", reduced=True, kv_cache_dtype="int4")
