"""Pallas kernels vs ref.py oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.configs.base import QuantConfig
from repro.core.alibi import alibi_slopes
from repro.core.gptq import gptq_quantize
from repro.core.quant import make_quant_params
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gptq_matmul import gptq_matmul
from repro.kernels.paged_attention import (paged_attention,
                                           pages_per_block)

TOL = {jnp.float32: 5e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("B,S,H,KV,D", [
    (2, 64, 8, 2, 32), (1, 96, 4, 4, 16), (2, 128, 12, 2, 64),
    (1, 64, 16, 1, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("alibi,win", [(False, 0), (True, 0), (True, 24)])
def test_flash_attention_sweep(B, S, H, KV, D, dtype, alibi, win):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, D), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, D), dtype)
    sl = alibi_slopes(H) if alibi else None
    o = flash_attention(q, k, v, sl, causal=True, sliding_window=win,
                        block_q=32, block_k=32, interpret=True)
    r = ref.flash_attention_ref(q, k, v, causal=True, sliding_window=win,
                                alibi_slopes=sl)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), atol=TOL[dtype])


# the TPU interpreter, which raises on any out-of-bounds read: a decode
# kernel that DMAs a page through a table entry past the live pages
# (set to out-of-range garbage below) fails here
OOB = pltpu.InterpretParams(out_of_bounds_reads="raise")


def _walk_cases(B, P, BS, MB, rng):
    """Sequence lengths that hit the live-page walk's edges (a slot
    with no tokens, one and two whole P-page blocks and one token past
    them, a full table), then random ones."""
    edges = [0, P * BS, P * BS + 1, 2 * P * BS, 2 * P * BS + 1, MB * BS, 1]
    edges = [n for n in edges if n <= MB * BS]
    rand = rng.integers(1, MB * BS + 1, B)
    return np.asarray((edges + list(rand))[:B], np.int32)


def _garbage_tail(bt, sl, BS, NB):
    """The table with every entry at or past a slot's live page count
    set to an out-of-range page id (the kernel must never read them)."""
    bt = np.array(bt)
    live = -(-np.asarray(sl) // BS)
    cols = np.arange(bt.shape[1])[None, :]
    junk = np.where(np.arange(bt.shape[0]) % 2 == 0, NB + 1000, -5)[:, None]
    return jnp.asarray(np.where(cols >= live[:, None], junk, bt), jnp.int32)


def _check_decode(o, r, sl, atol):
    """Kernel vs reference on live slots; a slot with no tokens gives
    zeros (the reference averages over masked positions there)."""
    o, r = np.asarray(o, np.float32), np.asarray(r, np.float32)
    live = np.asarray(sl) > 0
    np.testing.assert_allclose(o[live], r[live], atol=atol)
    assert not o[~live].any()


@pytest.mark.parametrize("B,H,KV,D,BS,MB", [
    (3, 8, 2, 32, 8, 4), (2, 4, 4, 16, 16, 3), (2, 12, 2, 64, 8, 6),
    (1, 8, 1, 128, 16, 2),
    # head dim 128: the manual live-page walk, with P = MB (16-token
    # pages), and with P below MB and MB not a multiple of it (64-token
    # pages: P = 4 in f32, 8 in bf16)
    (7, 8, 2, 128, 16, 5), (7, 16, 4, 128, 64, 10),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_sweep(B, H, KV, D, BS, MB, dtype):
    """Decode kernel vs reference at the last layer of a two-layer
    pool, over the walk's edge lengths, with garbage table entries past
    each slot's live pages."""
    L, NB = 2, B * MB + 2
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    kp = jax.random.normal(ks[1], (L, NB, KV, BS, D), dtype)
    vp = jax.random.normal(ks[2], (L, NB, KV, BS, D), dtype)
    bt = jax.random.permutation(ks[3], NB)[:B * MB].reshape(B, MB)
    bt = bt.astype(jnp.int32)
    P = pages_per_block(KV, BS, D, jnp.dtype(dtype).itemsize, MB) \
        if D % 128 == 0 else 1
    sl = jnp.asarray(_walk_cases(B, P, BS, MB, np.random.default_rng(0)))
    o = paged_attention(q, kp, vp, L - 1, _garbage_tail(bt, sl, BS, NB), sl,
                        interpret=OOB)
    r = ref.paged_attention_ref(q, kp, vp, L - 1, bt, sl)
    _check_decode(o, r, sl, TOL[dtype])


def test_paged_attention_alibi_and_window():
    """ALiBi + sliding window through both page walks: the grid's (head
    dim 32) and the live-page walk's (head dim 128)."""
    B, H, KV, BS, MB = 2, 8, 2, 8, 5
    NB = B * MB
    for D in (32, 128):
        ks = jax.random.split(jax.random.PRNGKey(2), 4)
        q = jax.random.normal(ks[0], (B, H, D))
        kp = jax.random.normal(ks[1], (1, NB, KV, BS, D))
        vp = jax.random.normal(ks[2], (1, NB, KV, BS, D))
        bt = jnp.arange(NB, dtype=jnp.int32).reshape(B, MB)
        sl = jnp.array([37, 12], jnp.int32)
        slo = alibi_slopes(H)
        o = paged_attention(q, kp, vp, 0, bt, sl, slo, sliding_window=16,
                            interpret=True)
        r = ref.paged_attention_ref(q, kp, vp, 0, bt, sl, alibi_slopes=slo,
                                    sliding_window=16)
        np.testing.assert_allclose(o, r, atol=5e-5)


@pytest.mark.parametrize("q_off", [0, 8, 5, 11])   # 0 / block-aligned /
@pytest.mark.parametrize("alibi,win", [(False, 0), (True, 0),  # unaligned
                                       (False, 12)])
@pytest.mark.parametrize("quant", [False, True])
def test_flash_attention_chunk_dynamic_offset(q_off, alibi, win, quant):
    """The dynamic-offset chunk kernel (scalar-prefetch q_offset /
    total_len, paged-pool page walk + raw chunk overlay, in-register int8
    dequant) matches the bounded-gather XLA oracle across chunk offsets,
    ALiBi, sliding window, and both pool formats — interpret mode, so the
    Pallas path is exercised without TPU hardware."""
    from repro.kernels.flash_attention import flash_attention_chunk
    rng = np.random.default_rng(3 + q_off)
    L, NB, BS, KV, D, H, MB, W = 1, 12, 8, 2, 16, 4, 6, 16
    total = q_off + int(rng.integers(1, W + 1))
    q = jnp.asarray(rng.normal(size=(1, W, H, D)), jnp.float32)
    kr = jnp.asarray(rng.normal(size=(1, W, KV, D)), jnp.float32)
    vr = jnp.asarray(rng.normal(size=(1, W, KV, D)), jnp.float32)
    bt = jnp.asarray(rng.permutation(NB)[:MB][None], jnp.int32)
    if quant:
        kp = jnp.asarray(rng.integers(-127, 128, (L, NB, KV, BS, D)),
                         jnp.int8)
        vp = jnp.asarray(rng.integers(-127, 128, (L, NB, KV, BS, D)),
                         jnp.int8)
        ks = jnp.asarray(rng.uniform(0.01, 0.1, (L, NB, KV)), jnp.float32)
        vs = jnp.asarray(rng.uniform(0.01, 0.1, (L, NB, KV)), jnp.float32)
    else:
        kp = jnp.asarray(rng.normal(size=(L, NB, KV, BS, D)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(L, NB, KV, BS, D)), jnp.float32)
        ks = vs = None
    sl = alibi_slopes(H) if alibi else None
    o = flash_attention_chunk(
        q, kp, vp, jnp.int32(0), bt, jnp.int32(q_off), jnp.int32(total),
        kr, vr, sl, k_scales=ks, v_scales=vs, sliding_window=win,
        block_q=8, interpret=True)
    r = ref.chunk_prefill_attention_ref(
        q, kp, vp, ks, vs, 0, bt, jnp.int32(q_off), jnp.int32(total),
        kr, vr, alibi_slopes=sl, sliding_window=win)
    live = total - q_off            # padded q rows are garbage on both
    np.testing.assert_allclose(np.asarray(o[:, :live], np.float32),
                               np.asarray(r[:, :live], np.float32),
                               atol=5e-5)


def test_flash_attention_chunk_one_compile_across_offsets():
    """q_offset / total_len / layer are traced operands: every chunk
    shape of a serving run, at every layer, hits one executable (the
    whole point of the variant)."""
    from repro.kernels.flash_attention import flash_attention_chunk
    rng = np.random.default_rng(7)
    NB, BS, KV, D, H, MB, W = 8, 8, 2, 16, 4, 4, 8
    q = jnp.asarray(rng.normal(size=(1, W, H, D)), jnp.float32)
    kr = jnp.asarray(rng.normal(size=(1, W, KV, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(2, NB, KV, BS, D)), jnp.float32)
    bt = jnp.arange(MB, dtype=jnp.int32)[None]
    before = flash_attention_chunk._cache_size()
    for off, layer in ((0, 0), (3, 1), (8, 0), (17, 1)):
        flash_attention_chunk(q, kp, kp, jnp.int32(layer), bt,
                              jnp.int32(off), jnp.int32(off + 5), kr, kr,
                              None, block_q=8, interpret=True)
    assert flash_attention_chunk._cache_size() - before == 1


@pytest.mark.parametrize("kind", ["decode", "chunk"])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_kernels_read_stacked_pool_at_layer(kind, quant):
    """The decode and chunk kernels read layer ``layer != 0`` of the
    stacked ``[L, NB, KV, BS, D]`` pool in place: they match the
    reference at that layer, and the reference over that layer's slice
    alone, so no other layer's pages leak in."""
    from repro.kernels.flash_attention import flash_attention_chunk
    from repro.kernels.paged_attention_quant import paged_attention_quant
    rng = np.random.default_rng(11)
    L, NB, BS, KV, D, H, MB, layer = 3, 16, 8, 2, 16, 4, 5, 2
    # int8 pages meet f32 queries (as in the int8 tests above); the dense
    # pool is bf16 throughout
    dtype = jnp.float32 if quant else jnp.bfloat16
    if quant:
        kp = jnp.asarray(rng.integers(-127, 128, (L, NB, KV, BS, D)),
                         jnp.int8)
        vp = jnp.asarray(rng.integers(-127, 128, (L, NB, KV, BS, D)),
                         jnp.int8)
        ks = jnp.asarray(rng.uniform(0.01, 0.1, (L, NB, KV)), jnp.float32)
        vs = jnp.asarray(rng.uniform(0.01, 0.1, (L, NB, KV)), jnp.float32)
        one = (kp[layer][None], vp[layer][None], ks[layer][None],
               vs[layer][None])
    else:
        kp = jnp.asarray(rng.normal(size=(L, NB, KV, BS, D)), dtype)
        vp = jnp.asarray(rng.normal(size=(L, NB, KV, BS, D)), dtype)
        ks = vs = None
        one = (kp[layer][None], vp[layer][None], None, None)
    slo = alibi_slopes(H)
    if kind == "decode":
        B = 3
        q = jnp.asarray(rng.normal(size=(B, H, D)), dtype)
        bt = jnp.asarray(rng.permutation(NB)[:B * MB].reshape(B, MB),
                         jnp.int32)
        sl = jnp.asarray([17, 1, MB * BS], jnp.int32)
        if quant:
            o = paged_attention_quant(q, kp, ks, vp, vs, jnp.int32(layer),
                                      bt, sl, slo, interpret=True)
            rs = [ref.paged_attention_quant_ref(
                q, k, kscale, v, vscale, li, bt, sl, alibi_slopes=slo)
                for (k, v, kscale, vscale), li in (((kp, vp, ks, vs), layer),
                                                   (one, 0))]
        else:
            o = paged_attention(q, kp, vp, jnp.int32(layer), bt, sl, slo,
                                interpret=True)
            rs = [ref.paged_attention_ref(q, k, v, li, bt, sl,
                                          alibi_slopes=slo)
                  for (k, v, _, _), li in (((kp, vp, None, None), layer),
                                           (one, 0))]
        live = slice(None)
    else:
        W, q_off = 16, 13
        total = q_off + 11
        q = jnp.asarray(rng.normal(size=(1, W, H, D)), dtype)
        kr = jnp.asarray(rng.normal(size=(1, W, KV, D)), dtype)
        vr = jnp.asarray(rng.normal(size=(1, W, KV, D)), dtype)
        bt = jnp.asarray(rng.permutation(NB)[:MB][None], jnp.int32)
        o = flash_attention_chunk(
            q, kp, vp, jnp.int32(layer), bt, jnp.int32(q_off),
            jnp.int32(total), kr, vr, slo, k_scales=ks, v_scales=vs,
            block_q=8, interpret=True)
        rs = [ref.chunk_prefill_attention_ref(
            q, k, v, kscale, vscale, li, bt, jnp.int32(q_off),
            jnp.int32(total), kr, vr, alibi_slopes=slo)
            for (k, v, kscale, vscale), li in (((kp, vp, ks, vs), layer),
                                               (one, 0))]
        live = (slice(None), slice(0, total - q_off))
    for r in rs:
        np.testing.assert_allclose(np.asarray(o[live], np.float32),
                                   np.asarray(r[live], np.float32),
                                   atol=TOL[dtype])


@pytest.mark.parametrize("M,K,N,gs", [(16, 64, 32, 32), (8, 128, 48, 128),
                                      (32, 256, 128, 64), (5, 64, 17, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gptq_matmul_sweep(rng, M, K, N, gs, dtype):
    w = rng.normal(size=(K, N))
    qt = gptq_quantize(w, None, QuantConfig(group_size=gs, act_order=False))
    p = make_quant_params(qt)
    x = jnp.asarray(rng.normal(size=(M, K)), dtype)
    y = gptq_matmul(x, p["qweight"], p["scales"], p["zeros"], interpret=True)
    r = ref.quant_matmul_ref(x, p)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(r, np.float32),
                               rtol=2e-2, atol=TOL[dtype] * np.abs(np.asarray(r)).max())
