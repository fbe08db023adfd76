"""Hypothesis property tests on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="hypothesis not installed (CI pins CPU jax only)")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.configs.base import QuantConfig
from repro.core.gqa import grouped_attention
from repro.core.gptq import gptq_quantize
from repro.core.paged_cache import BlockAllocator
from repro.core.quant import pack_int4, unpack_int4

SET = dict(max_examples=25, deadline=None)


@settings(**SET)
@given(st.integers(1, 3), st.integers(2, 24), st.integers(1, 4),
       st.integers(1, 4), st.data())
def test_attention_is_convex_combination(B, S, KV, G, data):
    """Every output lies in the convex hull of V rows -> bounded by V."""
    H = KV * G
    D = 8
    seed = data.draw(st.integers(0, 2**30))
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, KV, D))
    v = jax.random.normal(ks[2], (B, S, KV, D))
    o = grouped_attention(q, k, v, causal=True)
    assert float(o.max()) <= float(v.max()) + 1e-4
    assert float(o.min()) >= float(v.min()) - 1e-4


@settings(**SET)
@given(st.integers(0, 2**30), st.integers(1, 16), st.integers(1, 30))
def test_pack_roundtrip_property(seed, dout, din):
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, 16, size=(din, dout)).astype(np.uint8)
    got = np.asarray(unpack_int4(jnp.asarray(pack_int4(codes)), din))
    np.testing.assert_array_equal(got, codes)


@settings(**SET)
@given(st.integers(0, 2**30), st.lists(st.integers(1, 40), min_size=1,
                                       max_size=12))
def test_allocator_conservation(seed, lens):
    """free + live == total, always; free-all restores everything."""
    a = BlockAllocator(256, 4, watermark_frac=0.0)
    rng = np.random.default_rng(seed)
    live = []
    for n in lens:
        toks = rng.integers(0, 50, n).tolist()
        ids, _ = a.allocate_prompt(toks)
        live.append(ids)
    # physical-block conservation (shared blocks counted once)
    phys = {b for ids in live for b in ids}
    assert a.num_free + len(phys) == a.num_blocks
    for ids in live:
        a.free_sequence(ids)
    assert a.num_free == a.num_blocks


@settings(**SET)
@given(st.integers(0, 2**30))
def test_gptq_monotone_bits(seed):
    """More bits never increases quantization error."""
    rng = np.random.default_rng(seed)
    w = rng.normal(size=(16, 8))
    errs = []
    for bits in (2, 4, 8):
        qt = gptq_quantize(w, None, QuantConfig(bits=bits, group_size=16,
                                                act_order=False))
        errs.append(np.abs(qt.dequant() - w).mean())
    assert errs[0] >= errs[1] >= errs[2]


@settings(**SET)
@given(st.integers(0, 2**30), st.integers(1, 64))
def test_prefix_reuse_shares_only_full_blocks(seed, n):
    a = BlockAllocator(128, 4)
    rng = np.random.default_rng(seed)
    p = rng.integers(0, 9, n).tolist()
    ids1, _ = a.allocate_prompt(p)
    ids2, reused = a.allocate_prompt(p)
    assert reused == n // 4                  # all full blocks shared
    full = n // 4
    assert ids1[:full] == ids2[:full]
    if n % 4:
        assert ids1[full] != ids2[full]      # partial tails never shared


@settings(**SET)
@given(st.integers(0, 2**30), st.integers(1, 16), st.integers(1, 4),
       st.integers(1, 32), st.floats(-4, 4))
def test_kv_quant_roundtrip_bounded(seed, BS, KV, D, log_mag):
    """int8 KV roundtrip: every live value within scale/2; dead slots and
    on-grid values exact."""
    from repro.core.kv_quant import dequantize_blocks, quantize_blocks
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(2, KV, BS, D)) * 10.0 ** log_mag,
                    jnp.float32)
    live = jnp.asarray(rng.random((2, BS)) < 0.7)
    q, scales = quantize_blocks(x, live)
    deq = dequantize_blocks(q, scales)
    err = jnp.abs(jnp.where(live[:, None, :, None], x, 0.0) - deq)
    assert bool(jnp.all(err <= (scales / 2 * (1 + 1e-5))[:, :, None, None]))
    assert bool(jnp.all(jnp.where(live[:, None, :, None], 0.0, deq) == 0))
    # a second pass over the dequantized values is a fixed point when the
    # scale is unchanged (round(int) == int) -- no drift without growth
    q2, scales2 = quantize_blocks(deq, live)
    np.testing.assert_array_equal(np.asarray(q2), np.asarray(q))
