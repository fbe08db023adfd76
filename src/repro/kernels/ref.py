"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

These are the *semantic* definitions; kernels must match them to
``assert_allclose`` tolerance across the test shape/dtype sweep. They are
also the path the multi-pod dry-run lowers (Pallas TPU kernels cannot
lower on the CPU backend — see DESIGN.md §3).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.core.gqa import decode_attention, grouped_attention
from repro.core.paged_cache import gather_kv
from repro.core.quant import quant_matmul_ref as _qmm


def flash_attention_ref(q, k, v, *, causal=True, sliding_window=0,
                        alibi_slopes=None, q_offset=0, segment_ids=None):
    """[B,S,H,D] x [B,S,KV,D]^2 -> [B,S,H,D]; O(S^2) reference."""
    del segment_ids
    return grouped_attention(q, k, v, causal=causal,
                             sliding_window=sliding_window,
                             alibi_slopes=alibi_slopes, q_offset=q_offset)


def paged_attention_ref(q, k_pool, v_pool, layer, block_table, seq_lens, *,
                        alibi_slopes=None, sliding_window=0):
    """Decode attention over layer ``layer`` of the paged pool.

    q: [B, H, D]; k_pool/v_pool: [L, NB, KV, BS, D] (the stacked pool);
    layer: scalar; block_table: [B, MB]; seq_lens: [B].
    """
    bs = k_pool.shape[3]
    max_len = block_table.shape[1] * bs
    kc = gather_kv(k_pool, layer, block_table, max_len)
    vc = gather_kv(v_pool, layer, block_table, max_len)
    return decode_attention(q, kc, vc, seq_lens, alibi_slopes=alibi_slopes,
                            sliding_window=sliding_window)


def paged_attention_quant_ref(q, k_values, k_scales, v_values, v_scales,
                              layer, block_table, seq_lens, *,
                              alibi_slopes=None, sliding_window=0):
    """Decode attention over layer ``layer`` of the int8 paged pool:
    dequantize the gathered pages (per-block-per-head scales), then the
    same contiguous oracle.

    q: [B, H, D]; k_values/v_values: [L, NB, KV, BS, D] int8 (stacked);
    k_scales/v_scales: [L, NB, KV] f32; layer: scalar; block_table:
    [B, MB]; seq_lens: [B].
    """
    from repro.core.kv_quant import gather_kv_quant
    bs = k_values.shape[3]
    max_len = block_table.shape[1] * bs
    kc = gather_kv_quant(k_values, k_scales, layer, block_table, max_len)
    vc = gather_kv_quant(v_values, v_scales, layer, block_table, max_len)
    return decode_attention(q, kc, vc, seq_lens, alibi_slopes=alibi_slopes,
                            sliding_window=sliding_window)


def chunk_prefill_attention_ref(q, k_pool, v_pool, k_scales, v_scales,
                                layer, block_table, q_offset, total_len,
                                k_raw, v_raw, *, alibi_slopes=None,
                                sliding_window=0):
    """Chunk-prefill attention over the paged pool (XLA oracle).

    The semantic definition of ``flash_attention_chunk``: gather the
    pool's live pages (a *bounded* walk — ``ceil(total_len / BS)`` page
    reads via ``kv_gather_bounded``, never the table capacity), overlay
    the chunk's own raw K/V at ``[q_offset, q_offset + W)`` so the chunk
    never sees itself pool-roundtripped (int8 parity), then the O(S^2)
    grouped-attention reference with the traced ``q_offset`` driving the
    causal mask.  This is also the lowering the serving engine runs off
    TPU and the multi-pod dry-run compiles.

    q: [1, W, H, D]; k_pool/v_pool: [L, NB, KV, BS, D] (int8 when scales
    are given, with k_scales/v_scales [L, NB, KV] f32); layer: traced
    index; block_table: [1, MB]; q_offset/total_len: traced i32 scalars;
    k_raw/v_raw: [1, W, KV, D].
    """
    from repro.core.kv_quant import KVCache, kv_gather_bounded
    cache = KVCache(k_pool, v_pool, k_scales, v_scales)
    bs = cache.block_size
    cap = block_table.shape[1] * bs
    W = q.shape[1]
    live = (jnp.asarray(total_len, jnp.int32) + bs - 1) // bs
    kc, vc = kv_gather_bounded(cache, layer, block_table, cap, live,
                               q.dtype)
    # raw overlay: the W-row scratch tail keeps the dynamic write from
    # clamping when a chunk ends at capacity (same trick as the serving
    # chunk executable always used).
    scratch = jnp.zeros((1, W) + kc.shape[2:], kc.dtype)
    kc = jax.lax.dynamic_update_slice(
        jnp.concatenate([kc, scratch], 1), k_raw.astype(kc.dtype),
        (0, q_offset, 0, 0))[:, :cap]
    vc = jax.lax.dynamic_update_slice(
        jnp.concatenate([vc, scratch], 1), v_raw.astype(vc.dtype),
        (0, q_offset, 0, 0))[:, :cap]
    return grouped_attention(q, kc, vc, causal=True,
                             sliding_window=sliding_window,
                             alibi_slopes=alibi_slopes, q_offset=q_offset)


def quant_matmul_ref(x: jnp.ndarray, params: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    """W4A16 matmul oracle: dequantize then matmul."""
    return _qmm(x, params)
