"""Pallas TPU paged-attention decode kernel (Opt-GQA over block tables).

The TPU form of the paper's custom DCU decode kernel:

* The stacked KV pool ``[L, NB, KV, BS, D]`` stays in HBM and is read in
  place: the *block table* and the layer index are scalar-prefetch
  operands (SMEM), so the BlockSpec ``index_map`` itself resolves
  ``(layer, physical block)`` per sequence — the DMA engine walks the
  page list, which is exactly "paging" on TPU, and no per-layer slice of
  the pool is ever materialized.
* One grid step = (sequence, page): the page's K/V for *all* KV heads,
  ``[KV, BS, D]``, is pulled into VMEM once.  Pages are head-major, so
  each head is one dense ``[BS, D]`` tile (its block dims equal the
  pool's last two, which is what the TPU's (8, 128) tiling rule asks of
  a block, and no KV padding is DMA'd); each head's tile is contracted
  with all G grouped query heads (shared K/V -> batched matmul, the
  Opt-GQA insight).
* ALiBi bias from iota in-tile; positions past ``seq_len`` masked; online
  softmax accumulated in VMEM scratch across pages.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _clamp_live(i, seq_len, block_size):
    """Clamp page index ``i`` to the sequence's last live page.

    ``seq_len`` may be 0 for inactive slots; clamp to page 0 then (the
    kernel's ``k_lo < seq_len`` guard skips the compute anyway).
    """
    last = jnp.maximum((seq_len + block_size - 1) // block_size, 1) - 1
    return jnp.minimum(i, last)


def _pa_kernel(block_tables_ref, seq_lens_ref, layer_ref,  # prefetch (SMEM)
               slopes_ref, q_ref, *refs,
               block_size: int, num_pages: int, num_kv_heads: int,
               use_alibi: bool, sliding_window: int, quantized: bool = False):
    """Shared online-softmax body for the bf16 and int8 decode kernels.

    ``refs`` is (k, v, o, acc, m, l) in the dense mode and
    (k, k_scale, v, v_scale, o, acc, m, l) when ``quantized`` — the int8
    wrapper (``paged_attention_quant.py``) reuses this body so the
    softmax loop can never diverge between the two pool formats.  The
    page refs are ``[1, 1, KV, BS, D]`` blocks of the stacked pool; the
    scale refs are SMEM ``[1, 1, 1, KV]`` rows: one f32 per (page, head).
    """
    del layer_ref                                     # used by the index maps
    if quantized:
        k_ref, ks_ref, v_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = refs
    else:
        k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    seq_len = seq_lens_ref[b]
    k_lo = i * block_size

    @pl.when(k_lo < seq_len)                          # skip pages past the end
    def _compute():
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (1, block_size), 1)
        q_pos = seq_len - 1
        mask = k_pos < seq_len
        if sliding_window > 0:
            mask &= k_pos > q_pos - sliding_window
        for h in range(num_kv_heads):
            q = q_ref[0, h].astype(jnp.float32)       # [G, D]
            k = k_ref[0, 0, h].astype(jnp.float32)    # [BS, D]
            v = v_ref[0, 0, h].astype(jnp.float32)    # [BS, D]
            if quantized:
                # in-register dequant: int8 tile * the page's per-head scale
                k = k * ks_ref[0, 0, 0, h]
                v = v * vs_ref[0, 0, 0, h]
            scale = q.shape[-1] ** -0.5
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            # s: [G, BS]
            if use_alibi:
                s = s - slopes_ref[h] * jnp.maximum(
                    q_pos - k_pos, 0).astype(jnp.float32)
            s = jnp.where(mask, s, NEG_INF)

            m_prev, l_prev = m_ref[h], l_ref[h]       # [G, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[h] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            m_ref[h] = m_new
            pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            acc_ref[h] = acc_ref[h] * alpha + pv

    @pl.when(i == num_pages - 1)
    def _final():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def group_slopes(alibi_slopes, KV, G):
    """[H] ALiBi slopes -> the kernels' ``[KV, G, 1]`` operand (zeros
    when the model has none; the kernel then never reads them)."""
    if alibi_slopes is None:
        return jnp.zeros((KV, G, 1), jnp.float32)
    return alibi_slopes.astype(jnp.float32).reshape(KV, G, 1)


def paged_decode_call(q, k_pool, v_pool, k_scales, v_scales, layer,
                      block_table, seq_lens, alibi_slopes, *,
                      sliding_window: int, interpret: bool) -> jnp.ndarray:
    """The decode ``pallas_call`` for both pool formats over the stacked
    ``[L, NB, KV, BS, D]`` pool at ``layer`` (a traced or static scalar;
    int8 when ``k_scales`` is given, with ``[L, NB, KV]`` f32 scales)."""
    B, H, D = q.shape
    L, NB, KV, BS, _ = k_pool.shape
    G = H // KV
    MB = block_table.shape[1]
    quantized = k_scales is not None
    slopes = group_slopes(alibi_slopes, KV, G)
    qg = q.reshape(B, KV, G, D)

    kernel = functools.partial(
        _pa_kernel, block_size=BS, num_pages=MB, num_kv_heads=KV,
        use_alibi=alibi_slopes is not None, sliding_window=sliding_window,
        quantized=quantized)

    # the paging step: the layer and the physical page id come from the
    # prefetched scalars inside the index_map, so the kernel reads the
    # stacked pool in place. Pages past the sequence's live page count
    # re-resolve to its last live page: Pallas skips the DMA when
    # consecutive grid steps map to the same block, so the HBM walk is
    # bounded by ceil(seq_len/BS), not the static MB (compute for those
    # steps is skipped too).
    def page_map(b, i, bt, sl, ly):
        return (ly[0], bt[b, _clamp_live(i, sl[b], BS)], 0, 0, 0)

    def scale_map(b, i, bt, sl, ly):
        return (ly[0], bt[b, _clamp_live(i, sl[b], BS)], 0, 0)

    page = pl.BlockSpec((1, 1, KV, BS, D), page_map)
    in_specs = [pl.BlockSpec((KV, G, 1), lambda b, i, bt, sl, ly: (0, 0, 0)),
                pl.BlockSpec((1, KV, G, D),
                             lambda b, i, bt, sl, ly: (b, 0, 0, 0))]
    if quantized:
        # [L, NB, KV] -> [L, NB, 1, KV]: a (1, 1, 1, KV) block equals the
        # array's last two dims, which a (1, 1, KV) block of [L, NB, KV]
        # does not.
        scale = pl.BlockSpec((1, 1, 1, KV), scale_map,
                             memory_space=pltpu.SMEM)
        in_specs += [page, scale, page, scale]
        args = [k_pool, k_scales.reshape(L, NB, 1, KV),
                v_pool, v_scales.reshape(L, NB, 1, KV)]
    else:
        in_specs += [page, page]
        args = [k_pool, v_pool]

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,          # block_table, seq_lens, layer
            grid=(B, MB),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, KV, G, D),
                                   lambda b, i, bt, sl, ly: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((KV, G, D), jnp.float32),
                pltpu.VMEM((KV, G, 1), jnp.float32),
                pltpu.VMEM((KV, G, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_attention_quant" if quantized else "paged_attention",
    )(block_table, seq_lens, jnp.asarray(layer, jnp.int32).reshape(1),
      slopes, qg, *args)
    return out.reshape(B, H, D)


@functools.partial(jax.jit, static_argnames=("sliding_window", "interpret"))
def paged_attention(
    q: jnp.ndarray,                  # [B, H, D] — one new token per sequence
    k_pool: jnp.ndarray,             # [L, NB, KV, BS, D]
    v_pool: jnp.ndarray,
    layer: jnp.ndarray,              # i32 scalar: the layer read in place
    block_table: jnp.ndarray,        # [B, MB] int32
    seq_lens: jnp.ndarray,           # [B] int32
    alibi_slopes: Optional[jnp.ndarray] = None,
    *,
    sliding_window: int = 0,
    interpret: bool,
) -> jnp.ndarray:
    return paged_decode_call(q, k_pool, v_pool, None, None, layer,
                             block_table, seq_lens, alibi_slopes,
                             sliding_window=sliding_window,
                             interpret=interpret)
