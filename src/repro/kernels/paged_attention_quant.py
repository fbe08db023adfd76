"""Pallas TPU paged-attention decode kernel over the int8 KV pool.

Same grid / scalar-prefetch structure as ``paged_attention.py`` — one
grid step = (sequence, page) covering every KV head; the block table and
the layer index resolve ``(layer, physical page)`` of the stacked
``[L, NB, KV, BS, D]`` pool inside the BlockSpec ``index_map``, so the
pool is read in place with no per-layer slice or relayout; online
softmax across pages in VMEM scratch; Opt-GQA shared-KV contraction of
all G grouped query heads per head tile.  The ``pallas_call`` IS
``paged_decode_call`` with scales: the K/V tiles DMA'd into VMEM are
**int8** ``[BS, D]`` head tiles with one f32 scale per (page, kv head),
read from SMEM and applied in-register right before the contraction.
The quantized cache is never materialized in HBM at full precision:
attention consumes it directly (the TurboAttention observation, arXiv
2412.08585), so the kernel moves ~1/2 (bf16) to ~1/4 (f32) of the
baseline's KV bytes per decode step.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention import paged_decode_call


@functools.partial(jax.jit, static_argnames=("sliding_window", "interpret"))
def paged_attention_quant(
    q: jnp.ndarray,                  # [B, H, D] — one new token per sequence
    k_values: jnp.ndarray,           # [L, NB, KV, BS, D] int8
    k_scales: jnp.ndarray,           # [L, NB, KV] f32
    v_values: jnp.ndarray,
    v_scales: jnp.ndarray,
    layer: jnp.ndarray,              # i32 scalar: the layer read in place
    block_table: jnp.ndarray,        # [B, MB] int32
    seq_lens: jnp.ndarray,           # [B] int32
    alibi_slopes: Optional[jnp.ndarray] = None,
    *,
    sliding_window: int = 0,
    interpret: bool,
) -> jnp.ndarray:
    return paged_decode_call(q, k_values, v_values, k_scales, v_scales,
                             layer, block_table, seq_lens, alibi_slopes,
                             sliding_window=sliding_window,
                             interpret=interpret)
