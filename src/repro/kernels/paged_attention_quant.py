"""Pallas TPU paged-attention decode kernel over the int8 KV pool.

The ``pallas_call`` IS ``paged_decode_call`` (``paged_attention.py``)
with scales: the same walk over each slot's live pages of the stacked
``[L, NB, KV, BS, D]`` pool, read in place, and the same online-softmax
body, Opt-GQA shared-KV contraction of all G grouped query heads per
head tile.  The pages DMA'd into VMEM are **int8** ``[BS, D]`` head
tiles; the layer's ``[KV, NB]`` f32 scales (one per page and KV head)
sit in SMEM, and each page's scale multiplies its scores and its
``p @ V`` product in-register.  The quantized cache is never
materialized in HBM at full precision: attention consumes it directly
(the TurboAttention observation, arXiv 2412.08585), so the kernel moves
~1/2 (bf16) to ~1/4 (f32) of the baseline's KV bytes per decode step.
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
