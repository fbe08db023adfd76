"""Public kernel entry points with automatic Pallas / XLA-reference dispatch.

``use_pallas=None`` (default) picks Pallas on TPU and the pure-XLA
reference elsewhere.  ``interpret=None`` resolves to "not on TPU": the
compiled kernel on the chip, the Pallas interpreter for CPU validation.
The kernels themselves take ``interpret`` with no default, so nothing
that bypasses this module can run the interpreter on a chip by accident.
The dry-run always lowers the reference path (Pallas cannot lower on the
CPU backend of the 512-device compile-only mesh).
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.flash_attention import (
    flash_attention_chunk as _flash_chunk_pallas)
from repro.kernels.paged_attention import paged_attention as _paged_pallas
from repro.kernels.paged_attention_quant import (
    paged_attention_quant as _paged_quant_pallas)
from repro.kernels.gptq_matmul import gptq_matmul as _gptq_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret(interpret: Optional[bool]) -> bool:
    return (not _on_tpu()) if interpret is None else interpret


def flash_attention(q, k, v, alibi_slopes=None, *, causal=True,
                    sliding_window=0, q_offset=0,
                    use_pallas: Optional[bool] = None,
                    interpret: Optional[bool] = None):
    if use_pallas is None:
        use_pallas = _on_tpu()
    if use_pallas:
        return _flash_pallas(q, k, v, alibi_slopes, causal=causal,
                             sliding_window=sliding_window, q_offset=q_offset,
                             interpret=_interpret(interpret))
    if q.shape[1] > 512 and isinstance(q_offset, int):
        # flash-structured XLA lowering: no [S,S] materialization
        from repro.core.gqa import grouped_attention_chunked
        return grouped_attention_chunked(q, k, v, causal=causal,
                                         sliding_window=sliding_window,
                                         alibi_slopes=alibi_slopes,
                                         q_offset=q_offset)
    return _ref.flash_attention_ref(q, k, v, causal=causal,
                                    sliding_window=sliding_window,
                                    alibi_slopes=alibi_slopes, q_offset=q_offset)


def chunk_prefill_attention(q, k_pool, v_pool, k_scales, v_scales, layer,
                            block_table, q_offset, total_len, k_raw, v_raw,
                            alibi_slopes=None, *, sliding_window=0,
                            use_pallas: Optional[bool] = None,
                            interpret: Optional[bool] = None):
    """Serving chunk-prefill attention with a *traced* ``q_offset``.

    One chunk of one sequence attends over the paged pool's live prefix
    plus its own raw K/V — the Pallas path walks the stacked pool's pages
    in place (scalar-prefetch block table and layer, page walk clamped to
    the live prefix, in-register int8 dequant when scales are given); the
    XLA path is the bounded-gather + raw-overlay oracle in ``ref.py``.
    Both cost O(total_len) pool bytes per layer per chunk, never
    O(capacity).

    q: [1, W, H, D]; k_pool/v_pool: [L, NB, KV, BS, D]; k_scales/
    v_scales: [L, NB, KV] f32 or None (bf16 pools); layer: traced layer
    index; block_table: [1, MB]; q_offset/total_len: traced i32 scalars;
    k_raw/v_raw: [1, W, KV, D] (the chunk's own full-precision K/V).
    """
    if use_pallas is None:
        use_pallas = _on_tpu()
    if use_pallas:
        return _flash_chunk_pallas(
            q, k_pool, v_pool, layer, block_table, q_offset, total_len,
            k_raw, v_raw, alibi_slopes, k_scales=k_scales,
            v_scales=v_scales, sliding_window=sliding_window,
            interpret=_interpret(interpret))
    return _ref.chunk_prefill_attention_ref(
        q, k_pool, v_pool, k_scales, v_scales, layer, block_table,
        q_offset, total_len, k_raw, v_raw, alibi_slopes=alibi_slopes,
        sliding_window=sliding_window)


def paged_attention(q, k_pool, v_pool, layer, block_table, seq_lens,
                    alibi_slopes=None, *, sliding_window=0,
                    use_pallas: Optional[bool] = None,
                    interpret: Optional[bool] = None):
    """Decode attention over layer ``layer`` of the stacked
    ``[L, NB, KV, BS, D]`` pool, read in place."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    if use_pallas:
        return _paged_pallas(q, k_pool, v_pool, layer, block_table,
                             seq_lens, alibi_slopes,
                             sliding_window=sliding_window,
                             interpret=_interpret(interpret))
    return _ref.paged_attention_ref(q, k_pool, v_pool, layer, block_table,
                                    seq_lens, alibi_slopes=alibi_slopes,
                                    sliding_window=sliding_window)


def paged_attention_quant(q, k_values, k_scales, v_values, v_scales, layer,
                          block_table, seq_lens, alibi_slopes=None, *,
                          sliding_window=0,
                          use_pallas: Optional[bool] = None,
                          interpret: Optional[bool] = None):
    """Decode attention over layer ``layer`` of the stacked int8 KV pool
    (per-block-per-head scales), read in place and dequantized inside the
    kernel instead of materializing bf16 pages."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    if use_pallas:
        return _paged_quant_pallas(
            q, k_values, k_scales, v_values, v_scales, layer, block_table,
            seq_lens, alibi_slopes, sliding_window=sliding_window,
            interpret=_interpret(interpret))
    return _ref.paged_attention_quant_ref(
        q, k_values, k_scales, v_values, v_scales, layer, block_table,
        seq_lens, alibi_slopes=alibi_slopes, sliding_window=sliding_window)


def quant_matmul(x: jnp.ndarray, params: Dict[str, jnp.ndarray], *,
                 use_pallas: Optional[bool] = None,
                 interpret: Optional[bool] = None,
                 ctx=None) -> jnp.ndarray:
    """x: [..., K] @ packed int4 weight -> [..., N]."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    if not use_pallas:
        if ctx is not None and ctx.tp_axis is not None:
            # keep the dequantized weight sharded like its packed source —
            # otherwise GSPMD may all-gather it (22 GB/step at qwen2 decode)
            from jax.sharding import PartitionSpec as P
            from repro.core.quant import dequantize
            from repro.runtime.sharding import shard
            n = params["scales"].shape[-1]
            tp = ctx.tp_axis if n % ctx.tp_size == 0 else None
            w = dequantize(params, x.shape[-1], x.dtype)
            w = shard(ctx, w, P(None, tp))
            y = x @ w
            if "bias" in params:
                y = y + params["bias"].astype(y.dtype)
            return y
        return _ref.quant_matmul_ref(x, params)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    y = _gptq_pallas(x2, params["qweight"], params["scales"], params["zeros"],
                     interpret=_interpret(interpret))
    if "bias" in params:
        y = y + params["bias"].astype(y.dtype)
    return y.reshape(*lead, -1)
