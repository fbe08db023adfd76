"""Quantized paged KV cache: int8 block pool + per-block-per-head scales.

The paper's thesis is that *combining* quantization with paging memory
management is what buys serving headroom; the GPTQ side only quantizes
weights.  This module quantizes the other big HBM consumer — the paged
KV pool — to symmetric per-block-per-head int8:

* values pool ``[L, NB, KV, BS, D]`` int8 (vs bf16/f32), head-major so
  each (block, kv head) is one dense ``[BS, D]`` tile, and
* scales pool ``[L, NB, KV]`` f32 — ONE scale per (block, kv head),
  covering all ``BS`` tokens × ``D`` dims of that head's tile,

so KV bytes per cached token drop ~2x vs bf16 (~4x vs the f32 CPU pools)
with a ``2 * L * KV * 4 / BS`` bytes/token scales overhead.  Reads
dequantize in-register: the Pallas decode kernel
(``kernels/paged_attention_quant.py``) multiplies each int8 page's
scores and ``p @ V`` product by the page's scale inside the
online-softmax loop — the quantized cache is never
materialized densely (TurboAttention, arXiv 2412.08585; MILLION, arXiv
2504.03661).  Writers and kernels address the stacked pool in place:
a write is a scatter into ``values[layer, block]``, a read is the
kernel's DMA of ``values[layer, block]``; no per-layer slice of the
pool is ever copied out or written back.

Write discipline (what keeps one scale per block sound):

* a *fresh* block is quantized from exactly the tokens written into it,
  junk slots masked to zero so stale garbage can never inflate the scale;
* an *appending* write (decode, or a chunked-prefill boundary block)
  dequantizes the block's live prefix, merges the new tokens, and
  requantizes the whole block with the recomputed amax.  When the scale
  is unchanged this is exact (``round(q) == q``); when it grows, existing
  values pick up at most half a quantization step — bounded drift, and
  bit-identical at every megastep horizon because each decode step runs
  this same op;
* copy-on-write (``copy_blocks_quant``) copies the scale row with the
  value block, so forks keep decoding correctly.

Everything here is shape-compatible with ``core.paged_cache``: the same
``BlockAllocator`` / block tables drive both pool formats, and the bf16
ops remain the parity oracle.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.paged_cache import (copy_blocks, gather_kv,
                                    gather_kv_bounded, pages_to_tokens,
                                    write_decode_kv, write_prefill_kv)

INT8_MAX = 127.0
# floor on amax before the /127: keeps all-zero blocks at scale ~1e-22
# (dequant exactly 0) without 0/0 in the quantize divide.
AMAX_FLOOR = 1e-20

KV_CACHE_DTYPES = ("bf16", "int8")


def normalize_kv_cache_dtype(kv_cache_dtype: Optional[str]) -> str:
    """Accept None / "bf16" / "bfloat16" as the unquantized pool (its
    element dtype stays whatever the runner picks) and "int8" as the
    quantized one."""
    if kv_cache_dtype in (None, "bf16", "bfloat16"):
        return "bf16"
    if kv_cache_dtype == "int8":
        return "int8"
    raise ValueError(f"unknown kv_cache_dtype {kv_cache_dtype!r}; "
                     f"expected one of {KV_CACHE_DTYPES}")


# --------------------------------------------------------------------------
# The cache carried through the layer loops
# --------------------------------------------------------------------------


class KVCache(NamedTuple):
    """K/V pools plus (optionally) their scale pools, as one pytree.

    ``k``/``v``: [L, NB, KV, BS, D] — bf16/f32/fp8 in the unquantized
    mode, int8 in the quantized one.  ``k_scale``/``v_scale``: [L, NB, KV]
    f32 in int8 mode, ``None`` otherwise (None is an empty pytree, so the
    same scan/shard_map plumbing carries both modes).
    """
    k: jnp.ndarray
    v: jnp.ndarray
    k_scale: Optional[jnp.ndarray] = None
    v_scale: Optional[jnp.ndarray] = None

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def block_size(self) -> int:
        return self.k.shape[3]

    def nbytes(self) -> int:
        return sum(int(a.size) * a.dtype.itemsize
                   for a in (self.k, self.v, self.k_scale, self.v_scale)
                   if a is not None)


def cache_from_state(state) -> KVCache:
    return KVCache(state["k_pool"], state["v_pool"],
                   state.get("k_scales"), state.get("v_scales"))


def cache_to_state(cache: KVCache) -> dict:
    st = {"k_pool": cache.k, "v_pool": cache.v}
    if cache.quantized:
        st["k_scales"] = cache.k_scale
        st["v_scales"] = cache.v_scale
    return st


def make_kv_pool_quant(num_layers: int, num_blocks: int, block_size: int,
                       num_kv_heads: int, head_dim: int
                       ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                  jnp.ndarray, jnp.ndarray]:
    """(k_values, v_values [L,NB,KV,BS,D] int8, k_scales, v_scales
    [L,NB,KV] f32)."""
    vshape = (num_layers, num_blocks, num_kv_heads, block_size, head_dim)
    sshape = (num_layers, num_blocks, num_kv_heads)
    return (jnp.zeros(vshape, jnp.int8), jnp.zeros(vshape, jnp.int8),
            jnp.zeros(sshape, jnp.float32), jnp.zeros(sshape, jnp.float32))


# --------------------------------------------------------------------------
# Quantize / dequantize primitives
# --------------------------------------------------------------------------


def quantize_blocks(x: jnp.ndarray, live: jnp.ndarray
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-block-per-head int8 quantization.

    x: [..., KV, BS, D] float (head-major pages); live: [..., BS] bool —
    slots outside the mask are zeroed *before* the amax so junk can never
    inflate the scale (and they quantize to exactly 0).  Returns (q int8
    like x, scales [..., KV] f32) with ``scale = amax / 127`` over each
    head's ``[BS, D]`` tile, so the roundtrip error of any live value is
    <= scale / 2.
    """
    xf = jnp.where(live[..., None, :, None], x.astype(jnp.float32), 0.0)
    amax = jnp.max(jnp.abs(xf), axis=(-2, -1))                 # [..., KV]
    scales = jnp.maximum(amax, AMAX_FLOOR) / INT8_MAX
    q = jnp.round(xf / scales[..., None, None])
    q = jnp.clip(q, -INT8_MAX, INT8_MAX).astype(jnp.int8)
    return q, scales


def dequantize_blocks(q: jnp.ndarray, scales: jnp.ndarray) -> jnp.ndarray:
    """q: [..., KV, BS, D] int8, scales: [..., KV] -> f32 values."""
    return q.astype(jnp.float32) * scales[..., None, None]


# --------------------------------------------------------------------------
# Quantize-on-write pool ops (int8 counterparts of core.paged_cache)
# --------------------------------------------------------------------------


def write_prefill_kv_quant(values: jnp.ndarray, scales: jnp.ndarray,
                           layer, k: jnp.ndarray, block_table: jnp.ndarray,
                           ctx_lens: jnp.ndarray, pos_offset=0
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Quantize a prompt (or prompt chunk) into the int8 pool.

    values: [L, NB, KV, BS, D] int8; scales: [L, NB, KV] f32;
    k: [B, S, KV, D] holding positions ``pos_offset + i``; only absolute
    positions < ctx_lens are live.  Each touched block is quantized whole:
    blocks starting at/after ``pos_offset`` are fresh (scale overwritten);
    the one boundary block a chunked prefill appends into merges the
    dequantized live prefix first (``lead == 0`` degenerates to the
    fresh write).  The blocks are scattered straight into
    ``values[layer]`` of the stacked pool.

    ``pos_offset`` may be a Python int or a *traced* scalar: the serving
    chunk-prefill executable compiles once for a fixed ``[B, S]`` chunk
    shape and feeds the chunk's start position as a device scalar, so
    all block arithmetic (pad widths, table slices) uses dynamic-slice
    forms — which constant-fold when the offset is static.
    """
    B, S, KV, D = k.shape
    NB, bs = values.shape[1], values.shape[3]
    nb = -(-S // bs) + 1                       # static max touched blocks
    j0 = pos_offset // bs                      # first touched block (traced)
    lead = pos_offset - j0 * bs                # live prefix rows in block j0

    buf = jnp.zeros((B, nb * bs, KV, D), jnp.float32)
    buf = jax.lax.dynamic_update_slice(buf, k.astype(jnp.float32),
                                       (0, lead, 0, 0))
    buf = jnp.swapaxes(buf.reshape(B, nb, bs, KV, D), 2, 3)  # [B,nb,KV,bs,D]
    pos = (j0 * bs + jnp.arange(nb * bs)).reshape(nb, bs)
    live = ((pos[None] >= pos_offset)
            & (pos[None] < ctx_lens[:, None, None]))           # [B, nb, bs]

    # pad the table with the OOB sentinel so the dynamic slice never
    # clamps (a clamped start would misalign every block of the chunk);
    # sentinel rows are dead (live is False past the capacity) anyway.
    btp = jnp.concatenate(
        [block_table, jnp.full((B, nb), NB, block_table.dtype)], axis=1)
    blk = jax.lax.dynamic_slice_in_dim(btp, j0, nb, axis=1)    # [B, nb]
    # chunk boundary: block j0 may already hold this sequence's tokens at
    # slots [0, lead) — dequantize and merge them before requantizing.
    safe0 = jnp.minimum(blk[:, 0], NB - 1)
    old = dequantize_blocks(values[layer, safe0],
                            scales[layer, safe0])              # [B,KV,bs,D]
    old_live = ((jnp.arange(bs)[None] < lead)
                & (pos[0][None] < ctx_lens[:, None]))          # [B, bs]
    buf = buf.at[:, 0].add(jnp.where(old_live[:, None, :, None], old, 0.0))
    live = live.at[:, 0].set(live[:, 0] | old_live)

    q, sc = quantize_blocks(buf, live)
    tgt = jnp.where(live.any(-1), blk, NB)                     # [B, nb]
    return (values.at[layer, tgt].set(q, mode="drop"),
            scales.at[layer, tgt].set(sc, mode="drop"))


def write_decode_kv_quant(values: jnp.ndarray, scales: jnp.ndarray,
                          layer, k_new: jnp.ndarray,
                          block_table: jnp.ndarray, positions: jnp.ndarray
                          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Append one token per sequence to its (private, CoW-guaranteed) tail
    block: dequantize the live prefix, insert the token, requantize the
    block with the recomputed amax, and scatter the block straight into
    ``values[layer]`` (in place: one [KV, BS, D] block per sequence, never
    the layer's whole slice).  positions: [B] absolute position of the
    new token; negative => inactive slot, write dropped.
    """
    NB, bs = values.shape[1], values.shape[3]
    valid = positions >= 0
    pos = jnp.maximum(positions, 0)
    blk = jnp.take_along_axis(block_table, (pos // bs)[:, None],
                              axis=1)[:, 0]                    # [B]
    off = pos % bs                                             # [B]

    old = dequantize_blocks(values[layer, blk],
                            scales[layer, blk])                # [B,KV,bs,D]
    slot = jnp.arange(bs)[None, :]                             # [1, bs]
    buf = jnp.where((slot < off[:, None])[:, None, :, None], old, 0.0)
    buf = jnp.where((slot == off[:, None])[:, None, :, None],
                    k_new[:, :, None].astype(jnp.float32), buf)
    live = slot <= off[:, None]                                # [B, bs]
    q, sc = quantize_blocks(buf, live)

    tgt = jnp.where(valid, blk, NB)                            # OOB -> dropped
    return (values.at[layer, tgt].set(q, mode="drop"),
            scales.at[layer, tgt].set(sc, mode="drop"))


def gather_kv_quant(values: jnp.ndarray, scales: jnp.ndarray, layer,
                    block_table: jnp.ndarray, max_len: int,
                    dtype=jnp.float32) -> jnp.ndarray:
    """Dequantizing counterpart of ``gather_kv`` (reference / chunked
    prefill path): [B, max_len, KV, D] in ``dtype``."""
    bs = values.shape[3]
    nb = -(-max_len // bs)
    blk = block_table[:, :nb]                                  # [B, nb]
    x = dequantize_blocks(values[layer, blk], scales[layer, blk])
    return pages_to_tokens(x)[:, :max_len].astype(dtype)


def gather_kv_quant_bounded(values: jnp.ndarray, scales: jnp.ndarray, layer,
                            block_table: jnp.ndarray, max_len: int,
                            num_live_blocks, dtype=jnp.float32
                            ) -> jnp.ndarray:
    """``gather_kv_quant`` bounded by a *traced* live-page count: only the
    first ``num_live_blocks`` table entries are read and dequantized (one
    page per ``fori_loop`` iteration), the rest of the static
    ``[B, max_len, KV, D]`` view stays zero — O(live) dequant work
    instead of O(capacity) per layer per chunk."""
    bs = values.shape[3]
    nb = -(-max_len // bs)
    B = block_table.shape[0]
    buf = jnp.zeros((B, nb) + values.shape[2:], dtype)

    def body(j, buf):
        blk = block_table[:, j]                            # [B]
        page = dequantize_blocks(values[layer, blk],
                                 scales[layer, blk]).astype(dtype)
        return jax.lax.dynamic_update_slice_in_dim(buf, page[:, None], j,
                                                   axis=1)

    buf = jax.lax.fori_loop(
        0, jnp.minimum(jnp.asarray(num_live_blocks, jnp.int32), nb),
        body, buf)
    return pages_to_tokens(buf)[:, :max_len]


def copy_blocks_quant(values: jnp.ndarray, scales: jnp.ndarray,
                      src: jnp.ndarray, dst: jnp.ndarray
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Copy-on-write for the quantized pool: the scale rows move with the
    value blocks (a fork that dropped them would dequantize its shared
    prefix with garbage)."""
    return copy_blocks(values, src, dst), copy_blocks(scales, src, dst)


# --------------------------------------------------------------------------
# Mode-dispatching writes/reads over a KVCache (what the model layers call)
# --------------------------------------------------------------------------


def kv_write_prefill(cache: KVCache, layer, k, v, block_table, ctx_lens,
                     pos_offset: int = 0) -> KVCache:
    if cache.quantized:
        kq, ks = write_prefill_kv_quant(cache.k, cache.k_scale, layer, k,
                                        block_table, ctx_lens, pos_offset)
        vq, vs = write_prefill_kv_quant(cache.v, cache.v_scale, layer, v,
                                        block_table, ctx_lens, pos_offset)
        return KVCache(kq, vq, ks, vs)
    return cache._replace(
        k=write_prefill_kv(cache.k, layer, k, block_table, ctx_lens,
                           pos_offset=pos_offset),
        v=write_prefill_kv(cache.v, layer, v, block_table, ctx_lens,
                           pos_offset=pos_offset))


def kv_write_decode(cache: KVCache, layer, k, v, block_table,
                    positions) -> KVCache:
    if cache.quantized:
        kq, ks = write_decode_kv_quant(cache.k, cache.k_scale, layer, k,
                                       block_table, positions)
        vq, vs = write_decode_kv_quant(cache.v, cache.v_scale, layer, v,
                                       block_table, positions)
        return KVCache(kq, vq, ks, vs)
    return cache._replace(
        k=write_decode_kv(cache.k, layer, k, block_table, positions),
        v=write_decode_kv(cache.v, layer, v, block_table, positions))


def kv_gather_bounded(cache: KVCache, layer, block_table, max_len: int,
                      num_live_blocks, dtype
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``kv_gather`` whose page walk stops at ``num_live_blocks`` (traced):
    the serving chunk path's O(total_len) gather — see
    ``gather_kv_bounded``; positions past the live pages are zeros, which
    downstream causal masking makes indistinguishable from the
    full-capacity gather."""
    if cache.quantized:
        return (gather_kv_quant_bounded(cache.k, cache.k_scale, layer,
                                        block_table, max_len,
                                        num_live_blocks, dtype),
                gather_kv_quant_bounded(cache.v, cache.v_scale, layer,
                                        block_table, max_len,
                                        num_live_blocks, dtype))
    return (gather_kv_bounded(cache.k, layer, block_table, max_len,
                              num_live_blocks).astype(dtype),
            gather_kv_bounded(cache.v, layer, block_table, max_len,
                              num_live_blocks).astype(dtype))


def kv_gather(cache: KVCache, layer, block_table, max_len: int,
              dtype) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if cache.quantized:
        return (gather_kv_quant(cache.k, cache.k_scale, layer, block_table,
                                max_len, dtype),
                gather_kv_quant(cache.v, cache.v_scale, layer, block_table,
                                max_len, dtype))
    return (gather_kv(cache.k, layer, block_table, max_len).astype(dtype),
            gather_kv(cache.v, layer, block_table, max_len).astype(dtype))
