"""Pallas TPU paged-attention decode kernel (Opt-GQA over block tables).

The TPU form of the paper's custom DCU decode kernel:

* The stacked KV pool ``[L, NB, KV, BS, D]`` is read in place: the
  *block table*, the sequence lengths and the layer index are
  scalar-prefetch operands (SMEM) that resolve ``(layer, physical
  page)`` per sequence, so no per-layer slice of the pool is ever
  materialized.  A page moves all KV heads, ``[KV, BS, D]`` head-major;
  each head is a dense ``[BS, D]`` tile contracted with all G grouped
  query heads (shared K/V -> batched matmul, the Opt-GQA insight).
* One grid step = one sequence (slot).  The pool stays in HBM
  (``memory_space=pl.ANY``) and a loop walks only the slot's *live*
  pages, ``P`` per block: ``ceil(seq_len / (P*BS))`` blocks, never the
  table's static width, and no table entry at or past
  ``ceil(seq_len/BS)`` is read.  The page DMAs are double-buffered:
  block ``n+1`` is in flight while block ``n`` computes, and a slot's
  last block starts the first block of the next slot that has one, so
  the pipeline does not drain between slots.  ``P`` comes from the
  shapes (``pages_per_block``).
* The TPU's DMA slices an HBM array only in whole (sublane, 128) tiles
  of its last dim, so a pool whose head dim is not a multiple of 128
  cannot be paged by hand.  Such a pool is walked by the grid instead,
  one (slot, table entry) step per page, with the page DMA'd by the
  BlockSpec pipeline and entries past the live pages re-resolved to the
  last live page (no DMA, no compute).  Same block body, one page per
  block.
* The int8 pool's per-(page, head) scales reach SMEM as scalar-prefetch
  ``[KV, NB]`` rows of the layer; each page's scale multiplies its
  ``[G, BS]`` scores and its ``p @ V`` product.
* ALiBi bias from iota in-tile; positions past ``seq_len`` (and outside
  the sliding window) masked; one online-softmax update per block, in
  VMEM scratch.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

# K bytes one block's page DMAs aim to move, and the VMEM that the four
# page buffers (K and V, double-buffered) may take at most
BLOCK_BYTES = 512 * 1024
BUFFER_VMEM = 8 * 1024 * 1024
LANES = 128


def pages_per_block(num_kv_heads: int, block_size: int, head_dim: int,
                    itemsize: int, table_width: int) -> int:
    """Pages per DMA block: ``BLOCK_BYTES`` of K per block, at most the
    table width, and at most what fits ``BUFFER_VMEM`` for the K and V
    double buffers once each ``[BS, D]`` tile is padded to the TPU's
    native (sublane, 128) tile of its dtype."""
    page = num_kv_heads * block_size * head_dim * itemsize
    sub = 32 // itemsize                       # 8 f32, 16 bf16, 32 int8
    padded = (num_kv_heads * -(-block_size // sub) * sub
              * -(-head_dim // LANES) * LANES * itemsize)
    p = min(BLOCK_BYTES // page, BUFFER_VMEM // (4 * padded), table_width)
    return max(p, 1)


def _clamp_live(i, seq_len, block_size):
    """Clamp page index ``i`` to the sequence's last live page.

    ``seq_len`` may be 0 for inactive slots; clamp to page 0 then (the
    kernel's live-page guard skips the compute anyway).
    """
    last = jnp.maximum((seq_len + block_size - 1) // block_size, 1) - 1
    return jnp.minimum(i, last)


def _pa_kernel(*refs, block_size: int, pages: int, num_slots: int,
               num_kv_heads: int, use_alibi: bool, sliding_window: int,
               quantized: bool, walk: bool):
    """Shared decode body for every pool format and both page walks.

    ``refs``: the scalar prefetch (block_table, seq_lens, layer, and
    the layer's ``[KV, NB]`` k / v scales when ``quantized``), then
    slopes, q, k, v, o, then the scratch.  With ``walk`` k / v are the
    whole stacked pool in HBM and the scratch is (k_buf, v_buf, sems,
    buf_idx, acc, m, l); without it they are the grid step's
    ``[1, 1, KV, BS, D]`` page and the scratch is (acc, m, l).  The int8
    wrapper (``paged_attention_quant.py``) reuses this body so the
    softmax loop can never diverge between the pool formats.
    """
    bt_ref, sl_ref, ly_ref = refs[:3]
    ks_ref, vs_ref = refs[3:5] if quantized else (None, None)
    slopes_ref, q_ref, k_ref, v_ref, o_ref, *scratch = \
        refs[5 if quantized else 3:]
    if walk:
        k_buf, v_buf, sems, buf_idx, acc_ref, m_ref, l_ref = scratch
    else:
        acc_ref, m_ref, l_ref = scratch
    b = pl.program_id(0)
    BS, P = block_size, pages
    seq_len = sl_ref[b]
    npages = (seq_len + BS - 1) // BS
    q_pos = seq_len - 1
    sm_scale = q_ref.shape[-1] ** -0.5
    # the int8 codes and a bf16 q are exact in bf16: contract them there
    # (f32 accumulation); f32 operands stay f32
    cdt = jnp.promote_types(q_ref.dtype, k_ref.dtype)

    def attend(n, k_tile, v_tile):
        """One online-softmax update over block ``n``'s P pages, one
        ``[G, P*BS]`` score row per head; ``k_tile(j, h)`` /
        ``v_tile(j, h)`` give page j's head-h ``[BS, D]`` tile.  A page
        past the live count holds stale (finite) data: its positions are
        masked, and its scale is the last live page's."""
        k_pos = n * P * BS + jax.lax.broadcasted_iota(
            jnp.int32, (1, P * BS), 1)
        mask = k_pos < seq_len
        if sliding_window > 0:
            mask &= k_pos > q_pos - sliding_window
        cols = [jnp.minimum(n * P + j, jnp.maximum(npages - 1, 0))
                for j in range(P)]

        def scale_row(ref, h):                          # [1, P*BS]
            return jnp.concatenate(
                [jnp.full((1, BS), ref[h, bt_ref[b, c]], jnp.float32)
                 for c in cols], axis=1)

        for h in range(num_kv_heads):
            q = q_ref[0, h].astype(cdt)                 # [G, D]
            k = jnp.concatenate([k_tile(j, h) for j in range(P)], axis=0)
            s = jax.lax.dot_general(
                q, k.astype(cdt), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            if quantized:
                s = s * scale_row(ks_ref, h)
            if use_alibi:
                s = s - slopes_ref[h] * jnp.maximum(
                    q_pos - k_pos, 0).astype(jnp.float32)
            s = jnp.where(mask, s, NEG_INF)             # [G, P*BS]
            m_prev = m_ref[h]                           # [G, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)                      # f32
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            if quantized:
                p = p * scale_row(vs_ref, h)            # each page's p @ V
            v = jnp.concatenate([v_tile(j, h) for j in range(P)], axis=0)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)     # [G, D]
            m_ref[h] = m_new

    def init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)

    if not walk:
        i = pl.program_id(1)
        pl.when(i == 0)(init)
        pl.when(i < npages)(lambda: attend(
            i, lambda j, h: k_ref[0, 0, h], lambda j, h: v_ref[0, 0, h]))
        pl.when(i == pl.num_programs(1) - 1)(finish)
        return

    layer = ly_ref[0]

    def next_live(c):
        """The first slot at or after ``c`` with a live page, else
        ``num_slots``."""
        return jax.lax.while_loop(
            lambda c: (c < num_slots) & (
                sl_ref[jnp.minimum(c, num_slots - 1)] == 0),
            lambda c: c + 1, c)

    def block_dma(c, n, buf, wait: bool):
        """Start (or wait for) the page DMAs of block ``n`` of slot ``c``
        into buffer ``buf``: one per live page, none past the last."""
        live = (sl_ref[c] + BS - 1) // BS
        for j in range(P):
            col = n * P + j

            @pl.when(col < live)
            def _():
                page = bt_ref[c, col]
                for kind, (pool, dst) in enumerate(((k_ref, k_buf),
                                                    (v_ref, v_buf))):
                    cp = pltpu.make_async_copy(pool.at[layer, page],
                                               dst.at[buf, j],
                                               sems.at[kind, buf])
                    if wait:
                        cp.wait()
                    else:
                        cp.start()

    @pl.when(b == 0)
    def _prime():                    # the walk's first block
        buf_idx[0] = 0
        if not quantized:
            # a page past the live count is computed and masked: its
            # buffer must never hold uninitialized (NaN) floats
            v_buf[...] = jnp.zeros_like(v_buf)
        first = next_live(0)

        @pl.when(first < num_slots)
        def _():
            block_dma(first, 0, 0, wait=False)

    nblocks = (npages + P - 1) // P
    nxt = next_live(b + 1)
    base = buf_idx[0]
    init()

    def block(n, carry):
        buf = (base + n) % 2

        @pl.when(n + 1 < nblocks)
        def _():
            block_dma(b, n + 1, 1 - buf, wait=False)

        @pl.when((n + 1 == nblocks) & (nxt < num_slots))
        def _():
            block_dma(nxt, 0, 1 - buf, wait=False)

        block_dma(b, n, buf, wait=True)
        attend(n, lambda j, h: k_buf[buf, j, h], lambda j, h: v_buf[buf, j, h])
        return carry

    jax.lax.fori_loop(0, nblocks, block, 0)
    buf_idx[0] = (base + nblocks) % 2
    finish()


def group_slopes(alibi_slopes, KV, G):
    """[H] ALiBi slopes -> the kernels' ``[KV, G, 1]`` operand (zeros
    when the model has none; the kernel then never reads them)."""
    if alibi_slopes is None:
        return jnp.zeros((KV, G, 1), jnp.float32)
    return alibi_slopes.astype(jnp.float32).reshape(KV, G, 1)


def paged_decode_call(q, k_pool, v_pool, k_scales, v_scales, layer,
                      block_table, seq_lens, alibi_slopes, *,
                      sliding_window: int, interpret) -> jnp.ndarray:
    """The decode ``pallas_call`` for both pool formats over the stacked
    ``[L, NB, KV, BS, D]`` pool at ``layer`` (a traced or static scalar;
    int8 when ``k_scales`` is given, with ``[L, NB, KV]`` f32 scales)."""
    B, H, D = q.shape
    L, NB, KV, BS, _ = k_pool.shape
    G = H // KV
    MB = block_table.shape[1]
    quantized = k_scales is not None
    walk = D % LANES == 0
    P = pages_per_block(KV, BS, D, k_pool.dtype.itemsize, MB) if walk else 1
    slopes = group_slopes(alibi_slopes, KV, G)
    qg = q.reshape(B, KV, G, D)
    layer = jnp.asarray(layer, jnp.int32)
    prefetch = [block_table, seq_lens, layer.reshape(1)]
    if quantized:
        # the layer's scales as [KV, NB] SMEM rows: one f32 per (page,
        # head), NB along the row
        prefetch += [jnp.swapaxes(jax.lax.dynamic_index_in_dim(
            s, layer, 0, keepdims=False), 0, 1) for s in (k_scales, v_scales)]

    kernel = functools.partial(
        _pa_kernel, block_size=BS, pages=P, num_slots=B, num_kv_heads=KV,
        use_alibi=alibi_slopes is not None, sliding_window=sliding_window,
        quantized=quantized, walk=walk)

    def per_slot(b, *_):
        return (b, 0, 0, 0)

    # the grid walk's paging step: the layer and the physical page come
    # from the prefetched scalars; entries past the live pages re-resolve
    # to the last live page, which Pallas does not DMA again (a slot with
    # no tokens reads the layer's page 0, not its stale first entry)
    def page_map(b, i, bt, sl, ly, *_):
        return (ly[0], bt[b, _clamp_live(i, sl[b], BS)] * (sl[b] > 0),
                0, 0, 0)

    if walk:
        grid = (B,)
        page = pl.BlockSpec(memory_space=pl.ANY)
        buf = pltpu.VMEM((2, P, KV, BS, D), k_pool.dtype)
        scratch = [buf, buf, pltpu.SemaphoreType.DMA((2, 2)),  # (K/V, buf)
                   pltpu.SMEM((1,), jnp.int32)]        # the next block's buf
    else:
        grid = (B, MB)
        page = pl.BlockSpec((1, 1, KV, BS, D), page_map)
        scratch = []

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=[pl.BlockSpec((KV, G, 1), lambda b, *_: (0, 0, 0)),
                      pl.BlockSpec((1, KV, G, D), per_slot), page, page],
            out_specs=pl.BlockSpec((1, KV, G, D), per_slot),
            scratch_shapes=scratch + [
                pltpu.VMEM((KV, G, D), jnp.float32),
                pltpu.VMEM((KV, G, 1), jnp.float32),
                pltpu.VMEM((KV, G, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        # sequential: a slot's last block starts the next slot's first
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid)),
        interpret=interpret,
        name="paged_attention_quant" if quantized else "paged_attention",
    )(*prefetch, slopes, qg, k_pool, v_pool)
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
