"""Pallas TPU flash-attention kernel for Opt-GQA prefill.

Adaptation of the paper's DCU attention kernel to TPU (DESIGN.md §3):

* Q is laid out as [B, KV, G, S, D] (G = q_per_kv): the grid iterates over
  *KV heads*, and each K/V tile loaded into VMEM is contracted against all
  G query heads of its group at once — the paper's "shared key-value"
  becomes a batched MXU matmul with G× higher arithmetic intensity.
* ALiBi bias is computed from iota inside the tile (never a [S,S] mask).
* Causal / sliding-window tiles that are fully masked are *skipped*
  (pl.when) — the sparse-attention half of the paper's title.
* Online softmax (flash) with f32 accumulators in VMEM scratch.

Tile sizes default to MXU-aligned (128) in S and D.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.paged_attention import group_slopes

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _fa_kernel(slopes_ref, q_ref, k_ref, v_ref, o_ref,
               acc_ref, m_ref, l_ref, *,
               block_q: int, block_k: int, causal: bool,
               sliding_window: int, use_alibi: bool, q_offset: int,
               num_k_blocks: int, seq_len_k: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_pos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    dist = q_pos - k_pos

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)               # [G, Tq, D]
        k = k_ref[0, 0].astype(jnp.float32)               # [Tk, D]
        v = v_ref[0, 0].astype(jnp.float32)               # [Tk, D]
        scale = q.shape[-1] ** -0.5
        s = jax.lax.dot_general(q, k, (((2,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        # s: [G, Tq, Tk]
        if use_alibi:
            s = s - slopes_ref[0] \
                * jnp.maximum(dist, 0)[None].astype(jnp.float32)
        mask = k_pos < seq_len_k
        if causal:
            mask &= dist >= 0
        if sliding_window > 0:
            mask &= dist < sliding_window
        s = jnp.where(mask[None], s, NEG_INF)

        m_prev = m_ref[...]                               # [G, Tq]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[..., None])                  # [G, Tq, Tk]
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1)
        m_ref[...] = m_new
        pv = jax.lax.dot_general(p, v, (((2,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[..., None] + pv

    if causal or sliding_window > 0:
        # tile-skip: live iff some (q,k) in tile satisfies the band.
        q_hi = q_offset + (iq + 1) * block_q - 1
        q_lo = q_offset + iq * block_q
        k_lo = ik * block_k
        k_hi = (ik + 1) * block_k - 1
        live = True
        if causal:
            live = jnp.logical_and(live, k_lo <= q_hi)
        if sliding_window > 0:
            live = jnp.logical_and(live, k_hi > q_lo - sliding_window)
        pl.when(live)(_compute)
    else:
        _compute()

    @pl.when(ik == num_k_blocks - 1)
    def _final():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


def _fa_chunk_kernel(block_table_ref, info_ref,      # scalar prefetch (SMEM)
                     slopes_ref, q_ref, *refs,
                     block_q: int, block_size: int, num_pool_blocks: int,
                     num_raw_blocks: int, num_kv_heads: int, use_alibi: bool,
                     sliding_window: int, quantized: bool):
    """Dynamic-offset chunk-prefill flash body (one sequence).

    The K axis of the grid walks TWO sources: the first
    ``num_pool_blocks`` steps are paged-pool pages holding the already-
    prefilled prefix ``[0, q_offset)`` (``(layer, physical page)`` of the
    stacked pool resolved from the prefetched scalars, exactly like
    ``paged_attention.py``: the pool is read in place), the
    remaining ``num_raw_blocks`` steps are the chunk's own raw K/V tiles
    at absolute positions ``[q_offset, q_offset + W)`` — the chunk
    attends its own tokens unquantized / un-roundtripped, matching the
    whole-prompt prefill semantics (and keeping int8 parity).

    Every tile holds all KV heads, head-major (``[KV, BS, D]``, the
    pool's own page layout; the raw tiles are laid out the same way); a
    static loop over heads contracts each head's ``[BS, D]`` tile with
    its G query heads, as the decode kernel does.

    ``info_ref`` holds the *traced* scalars ``[q_offset, total_len,
    layer]`` — the causal mask, ALiBi distances, the live-page clamp and
    the pool's layer are all computed from them, so every chunk of every
    prompt and every layer runs from one compiled executable.
    ``quantized`` reuses the in-register dequant of
    ``paged_attention_quant.py``: pool tiles are int8 with one f32 scale
    per (page, kv head) in SMEM; raw tiles are always full precision.
    """
    if quantized:
        (kp_ref, ks_ref, vp_ref, vs_ref, kr_ref, vr_ref,
         o_ref, acc_ref, m_ref, l_ref) = refs
    else:
        kp_ref, vp_ref, kr_ref, vr_ref, o_ref, acc_ref, m_ref, l_ref = refs
    iq = pl.program_id(0)
    ik = pl.program_id(1)
    q_off = info_ref[0]
    tlen = info_ref[1]

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_pos = q_off + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_size), 0)

    def _accum(h, k, v, k_pos, mask):
        q = q_ref[h].astype(jnp.float32)                   # [G, Tq, D]
        scale = q.shape[-1] ** -0.5
        s = jax.lax.dot_general(q, k, (((2,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        dist = q_pos - k_pos                               # [Tq, Tk]
        if use_alibi:
            s = s - slopes_ref[h] \
                * jnp.maximum(dist, 0)[None].astype(jnp.float32)
        if sliding_window > 0:
            mask &= dist < sliding_window
        s = jnp.where(mask[None], s, NEG_INF)
        m_prev = m_ref[h]                                  # [G, Tq]
        l_prev = l_ref[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[..., None])                  # [G, Tq, Tk]
        l_ref[h] = l_prev * alpha + jnp.sum(p, axis=-1)
        m_ref[h] = m_new
        pv = jax.lax.dot_general(p, v, (((2,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[h] = acc_ref[h] * alpha[..., None] + pv

    # ---- pool pages: the prefix [0, q_offset). Pages past the prefix
    # are skipped (their DMA re-resolved to the last live page, compute
    # gated off) — the HBM walk is ceil(q_offset / block_size), never
    # the static table capacity.
    def _pool():
        k_pos = ik * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_size), 1)
        for h in range(num_kv_heads):
            k = kp_ref[0, 0, h].astype(jnp.float32)        # [BS, D]
            v = vp_ref[0, 0, h].astype(jnp.float32)
            if quantized:
                k = k * ks_ref[0, 0, 0, h]
                v = v * vs_ref[0, 0, 0, h]
            _accum(h, k, v, k_pos, k_pos < q_off)

    pool_live = jnp.logical_and(ik < num_pool_blocks,
                                ik * block_size < q_off)
    if sliding_window > 0:
        pool_live = jnp.logical_and(
            pool_live,
            (ik + 1) * block_size - 1 > q_off + iq * block_q
            - sliding_window)
    pl.when(pool_live)(_pool)

    # ---- raw chunk tiles: positions [q_offset, q_offset + W), causal
    # within the chunk; padded tail positions masked by total_len.
    def _raw():
        j = ik - num_pool_blocks
        k_pos = q_off + j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_size), 1)
        mask = (k_pos < tlen) & (q_pos - k_pos >= 0)
        for h in range(num_kv_heads):
            k = kr_ref[0, h].astype(jnp.float32)           # [BS, D]
            v = vr_ref[0, h].astype(jnp.float32)
            _accum(h, k, v, k_pos, mask)

    j = ik - num_pool_blocks
    raw_live = jnp.logical_and(
        ik >= num_pool_blocks,
        jnp.logical_and(j * block_size <= iq * block_q + block_q - 1,
                        q_off + j * block_size < tlen))
    if sliding_window > 0:
        raw_live = jnp.logical_and(
            raw_live,
            (j + 1) * block_size - 1 > iq * block_q - sliding_window)
    pl.when(raw_live)(_raw)

    @pl.when(ik == num_pool_blocks + num_raw_blocks - 1)
    def _final():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("sliding_window", "block_q", "interpret"))
def flash_attention_chunk(
    q: jnp.ndarray,                  # [1, W, H, D] — one chunk, one sequence
    k_pool: jnp.ndarray,             # [L, NB, KV, BS, D] (int8 when quantized)
    v_pool: jnp.ndarray,
    layer: jnp.ndarray,              # i32 scalar: the layer read in place
    block_table: jnp.ndarray,        # [1, MB] int32
    q_offset: jnp.ndarray,           # i32 scalar (traced)
    total_len: jnp.ndarray,          # i32 scalar (traced): q_offset + live len
    k_raw: jnp.ndarray,              # [1, W, KV, D] — the chunk's own K/V
    v_raw: jnp.ndarray,
    alibi_slopes: Optional[jnp.ndarray] = None,   # [H]
    *,
    k_scales: Optional[jnp.ndarray] = None,       # [L, NB, KV] f32 (int8)
    v_scales: Optional[jnp.ndarray] = None,
    sliding_window: int = 0,
    block_q: int = 128,
    interpret: bool,
) -> jnp.ndarray:
    """Chunk-prefill attention straight over the paged pool (TPU serving).

    The dynamic-offset counterpart of ``flash_attention``: ``q_offset``
    and ``total_len`` are *device scalars* (scalar-prefetch operands), so
    the fixed-shape ``[1, W]`` serving chunk executable needs no gather
    of the pool to a contiguous ``[cap]`` view, no per-layer slice of the
    stacked pool and no per-offset recompile — the page walk is bounded
    by the live prefix length the way ``paged_attention`` bounds its
    decode walk.  Causality within the
    chunk is handled by raw-tile masking; the chunk's own K/V come from
    ``k_raw``/``v_raw`` (never pool-roundtripped, so int8 quantization
    noise only enters for *earlier* chunks' positions — identical
    semantics to the XLA oracle in ``ref.chunk_prefill_attention_ref``).
    """
    B, W, H, D = q.shape
    assert B == 1, "chunk executable serves one sequence per dispatch"
    L, NB, KV, BS, _ = k_pool.shape
    G = H // KV
    MB = block_table.shape[1]
    quantized = k_scales is not None
    slopes = group_slopes(alibi_slopes, KV, G)[..., None]    # [KV, G, 1, 1]

    bq = min(block_q, W)
    pq = (-W) % bq
    nq = (W + pq) // bq
    pr = (-W) % BS
    nr = (W + pr) // BS                              # raw chunk K tiles
    qg = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))[0] \
        .reshape(W + pq, KV, G, D).transpose(1, 2, 0, 3)   # [KV, G, Wq, D]
    # raw tiles keep the pool's head-major page layout: [nr, KV, BS, D]
    kr = jnp.pad(k_raw, ((0, 0), (0, pr), (0, 0), (0, 0))) \
        .reshape(nr, BS, KV, D).swapaxes(1, 2)
    vr = jnp.pad(v_raw, ((0, 0), (0, pr), (0, 0), (0, 0))) \
        .reshape(nr, BS, KV, D).swapaxes(1, 2)
    info = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                      jnp.asarray(total_len, jnp.int32),
                      jnp.asarray(layer, jnp.int32)])

    kernel = functools.partial(
        _fa_chunk_kernel, block_q=bq, block_size=BS, num_pool_blocks=MB,
        num_raw_blocks=nr, num_kv_heads=KV, use_alibi=alibi_slopes is not None,
        sliding_window=sliding_window, quantized=quantized)

    def page_map(iq, ik, bt, info):
        # pages past the live prefix re-resolve to its last live page
        # (Pallas skips the DMA when consecutive steps map to the same
        # block), so the walk is bounded by ceil(q_offset / BS).
        return (info[2], bt[0, _chunk_clamp(ik, info[0], BS, MB)], 0, 0, 0)

    def scale_map(iq, ik, bt, info):
        return (info[2], bt[0, _chunk_clamp(ik, info[0], BS, MB)], 0, 0)

    def raw_map(iq, ik, bt, info):
        return (jnp.clip(ik - MB, 0, nr - 1), 0, 0, 0)

    page = pl.BlockSpec((1, 1, KV, BS, D), page_map)
    in_specs = [
        pl.BlockSpec((KV, G, 1, 1), lambda iq, ik, bt, info: (0, 0, 0, 0)),
        pl.BlockSpec((KV, G, bq, D), lambda iq, ik, bt, info: (0, 0, iq, 0)),
    ]
    if quantized:
        # one f32 per (page, head) in SMEM; see paged_decode_call
        scale = pl.BlockSpec((1, 1, 1, KV), scale_map,
                             memory_space=pltpu.SMEM)
        in_specs += [page, scale, page, scale]
        args = [k_pool, k_scales.reshape(L, NB, 1, KV),
                v_pool, v_scales.reshape(L, NB, 1, KV)]
    else:
        in_specs += [page, page]
        args = [k_pool, v_pool]
    in_specs += [pl.BlockSpec((1, KV, BS, D), raw_map),
                 pl.BlockSpec((1, KV, BS, D), raw_map)]
    args += [kr, vr]

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,         # block_table, [off, len, layer]
            grid=(nq, MB + nr),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((KV, G, bq, D),
                                   lambda iq, ik, bt, info: (0, 0, iq, 0)),
            scratch_shapes=[
                pltpu.VMEM((KV, G, bq, D), jnp.float32),
                pltpu.VMEM((KV, G, bq), jnp.float32),
                pltpu.VMEM((KV, G, bq), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((KV, G, W + pq, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention_chunk",
    )(block_table, info, slopes, qg, *args)

    return out.transpose(2, 0, 1, 3).reshape(1, W + pq, H, D)[:, :W]


def _chunk_clamp(i, prefix_len, block_size, num_table_blocks):
    """Clamp K-grid step ``i`` to the prefix's last live table entry
    (``prefix_len`` may be 0 on a first chunk: clamp to entry 0, the
    kernel's ``pool_live`` guard skips the compute anyway)."""
    last = jnp.maximum((prefix_len + block_size - 1) // block_size, 1) - 1
    return jnp.minimum(jnp.minimum(i, num_table_blocks - 1), last)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "sliding_window", "block_q", "block_k",
                     "q_offset", "interpret"))
def flash_attention(
    q: jnp.ndarray,                  # [B, S, H, D]
    k: jnp.ndarray,                  # [B, S_k, KV, D]
    v: jnp.ndarray,
    alibi_slopes: Optional[jnp.ndarray] = None,   # [H]
    *,
    causal: bool = True,
    sliding_window: int = 0,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool,
) -> jnp.ndarray:
    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    # pad seq to tile multiples
    pq = (-Sq) % block_q
    pk = (-Sk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))

    qg = qp.reshape(B, Sq + pq, KV, G, D).transpose(0, 2, 3, 1, 4)  # [B,KV,G,S,D]
    kg = kp.transpose(0, 2, 1, 3)                                    # [B,KV,S,D]
    vg = vp.transpose(0, 2, 1, 3)
    use_alibi = alibi_slopes is not None
    slopes = group_slopes(alibi_slopes, KV, G)[..., None]    # [KV, G, 1, 1]

    nq = (Sq + pq) // block_q
    nk = (Sk + pk) // block_k
    grid = (B, KV, nq, nk)

    kernel = functools.partial(
        _fa_kernel, block_q=block_q, block_k=block_k, causal=causal,
        sliding_window=sliding_window, use_alibi=use_alibi,
        q_offset=q_offset, num_k_blocks=nk, seq_len_k=Sk)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, G, 1, 1), lambda b, h, iq, ik: (h, 0, 0, 0)),
                pl.BlockSpec((1, 1, G, block_q, D),
                             lambda b, h, iq, ik: (b, h, 0, iq, 0)),
                pl.BlockSpec((1, 1, block_k, D),
                             lambda b, h, iq, ik: (b, h, ik, 0)),
                pl.BlockSpec((1, 1, block_k, D),
                             lambda b, h, iq, ik: (b, h, ik, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, G, block_q, D),
                                   lambda b, h, iq, ik: (b, h, 0, iq, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, block_q, D), jnp.float32),
                pltpu.VMEM((G, block_q), jnp.float32),
                pltpu.VMEM((G, block_q), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, Sq + pq, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(slopes, qg, kg, vg)

    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq + pq, H, D)
    return out[:, :Sq]
