"""Attention layer (full / sliding-window) built on the Opt-GQA core.

Train/prefill use the flash kernel (or its XLA reference); decode uses the
paged kernel over the block-table pool, or a ring cache for sliding-window
layers (DESIGN.md §5).
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.alibi import alibi_slopes
from repro.core.kv_quant import KVCache, kv_write_decode, kv_write_prefill
from repro.kernels import ops
from repro.models.layers import dense_init, linear, rope
from repro.runtime.sharding import ParallelCtx, shard, shard_map

Params = Dict[str, jnp.ndarray]


def attn_init(key, cfg: ModelConfig) -> Params:
    d, H, KV, Dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, H, Dh), in_axis_size=d),
        "wk": dense_init(ks[1], (d, KV, Dh), in_axis_size=d),
        "wv": dense_init(ks[2], (d, KV, Dh), in_axis_size=d),
        "wo": dense_init(ks[3], (H, Dh, d), in_axis_size=H * Dh),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, Dh))
        p["bk"] = jnp.zeros((KV, Dh))
        p["bv"] = jnp.zeros((KV, Dh))
    return p


def _qkv(cfg: ModelConfig, p: Params, x: jnp.ndarray, positions,
         ctx: Optional[ParallelCtx], rt: Optional[dict] = None):
    H, KV, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = linear(x, p["wq"], rt, out_tail=(H, Dh))
    k = linear(x, p["wk"], rt, out_tail=(KV, Dh))
    v = linear(x, p["wv"], rt, out_tail=(KV, Dh))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if cfg.pos_emb == "rope":
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    if ctx is not None:
        tp = ctx.tp_axis if cfg.num_heads % ctx.tp_size == 0 else None
        kv_tp = ctx.tp_axis if cfg.num_kv_heads % ctx.tp_size == 0 else None
        q = shard(ctx, q, P(ctx.dp_axes, None, tp, None))
        k = shard(ctx, k, P(ctx.dp_axes, None, kv_tp, None))
        v = shard(ctx, v, P(ctx.dp_axes, None, kv_tp, None))
    return q, k, v


def _slopes(cfg: ModelConfig):
    return alibi_slopes(cfg.num_heads) if cfg.pos_emb == "alibi" else None


def attn_apply(cfg: ModelConfig, p: Params, x: jnp.ndarray,
               ctx: Optional[ParallelCtx], *, kind: str = "full",
               pos_offset=0, rt: Optional[dict] = None) -> jnp.ndarray:
    """Train/prefill path. x: [B, S, d] -> [B, S, d]."""
    rt = rt or {}
    B, S, d = x.shape
    positions = pos_offset + jnp.arange(S)
    q, k, v = _qkv(cfg, p, x, positions, ctx, rt)
    win = cfg.sliding_window if kind == "sliding" else 0
    if rt.get("skip_mixer_core"):
        # roofline decomposition lower: mixer core replaced by identity
        # (kernel terms added analytically — launch/roofline.py)
        o = q + 1e-30 * (k.sum(2, keepdims=True) + v.sum(2, keepdims=True))
    else:
        o = ops.flash_attention(
            q, k, v, _slopes(cfg), causal=not cfg.is_encoder,
            sliding_window=win,
            use_pallas=rt.get("use_pallas"), interpret=rt.get("interpret"))
    if ctx is not None:
        tp = ctx.tp_axis if cfg.num_heads % ctx.tp_size == 0 else None
        o = shard(ctx, o, P(ctx.dp_axes, None, tp, None))
    B_, S_, H_, D_ = o.shape
    return linear(o.reshape(B_, S_, H_ * D_), p["wo"], rt)


# --------------------------------------------------------------------------
# Serving paths: prefill-with-cache-write and paged decode.
# --------------------------------------------------------------------------

def attn_prefill(cfg: ModelConfig, p: Params, x: jnp.ndarray,
                 ctx: Optional[ParallelCtx], *, kind: str,
                 cache: KVCache, layer: int, block_table, ctx_lens,
                 rt: Optional[dict] = None):
    """Prefill: attention over the prompt AND write K/V into the paged pool.

    Returns (y, cache). cache pools: [L, NB, KV, BS, D] (quantize-on-write
    when the cache carries int8 values + scales).
    """
    rt = rt or {}
    B, S, _ = x.shape
    positions = jnp.arange(S)
    q, k, v = _qkv(cfg, p, x, positions, ctx, rt)
    win = cfg.sliding_window if kind == "sliding" else 0
    if rt.get("skip_mixer_core"):
        o = q + 1e-30 * (k.sum(2, keepdims=True) + v.sum(2, keepdims=True))
    else:
        o = ops.flash_attention(q, k, v, _slopes(cfg), causal=True,
                                sliding_window=win,
                                use_pallas=rt.get("use_pallas"),
                                interpret=rt.get("interpret"))
    cache = kv_write_prefill(cache, layer, k, v, block_table, ctx_lens)
    B_, S_, H_, D_ = o.shape
    y = linear(o.reshape(B_, S_, H_ * D_), p["wo"], rt)
    return y, cache


def attn_decode(cfg: ModelConfig, p: Params, x: jnp.ndarray,
                ctx: Optional[ParallelCtx], *, kind: str,
                cache: KVCache, layer: int, block_table, seq_lens,
                rt: Optional[dict] = None):
    """One-token decode. x: [B, d]; cache pools [L, NB, KV, BS, D] (ring
    for SWA; int8 values + [L, NB, KV] scales when quantized).

    Returns (y [B, d], cache).

    Under a mesh, the cache write + paged attention run inside a shard_map
    island manual over the dp axes: each dp shard owns its sequences' pool
    blocks and block table (local ids), so decode attention is collective-
    free (DESIGN.md §4). The model axis stays auto (TP in the projections).
    """
    rt = rt or {}
    B, d = x.shape
    positions = (seq_lens - 1)[:, None]                   # [B,1] absolute pos
    q, k, v = _qkv(cfg, p, x[:, None, :], positions, None, rt)
    q, k, v = q[:, 0], k[:, 0], v[:, 0]                   # [B, H/KV, D]

    win = cfg.sliding_window if kind == "sliding" else 0
    cache_leaves, cache_def = jax.tree.flatten(cache)

    def island(q, k, v, block_table, seq_lens, layer, *leaves):
        o, c = _decode_cache_attend(cfg, q, k, v,
                                    jax.tree.unflatten(cache_def, leaves),
                                    block_table, seq_lens, layer, win, rt)
        return (o, *jax.tree.leaves(c))

    if ctx is not None and B % ctx.dp_size == 0 and ctx.dp_size > 1:
        dp = ctx.dp_axes
        # every cache leaf — value pool [L,NB,KV,BS,D] or scale pool
        # [L,NB,KV] — shards over dp on the blocks dim.
        leaf_specs = tuple(P(None, dp) for _ in cache_leaves)
        o, *leaves = shard_map(
            island, mesh=ctx.mesh,
            in_specs=(P(dp), P(dp), P(dp), P(dp), P(dp), P(), *leaf_specs),
            out_specs=(P(dp), *leaf_specs),
            axis_names=set(dp), check_vma=False,
        )(q, k, v, block_table, seq_lens, jnp.asarray(layer, jnp.int32),
          *cache_leaves)
        cache = jax.tree.unflatten(cache_def, leaves)
    else:
        o, cache = _decode_cache_attend(cfg, q, k, v, cache, block_table,
                                        seq_lens, layer, win, rt)
    y = linear(o.reshape(o.shape[0], -1), p["wo"], rt)
    return y, cache


def _decode_cache_attend(cfg, q, k, v, cache: KVCache, block_table,
                         seq_lens, layer, win, rt):
    """Local (per-dp-shard) cache write + attention; block ids are local."""
    if win > 0:
        # ring cache: slot = pos % cache_len; all cached tokens are the most
        # recent ones -> attend over valid slots, mask by window distance
        # via the stored-position trick (DESIGN.md §5). bf16-only: int8 KV
        # is rejected for sliding archs at decode-state construction.
        from repro.core.paged_cache import gather_kv, write_decode_kv
        k_pool, v_pool = cache.k, cache.v
        cache_len = block_table.shape[1] * cache.block_size
        # inactive slots (seq_len == 0) get position -1 -> write dropped
        ring_pos = jnp.where(seq_lens > 0, (seq_lens - 1) % cache_len, -1)
        k_pool = write_decode_kv(k_pool, layer, k, block_table, ring_pos)
        v_pool = write_decode_kv(v_pool, layer, v, block_table, ring_pos)
        cache = cache._replace(k=k_pool, v=v_pool)
        kc = gather_kv(k_pool, layer, block_table, cache_len)
        vc = gather_kv(v_pool, layer, block_table, cache_len)
        # absolute position of ring slot s for a sequence of length t:
        # pos(s) = t-1 - ((ring_pos - s) mod cache_len)
        s_idx = jnp.arange(cache_len)[None, :]
        kpos = (seq_lens - 1)[:, None] - jnp.mod(ring_pos[:, None] - s_idx,
                                                 cache_len)
        valid = (kpos >= 0) & (kpos > (seq_lens - 1)[:, None] - win)
        if rt.get("skip_mixer_core"):
            o = q * (1 + 1e-30 * (kc.sum() + vc.sum() + valid.sum()))
        else:
            o = _ring_attention(q, kc, vc, valid)
    else:
        with jax.named_scope("kv_write"):
            cache = kv_write_decode(cache, layer, k, v, block_table,
                                    seq_lens - 1)
        if rt.get("skip_mixer_core"):
            o = q * (1 + 1e-30 * seq_lens.sum())
        elif cache.quantized:
            o = ops.paged_attention_quant(
                q, cache.k, cache.k_scale, cache.v, cache.v_scale, layer,
                block_table, seq_lens, _slopes(cfg),
                use_pallas=rt.get("use_pallas"),
                interpret=rt.get("interpret"))
        else:
            o = ops.paged_attention(q, cache.k, cache.v, layer, block_table,
                                    seq_lens, _slopes(cfg),
                                    use_pallas=rt.get("use_pallas"),
                                    interpret=rt.get("interpret"))
    return o, cache


def _ring_attention(q, kc, vc, valid):
    """Dense decode attention over a gathered ring cache with a slot mask."""
    B, H, D = q.shape
    KV = kc.shape[2]
    G = H // KV
    scale = D ** -0.5
    qg = q.reshape(B, KV, G, D).astype(jnp.float32)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, kc.astype(jnp.float32)) * scale
    s = jnp.where(valid[:, None, None, :], s, -0.7 * jnp.finfo(jnp.float32).max)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", w, vc.astype(jnp.float32))
    return o.reshape(B, H, D).astype(q.dtype)
