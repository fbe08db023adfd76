"""Model assembly for all families: init / forward / loss / prefill / decode.

Homogeneous stacks (dense, moe, ssm, encoder, vlm) scan over layer-stacked
params (fast compiles at 64+ layers); the heterogeneous hybrid
(recurrentgemma) python-loops over two per-kind stacks. Decode threads the
paged KV pool / SSM state pools through the layer loop.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.kv_quant import cache_from_state, cache_to_state
from repro.core.sampling import sample_from_logits
from repro.models import ssm as ssm_mod
from repro.models.attention import (attn_apply, attn_decode, attn_init,
                                    attn_prefill)
from repro.models.layers import (apply_norm, embed_init, linear, mlp_apply,
                                 mlp_init, norm_init, unembed)
from repro.models.moe import moe_apply, moe_init
from repro.runtime.sharding import ParallelCtx, shard, shard_map

Params = Dict[str, Any]


def _is_homogeneous(cfg: ModelConfig) -> bool:
    return len({cfg.layer_kind(i) for i in range(cfg.num_layers)}) == 1


# --------------------------------------------------------------------------
# Init
# --------------------------------------------------------------------------

def init_layer(key, cfg: ModelConfig, kind: str, ep: int = 1) -> Params:
    ks = jax.random.split(key, 4)
    if kind == "ssm":
        return {"attn_norm": norm_init(cfg.d_model, cfg.norm),
                "ssm": ssm_mod.ssm_init(ks[0], cfg)}
    p: Params = {"attn_norm": norm_init(cfg.d_model, cfg.norm),
                 "mlp_norm": norm_init(cfg.d_model, cfg.norm)}
    if kind == "recurrent":
        p["rec"] = ssm_mod.rglru_init(ks[0], cfg)
    else:
        p["attn"] = attn_init(ks[0], cfg)
    if cfg.num_experts:
        p["moe"] = moe_init(ks[1], cfg, ep)
    else:
        p["mlp"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.act)
    return p


def init_params(cfg: ModelConfig, key, ep: int = 1) -> Params:
    ks = jax.random.split(key, 6)
    params: Params = {"embed": embed_init(ks[0], cfg.vocab_size, cfg.d_model),
                      "final_norm": norm_init(cfg.d_model, cfg.norm)}
    if not cfg.tie_embeddings:
        params["head"] = (jax.random.normal(ks[1], (cfg.d_model, cfg.vocab_size))
                          * cfg.d_model ** -0.5)
    if cfg.frontend == "audio_frames":
        params["frontend_proj"] = (jax.random.normal(
            ks[2], (cfg.d_model, cfg.d_model)) * cfg.d_model ** -0.5)

    L = cfg.num_layers
    if _is_homogeneous(cfg):
        kind = cfg.layer_kind(0)
        lkeys = jax.random.split(ks[3], L)
        params["layers"] = jax.vmap(
            lambda k: init_layer(k, cfg, kind, ep))(lkeys)
    else:
        kinds = [cfg.layer_kind(i) for i in range(L)]
        for kset, name in ((("recurrent",), "rec_layers"),
                           (("full", "sliding"), "attn_layers")):
            idx = [i for i, k in enumerate(kinds) if k in kset]
            if idx:
                lkeys = jax.random.split(jax.random.fold_in(ks[3], hash(name) % 2**30),
                                         len(idx))
                params[name] = jax.vmap(
                    lambda k, kk=kinds[idx[0]]: init_layer(k, cfg, kk, ep))(lkeys)
    return params


# --------------------------------------------------------------------------
# Layer application (train / plain forward)
# --------------------------------------------------------------------------

def apply_layer(cfg: ModelConfig, lp: Params, x: jnp.ndarray, kind: str,
                ctx: Optional[ParallelCtx], rt: Optional[dict]) -> jnp.ndarray:
    h = apply_norm(lp["attn_norm"], x, cfg.norm, cfg.norm_eps)
    if kind == "ssm":
        return x + ssm_mod.ssm_apply(cfg, lp["ssm"], h, rt)
    if kind == "recurrent":
        mix = ssm_mod.rglru_apply(cfg, lp["rec"], h, rt)
    else:
        mix = attn_apply(cfg, lp["attn"], h, ctx, kind=kind, rt=rt)
    x = x + mix
    h = apply_norm(lp["mlp_norm"], x, cfg.norm, cfg.norm_eps)
    if cfg.num_experts:
        y = moe_apply(cfg, lp["moe"], h, ctx, rt)
    else:
        y = mlp_apply(lp["mlp"], h, cfg.act, rt)
    if ctx is not None:
        y = shard(ctx, y, P(ctx.dp_axes, None, None))
    return x + y


def _embed_inputs(cfg: ModelConfig, params: Params, batch: Dict[str, Any],
                  ctx, rt) -> jnp.ndarray:
    if cfg.frontend == "audio_frames":
        x = linear(batch["frames"], params["frontend_proj"], rt)
    else:
        x = params["embed"][batch["tokens"]]
        if cfg.frontend == "vision_patches" and "vision_embeds" in batch:
            x = jnp.concatenate(
                [batch["vision_embeds"].astype(x.dtype), x], axis=1)
    if ctx is not None:
        x = shard(ctx, x, P(ctx.dp_axes, None, None))
    return x.astype(jnp.dtype(cfg.dtype))


def forward(cfg: ModelConfig, params: Params, batch: Dict[str, Any],
            ctx: Optional[ParallelCtx] = None,
            rt: Optional[dict] = None) -> jnp.ndarray:
    """Full causal (or bidirectional-encoder) forward -> logits [B, S, V]."""
    rt = rt or {}
    x = _embed_inputs(cfg, params, batch, ctx, rt)
    L = cfg.num_layers

    if _is_homogeneous(cfg) and rt.get("scan_layers", True):
        kind = cfg.layer_kind(0)
        policy = rt.get("remat_policy")

        def body(h, lp):
            out = apply_layer(cfg, lp, h, kind, ctx, rt)
            return out, None

        body_r = jax.checkpoint(body, policy=policy)
        x, _ = jax.lax.scan(body_r, x, params["layers"])
    else:
        counters = {"rec_layers": 0, "attn_layers": 0, "layers": 0}
        for i in range(L):
            kind = cfg.layer_kind(i)
            if _is_homogeneous(cfg):
                stack, cname = params["layers"], "layers"
            elif kind == "recurrent":
                stack, cname = params["rec_layers"], "rec_layers"
            else:
                stack, cname = params["attn_layers"], "attn_layers"
            j = counters[cname]
            counters[cname] += 1
            lp = jax.tree.map(lambda a: a[j], stack)
            layer_fn = jax.checkpoint(
                lambda p_, x_, kind_=kind: apply_layer(cfg, p_, x_, kind_,
                                                       ctx, rt),
                policy=rt.get("remat_policy"))
            x = layer_fn(lp, x)

    x = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    logits = unembed(x, params["embed"], params.get("head"))
    if ctx is not None:
        tp = ctx.tp_axis if cfg.vocab_size % ctx.tp_size == 0 else None
        logits = shard(ctx, logits, P(ctx.dp_axes, None, tp))
    return logits


def loss_fn(cfg: ModelConfig, params: Params, batch: Dict[str, Any],
            ctx: Optional[ParallelCtx] = None,
            rt: Optional[dict] = None) -> jnp.ndarray:
    """Next-token (or frame-label) cross entropy, mean over valid tokens."""
    if cfg.is_encoder:
        logits = forward(cfg, params, batch, ctx, rt)
        labels = batch["labels"]
    else:
        tokens = batch["tokens"]
        inp = {**batch, "tokens": tokens[:, :-1]}
        logits = forward(cfg, params, inp, ctx, rt)
        labels = tokens[:, 1:]
        if cfg.frontend == "vision_patches" and "vision_embeds" in batch:
            logits = logits[:, batch["vision_embeds"].shape[1]:]
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    mask = batch.get("loss_mask")
    if mask is not None:
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return nll.mean()


# --------------------------------------------------------------------------
# Serving: decode state + prefill + decode_step
# --------------------------------------------------------------------------

def attn_layer_count(cfg: ModelConfig) -> Tuple[int, int]:
    """(#attention layers, #recurrent/ssm layers)."""
    kinds = [cfg.layer_kind(i) for i in range(cfg.num_layers)]
    na = sum(k in ("full", "sliding") for k in kinds)
    return na, cfg.num_layers - na


def make_decode_state(cfg: ModelConfig, max_seqs: int, num_blocks: int,
                      max_blocks_per_seq: int,
                      dtype=None, kv_cache_dtype: Optional[str] = None
                      ) -> Dict[str, jnp.ndarray]:
    """``kv_cache_dtype="int8"`` builds the quantized pool format (int8
    values + per-block-per-head f32 scales); the default keeps the dense
    ``dtype`` pool (bf16/f32/fp8 via ``cfg.paging.cache_dtype``)."""
    from repro.core.kv_quant import (make_kv_pool_quant,
                                     normalize_kv_cache_dtype)
    from repro.core.paged_cache import make_kv_pool
    kv_mode = normalize_kv_cache_dtype(kv_cache_dtype)
    dtype = dtype if dtype is not None else jnp.dtype(cfg.paging.cache_dtype)
    na, nr = attn_layer_count(cfg)
    if kv_mode == "int8" and not na:
        raise ValueError(
            f"kv_cache_dtype='int8' requested but {cfg.name} has no "
            "attention KV cache to quantize (attention-free family "
            f"{cfg.family!r}); drop the flag — SSM/recurrent state pools "
            "are not paged KV")
    st: Dict[str, jnp.ndarray] = {
        "seq_lens": jnp.zeros((max_seqs,), jnp.int32),
    }
    if na:
        bs = cfg.paging.block_size
        if kv_mode == "int8":
            if any(cfg.layer_kind(i) == "sliding"
                   for i in range(cfg.num_layers)):
                raise ValueError(
                    "kv_cache_dtype='int8' does not support sliding-window "
                    f"(ring-cache) attention layers ({cfg.name}); the ring "
                    "overwrite pattern defeats per-block scale tracking")
            kp, vp, ks, vs = make_kv_pool_quant(
                na, num_blocks, bs, cfg.num_kv_heads, cfg.resolved_head_dim)
            st.update(k_scales=ks, v_scales=vs)
        else:
            kp, vp = make_kv_pool(na, num_blocks, bs, cfg.num_kv_heads,
                                  cfg.resolved_head_dim, dtype)
        st.update(k_pool=kp, v_pool=vp,
                  block_table=jnp.zeros((max_seqs, max_blocks_per_seq),
                                        jnp.int32))
    if cfg.family == "ssm":
        din = cfg.ssm_expand * cfg.d_model
        st["ssm_h"] = jnp.zeros((cfg.num_layers, max_seqs, din, cfg.ssm_state),
                                jnp.float32)
        st["ssm_conv"] = jnp.zeros((cfg.num_layers, max_seqs, din,
                                    cfg.ssm_conv - 1), dtype)
    if cfg.family == "hybrid" and nr:
        w = cfg.lru_width or cfg.d_model
        st["lru_h"] = jnp.zeros((nr, max_seqs, w), jnp.float32)
        st["rec_conv"] = jnp.zeros((nr, max_seqs, w, 3), dtype)
    return st


def decode_step(cfg: ModelConfig, params: Params,
                state: Dict[str, jnp.ndarray], tokens: jnp.ndarray,
                ctx: Optional[ParallelCtx] = None,
                rt: Optional[dict] = None
                ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One decode step for every active slot.

    tokens: [B] last generated token per slot. state["seq_lens"] must
    already count the new token. Returns (logits [B, V], new state).
    """
    rt = rt or {}
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(jnp.dtype(cfg.dtype))  # [B, d]
    state = dict(state)
    seq_lens = state["seq_lens"]
    L = cfg.num_layers
    homog = _is_homogeneous(cfg)
    kind0 = cfg.layer_kind(0)

    pool_spec = scale_spec = None
    if ctx is not None:
        kv_tp = (ctx.tp_axis if ctx.tp_axis and
                 cfg.num_kv_heads % ctx.tp_size == 0 else None)
        pool_spec = P(None, ctx.dp_axes, None, kv_tp, None)
        scale_spec = P(None, ctx.dp_axes, kv_tp)

    def _pin_cache(c):
        # keep the scan-carried pools sharded over dp between iterations —
        # without this GSPMD re-gathers the whole pool every layer.
        if pool_spec is None:
            return c
        c = c._replace(k=shard(ctx, c.k, pool_spec),
                       v=shard(ctx, c.v, pool_spec))
        if c.quantized:
            c = c._replace(k_scale=shard(ctx, c.k_scale, scale_spec),
                           v_scale=shard(ctx, c.v_scale, scale_spec))
        return c

    if homog and kind0 in ("full", "sliding") and rt.get("scan_layers", True):
        def body(carry, inp):
            h, cache = carry
            lp, li = inp
            with jax.named_scope("attention"):
                hn = apply_norm(lp["attn_norm"], h, cfg.norm, cfg.norm_eps)
                mix, cache = attn_decode(
                    cfg, lp["attn"], hn, ctx, kind=kind0, cache=cache,
                    layer=li, block_table=state["block_table"],
                    seq_lens=seq_lens, rt=rt)
                cache = _pin_cache(cache)
                h = h + mix
            with jax.named_scope("mlp"):
                hn = apply_norm(lp["mlp_norm"], h, cfg.norm, cfg.norm_eps)
                if cfg.num_experts:
                    y = moe_apply(cfg, lp["moe"], hn[:, None, :], ctx,
                                  rt)[:, 0]
                else:
                    y = mlp_apply(lp["mlp"], hn, cfg.act, rt)
                h = h + y
            return (h, cache), None

        (x, cache), _ = jax.lax.scan(
            body, (x, cache_from_state(state)),
            (params["layers"], jnp.arange(L)))
        state.update(cache_to_state(cache))
    elif homog and kind0 == "ssm" and rt.get("scan_layers", True):
        def body(carry, inp):
            h, hp, cp = carry
            lp, li = inp
            hn = apply_norm(lp["attn_norm"], h, cfg.norm, cfg.norm_eps)
            y, hs, cs = ssm_mod.ssm_decode(cfg, lp["ssm"], hn,
                                           hp[li], cp[li])
            hp = jax.lax.dynamic_update_index_in_dim(hp, hs, li, 0)
            cp = jax.lax.dynamic_update_index_in_dim(cp, cs, li, 0)
            return (h + y, hp, cp), None

        (x, hp, cp), _ = jax.lax.scan(
            body, (x, state["ssm_h"], state["ssm_conv"]),
            (params["layers"], jnp.arange(L)))
        state["ssm_h"], state["ssm_conv"] = hp, cp
    else:
        ai = ri = 0
        for i in range(L):
            kind = cfg.layer_kind(i)
            if homog:
                lp = jax.tree.map(lambda a: a[i], params["layers"])
            elif kind == "recurrent":
                lp = jax.tree.map(lambda a: a[ri], params["rec_layers"])
            else:
                lp = jax.tree.map(lambda a: a[ai], params["attn_layers"])
            hn = apply_norm(lp["attn_norm"], x, cfg.norm, cfg.norm_eps)
            if kind == "ssm":
                y, hs, cs = ssm_mod.ssm_decode(cfg, lp["ssm"], hn,
                                               state["ssm_h"][i],
                                               state["ssm_conv"][i])
                state["ssm_h"] = state["ssm_h"].at[i].set(hs)
                state["ssm_conv"] = state["ssm_conv"].at[i].set(cs)
                x = x + y
                continue
            if kind == "recurrent":
                mix, hs, cs = ssm_mod.rglru_decode(cfg, lp["rec"], hn,
                                                   state["lru_h"][ri],
                                                   state["rec_conv"][ri])
                state["lru_h"] = state["lru_h"].at[ri].set(hs)
                state["rec_conv"] = state["rec_conv"].at[ri].set(cs)
                ri += 1
            else:
                mix, cache = attn_decode(
                    cfg, lp["attn"], hn, ctx, kind=kind,
                    cache=cache_from_state(state), layer=ai,
                    block_table=state["block_table"], seq_lens=seq_lens, rt=rt)
                state.update(cache_to_state(cache))
                ai += 1
            x = x + mix
            hn = apply_norm(lp["mlp_norm"], x, cfg.norm, cfg.norm_eps)
            if cfg.num_experts:
                y = moe_apply(cfg, lp["moe"], hn[:, None, :], ctx, rt)[:, 0]
            else:
                y = mlp_apply(lp["mlp"], hn, cfg.act, rt)
            x = x + y

    with jax.named_scope("lm_head"):
        x = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
        logits = unembed(x, params["embed"], params.get("head"))
        logits = logits.astype(jnp.float32)
    return logits, state


def decode_megastep(cfg: ModelConfig, params: Params,
                    state: Dict[str, jnp.ndarray], tokens: jnp.ndarray,
                    sampling: Dict[str, jnp.ndarray], active: jnp.ndarray,
                    n_steps: jnp.ndarray, *,
                    max_horizon: int,
                    ctx: Optional[ParallelCtx] = None,
                    rt: Optional[dict] = None
                    ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Fused decode fast path: up to ``max_horizon`` decode+sample steps in
    ONE device call — KV scatter, paged attention, logits and sampling all
    stay on device; the host only sees the final [max_horizon, B] token
    buffer (a single transfer per dispatched horizon).

    tokens: [B] last sampled token per slot (state["seq_lens"] counts it).
    sampling: padded per-slot ``SamplingParams`` arrays —
            keys [B, 2] uint32 (per-slot PRNG stream roots),
            counts [B] i32 (tokens generated so far: the stream position),
            temps [B] f32 (0 => greedy), top_ks [B] i32 (0 => off),
            top_ps [B] f32 (1.0 => off).  Step ``t`` of the horizon
            samples slot ``b`` with ``fold_in(keys[b], counts[b] + t)`` —
            exactly the key the legacy host loop derives, so fused and
            legacy outputs are bitwise identical per slot.
    active: [B] bool; inactive slots are carried through untouched (their
            KV writes are dropped, their seq_lens stay 0).
    n_steps: scalar int32 *dynamic* trip count <= max_horizon — the host
            dispatches exactly ``steps_until_boundary`` steps without a
            recompile (lax.fori_loop lowers to a while loop).

    Returns (out_tokens [max_horizon, B] i32 — rows >= n_steps are zero,
    new state). Jit with ``donate_argnums`` on ``state`` so the
    [L, NB, KV, BS, D] pools update in place instead of being copied
    every token.
    """
    rt = rt or {}
    B = tokens.shape[0]
    out = jnp.zeros((max_horizon, B), jnp.int32)
    active_i = active.astype(jnp.int32)
    # static sampling-guard flag (rt is a host dict closed over at trace
    # time): guarded rows sample -1 on non-finite logits — see
    # ``core.sampling.sample_from_logits``
    guard = bool(rt.get("sampling_guard"))

    def body(t, carry):
        state, toks, out = carry
        logits, state = decode_step(cfg, params, state, toks, ctx, rt)
        with jax.named_scope("sample"):
            nxt = sample_from_logits(
                logits, sampling["keys"], sampling["counts"] + t,
                sampling["temps"], sampling["top_ks"], sampling["top_ps"],
                poison=sampling.get("poison"), guard=guard)
        nxt = jnp.where(active, nxt, toks)
        state = dict(state)
        state["seq_lens"] = state["seq_lens"] + active_i
        out = out.at[t].set(jnp.where(active, nxt, 0))
        # a guarded -1 must not feed back into the next step's embedding
        # lookup (the row is dead; the host quarantines it on readback)
        safe = jnp.maximum(nxt, 0) if guard else nxt
        return (state, safe, out)

    state, _, out = jax.lax.fori_loop(
        0, n_steps, body, (state, tokens, out))
    return out, state


def prefill(cfg: ModelConfig, params: Params, state: Dict[str, jnp.ndarray],
            batch: Dict[str, Any], ctx: Optional[ParallelCtx] = None,
            rt: Optional[dict] = None
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Prompt prefill: fills caches, returns last-token logits [B, V].

    batch: tokens [B, S] (right-padded), ctx_lens [B]. state["seq_lens"]
    is set to ctx_lens.
    """
    rt = rt or {}
    tokens, ctx_lens = batch["tokens"], batch["ctx_lens"]
    x = _embed_inputs(cfg, params, batch, ctx, rt)
    S = x.shape[1]
    if S != tokens.shape[1]:               # vlm: vision prefix counts as context
        ctx_lens = ctx_lens + (S - tokens.shape[1])
    state = dict(state)
    state["seq_lens"] = ctx_lens
    mask = (jnp.arange(S)[None, :] < ctx_lens[:, None])

    homog = _is_homogeneous(cfg)
    kind0 = cfg.layer_kind(0)
    if (rt.get("prefill_chunk") and homog and kind0 == "full"):
        return _prefill_chunked(cfg, params, state, x, ctx_lens, ctx, rt)
    if homog and rt.get("scan_layers", True) and kind0 != "recurrent":
        if kind0 in ("full", "sliding"):
            pf = attn_prefill_ring if kind0 == "sliding" else attn_prefill

            def body(carry, inp):
                h, cache = carry
                lp, li = inp
                hn = apply_norm(lp["attn_norm"], h, cfg.norm, cfg.norm_eps)
                mix, cache = pf(cfg, lp["attn"], hn, ctx, kind=kind0,
                                cache=cache, layer=li,
                                block_table=state["block_table"],
                                ctx_lens=ctx_lens, rt=rt)
                h = h + mix
                hn = apply_norm(lp["mlp_norm"], h, cfg.norm, cfg.norm_eps)
                if cfg.num_experts:
                    y = moe_apply(cfg, lp["moe"], hn, ctx, rt)
                else:
                    y = mlp_apply(lp["mlp"], hn, cfg.act, rt)
                return (h + y, cache), None

            body = jax.checkpoint(body, policy=rt.get("remat_policy"))
            (x, cache), _ = jax.lax.scan(
                body, (x, cache_from_state(state)),
                (params["layers"], jnp.arange(cfg.num_layers)))
            state.update(cache_to_state(cache))
        else:                                    # ssm
            def body(carry, inp):
                h, hp, cp = carry
                lp, li = inp
                hn = apply_norm(lp["attn_norm"], h, cfg.norm, cfg.norm_eps)
                y, hs, cs = ssm_mod.ssm_prefill(cfg, lp["ssm"], hn, mask,
                                                ctx_lens, rt)
                hp = jax.lax.dynamic_update_index_in_dim(hp, hs, li, 0)
                cp = jax.lax.dynamic_update_index_in_dim(
                    cp, cs.astype(cp.dtype), li, 0)
                return (h + y, hp, cp), None

            body = jax.checkpoint(body, policy=rt.get("remat_policy"))
            (x, hp, cp), _ = jax.lax.scan(
                body, (x, state["ssm_h"], state["ssm_conv"]),
                (params["layers"], jnp.arange(cfg.num_layers)))
            state["ssm_h"], state["ssm_conv"] = hp, cp
        x = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
        last = jnp.take_along_axis(x, (ctx_lens - 1)[:, None, None],
                                   axis=1)[:, 0]
        logits = unembed(last, params["embed"], params.get("head"))
        return logits.astype(jnp.float32), state

    ai = ri = 0
    for i in range(cfg.num_layers):
        kind = cfg.layer_kind(i)
        if _is_homogeneous(cfg):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
        elif kind == "recurrent":
            lp = jax.tree.map(lambda a: a[ri], params["rec_layers"])
        else:
            lp = jax.tree.map(lambda a: a[ai], params["attn_layers"])
        hn = apply_norm(lp["attn_norm"], x, cfg.norm, cfg.norm_eps)
        if kind == "ssm":
            y, hs, cs = ssm_mod.ssm_prefill(cfg, lp["ssm"], hn, mask, ctx_lens, rt)
            state["ssm_h"] = state["ssm_h"].at[i].set(hs)
            state["ssm_conv"] = state["ssm_conv"].at[i].set(cs.astype(
                state["ssm_conv"].dtype))
            x = x + y
            continue
        if kind == "recurrent":
            mix, hs, cs = ssm_mod.rglru_prefill(cfg, lp["rec"], hn, mask,
                                                ctx_lens, rt)
            state["lru_h"] = state["lru_h"].at[ri].set(hs)
            state["rec_conv"] = state["rec_conv"].at[ri].set(cs.astype(
                state["rec_conv"].dtype))
            ri += 1
        else:
            pf = attn_prefill_ring if kind == "sliding" else attn_prefill
            mix, cache = pf(
                cfg, lp["attn"], hn, ctx, kind=kind,
                cache=cache_from_state(state), layer=ai,
                block_table=state["block_table"], ctx_lens=ctx_lens, rt=rt)
            state.update(cache_to_state(cache))
            ai += 1
        x = x + mix
        hn = apply_norm(lp["mlp_norm"], x, cfg.norm, cfg.norm_eps)
        if cfg.num_experts:
            y = moe_apply(cfg, lp["moe"], hn, ctx, rt)
        else:
            y = mlp_apply(lp["mlp"], hn, cfg.act, rt)
        x = x + y

    x = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    last = jnp.take_along_axis(x, (ctx_lens - 1)[:, None, None], axis=1)[:, 0]
    logits = unembed(last, params["embed"], params.get("head"))
    return logits.astype(jnp.float32), state


def _prefill_chunked(cfg: ModelConfig, params: Params, state, x, ctx_lens,
                     ctx, rt):
    """Chunked prefill (beyond-paper, vLLM-style): the prompt is processed
    in ``rt['prefill_chunk']``-token chunks; each chunk's attention reads
    the already-cached prefix back from the paged pool, so activation
    memory is O(chunk) instead of O(S). Full-attention homogeneous archs.
    """
    from repro.core.kv_quant import kv_gather, kv_write_prefill
    from repro.models.attention import _qkv, _slopes
    from repro.kernels import ops as kops
    B, S, d = x.shape
    c = min(rt["prefill_chunk"], S)
    state = dict(state)
    bt = state["block_table"]
    slopes = _slopes(cfg)
    cache_def = jax.tree.structure(cache_from_state(state))

    B_ = x.shape[0]
    use_island = (ctx is not None and ctx.dp_size > 1
                  and B_ % ctx.dp_size == 0)

    for off in range(0, S, c):
        ce = min(off + c, S)
        xc = x[:, off:ce]

        def cache_attend(q, k, v, bt_l, cl_l, li, *leaves, off=off, ce=ce):
            """Per-dp-shard: write chunk K/V, gather cached prefix, attend.
            Local block ids; collective-free (DESIGN.md §4)."""
            cache = jax.tree.unflatten(cache_def, leaves)
            cache = kv_write_prefill(cache, li, k, v, bt_l, cl_l,
                                     pos_offset=off)
            bs = cache.block_size
            ce_b = min(((ce + bs - 1) // bs) * bs, bt_l.shape[1] * bs)
            kc, vc = kv_gather(cache, li, bt_l, ce_b, q.dtype)
            kc, vc = kc[:, :ce], vc[:, :ce]
            if rt.get("skip_mixer_core"):
                o = q * (1 + 1e-30 * (kc.sum() + vc.sum()))
            else:
                o = kops.flash_attention(
                    q, kc, vc, slopes, causal=True, q_offset=off,
                    use_pallas=rt.get("use_pallas"),
                    interpret=rt.get("interpret"))
            return (o, *jax.tree.leaves(cache))

        def body(carry, inp, off=off, ce=ce):
            h, cache = carry
            lp, li = inp
            hn = apply_norm(lp["attn_norm"], h, cfg.norm, cfg.norm_eps)
            q, k, v = _qkv(cfg, lp["attn"], hn,
                           off + jnp.arange(ce - off), ctx, rt)
            leaves = jax.tree.leaves(cache)
            if use_island:
                dp = ctx.dp_axes
                leaf_specs = tuple(P(None, dp) for _ in leaves)
                o, *leaves = shard_map(
                    cache_attend, mesh=ctx.mesh,
                    in_specs=(P(dp), P(dp), P(dp), P(dp), P(dp), P(),
                              *leaf_specs),
                    out_specs=(P(dp), *leaf_specs),
                    axis_names=set(dp), check_vma=False,
                )(q, k, v, bt, ctx_lens, li, *leaves)
            else:
                o, *leaves = cache_attend(q, k, v, bt, ctx_lens, li, *leaves)
            cache = jax.tree.unflatten(cache_def, leaves)
            h = h + linear(o.reshape(*o.shape[:2], -1), lp["attn"]["wo"], rt)
            hn = apply_norm(lp["mlp_norm"], h, cfg.norm, cfg.norm_eps)
            if cfg.num_experts:
                y = moe_apply(cfg, lp["moe"], hn, ctx, rt)
            else:
                y = mlp_apply(lp["mlp"], hn, cfg.act, rt)
            return (h + y, cache), None

        body_r = jax.checkpoint(body, policy=rt.get("remat_policy"))
        if rt.get("scan_layers", True):
            (xc, cache), _ = jax.lax.scan(
                body_r, (xc, cache_from_state(state)),
                (params["layers"], jnp.arange(cfg.num_layers)))
        else:                    # unrolled (dry-run cost extrapolation)
            carry = (xc, cache_from_state(state))
            for li in range(cfg.num_layers):
                lp = jax.tree.map(lambda a: a[li], params["layers"])
                carry, _ = body_r(carry, (lp, jnp.int32(li)))
            xc, cache = carry
        state.update(cache_to_state(cache))
        x = x.at[:, off:ce].set(xc)        # final hidden states per chunk

    x = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    last = jnp.take_along_axis(x, (ctx_lens - 1)[:, None, None], axis=1)[:, 0]
    logits = unembed(last, params["embed"], params.get("head"))
    return logits.astype(jnp.float32), state


def supports_chunked_prefill(cfg: ModelConfig) -> bool:
    """Serving-side chunked prefill needs every layer to carry its state
    in the paged KV pool (homogeneous full-attention stacks): SSM /
    recurrent / sliding-ring layers hold per-slot recurrent state that is
    not yet re-enterable mid-prompt, so those archs keep the whole-prompt
    path.  Encoders are excluded too: bidirectional attention has no
    causal chunk decomposition (and no KV cache to chunk into)."""
    return _is_homogeneous(cfg) and cfg.layer_kind(0) == "full" \
        and not cfg.is_encoder


def prefill_chunk(cfg: ModelConfig, params: Params, cache,
                  tokens: jnp.ndarray, block_table: jnp.ndarray,
                  pos_offset: jnp.ndarray, total_len: jnp.ndarray,
                  ctx: Optional[ParallelCtx] = None,
                  rt: Optional[dict] = None):
    """One fixed-shape prefill chunk of ONE sequence (token-budget serving).

    Unlike ``prefill`` (whole padded prompt, one compile per ``[B, S]``)
    and ``_prefill_chunked`` (static per-offset chunks inside one call),
    this is the serving executable: ``tokens`` is always ``[1, W]``
    (W = the engine's chunk budget) and ``pos_offset`` / ``total_len``
    are *device scalars*, so every chunk of every prompt — first, middle,
    last, any length — runs from a single compiled executable.

    tokens: [1, W] right-padded chunk (positions pos_offset + i);
    block_table: [1, MB] this sequence's block row (chunk blocks already
    allocated); pos_offset: i32 scalar, absolute position of tokens[0, 0];
    total_len: i32 scalar, pos_offset + live chunk length.  Each layer
    writes the chunk's K/V into the paged pool at its absolute positions
    (int8 mode merges the boundary block via the dynamic-offset quant
    write), then attends over the pool's *live prefix* plus its own raw
    K/V through ``ops.chunk_prefill_attention`` — the dynamic-offset
    Pallas flash kernel on TPU (scalar-prefetch page walk clamped to the
    live length), the bounded-gather XLA oracle elsewhere; either way the
    per-layer pool traffic is O(total_len), not O(table capacity).
    Padded rows compute garbage that never escapes their row; the
    returned logits ``[1, V]`` are the *last live token's* — only
    meaningful on a prompt's final chunk.  Returns (logits, cache).
    """
    from repro.core.kv_quant import kv_write_prefill
    from repro.kernels import ops as kops
    from repro.models.attention import _qkv, _slopes
    rt = rt or {}
    assert supports_chunked_prefill(cfg), cfg.name
    W = tokens.shape[1]
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(jnp.dtype(cfg.dtype))  # [1,W,d]
    positions = pos_offset + jnp.arange(W)
    total_len = jnp.asarray(total_len, jnp.int32)
    ctx_lens = total_len[None] if total_len.ndim == 0 else total_len
    total_len = ctx_lens[0]                                    # scalar form
    slopes = _slopes(cfg)

    def body(carry, inp):
        h, cache = carry
        lp, li = inp
        with jax.named_scope("attention"):
            hn = apply_norm(lp["attn_norm"], h, cfg.norm, cfg.norm_eps)
            q, k, v = _qkv(cfg, lp["attn"], hn, positions, ctx, rt)
            with jax.named_scope("kv_write"):
                cache = kv_write_prefill(cache, li, k, v, block_table,
                                         ctx_lens, pos_offset=pos_offset)
            if rt.get("skip_mixer_core"):
                o = q * (1 + 1e-30 * (k.sum() + v.sum()))
            else:
                # the chunk attends its OWN tokens raw (exactly like
                # whole-prompt prefill), never pool-roundtripped, so int8
                # quantization noise only enters for *earlier* chunks'
                # positions; the traced q_offset drives the causal mask,
                # which also hides every not-yet-written pool position.
                o = kops.chunk_prefill_attention(
                    q, cache.k, cache.v, cache.k_scale, cache.v_scale, li,
                    block_table, pos_offset, total_len, k, v, slopes,
                    use_pallas=rt.get("use_pallas"),
                    interpret=rt.get("interpret"))
            h = h + linear(o.reshape(*o.shape[:2], -1), lp["attn"]["wo"],
                           rt)
        with jax.named_scope("mlp"):
            hn = apply_norm(lp["mlp_norm"], h, cfg.norm, cfg.norm_eps)
            if cfg.num_experts:
                y = moe_apply(cfg, lp["moe"], hn, ctx, rt)
            else:
                y = mlp_apply(lp["mlp"], hn, cfg.act, rt)
            h = h + y
        return (h, cache), None

    if rt.get("scan_layers", True):
        (x, cache), _ = jax.lax.scan(
            body, (x, cache), (params["layers"], jnp.arange(cfg.num_layers)))
    else:                        # unrolled (dry-run cost extrapolation)
        carry = (x, cache)
        for li in range(cfg.num_layers):
            lp = jax.tree.map(lambda a: a[li], params["layers"])
            carry, _ = body(carry, (lp, jnp.int32(li)))
        x, cache = carry

    with jax.named_scope("lm_head"):
        x = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
        last_i = jnp.clip(total_len - pos_offset - 1, 0, W - 1)
        last = jnp.take_along_axis(x, last_i[None, None, None], axis=1)[:, 0]
        logits = unembed(last, params["embed"], params.get("head"))
        logits = logits.astype(jnp.float32)
    return logits, cache


def unified_step(cfg: ModelConfig, params: Params,
                 state: Dict[str, jnp.ndarray], tokens: jnp.ndarray,
                 sampling: Dict[str, jnp.ndarray], active: jnp.ndarray,
                 chunk_tokens: jnp.ndarray, chunk_block_table: jnp.ndarray,
                 pos_offset: jnp.ndarray, total_len: jnp.ndarray,
                 ctx: Optional[ParallelCtx] = None,
                 rt: Optional[dict] = None
                 ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One serving iteration in ONE device dispatch: a single decode step
    for every active slot, one prefill chunk, and per-row sampling —
    the unified prefill/decode batch (vLLM-style) over the paged pools.

    While a prompt is being chunk-prefilled the scheduler pins the decode
    horizon to 1, which previously cost two (plus a sampling) device
    calls per engine iteration; this executable runs the same
    computations under one ``jit``: shared KV pools (the decode scatter
    and the chunk scatter touch disjoint physical blocks), one paged-
    attention + chunk-flash kernel invocation pair, and ONE
    logits/sample readback per step.

    tokens: [B] last sampled token per decode slot (seq_lens counts it;
        slots the plan excluded carry seq_len 0, so their KV writes are
        dropped exactly like in ``decode_megastep``);
    sampling: padded per-row ``SamplingParams`` arrays of B + 1 rows —
        rows [0, B) are the decode slots, row B is the chunk's request
        (each row's key is ``fold_in(keys[r], counts[r])``, the same
        stream position the two-call path derives, so sampled tokens are
        bitwise identical to the megastep + batched-sample pair);
    active: [B] bool decode mask (row gating only — the host ignores
        inactive rows of the output);
    chunk_tokens / chunk_block_table / pos_offset / total_len: the
        fixed-shape ``[1, W]`` chunk executable's operands (see
        ``prefill_chunk``).

    Returns (next_tokens [B + 1] i32, new state): rows [0, B) are the
    decode samples (inactive rows hold garbage the host drops), row B is
    the chunk's last-live-token sample — meaningful only on a prompt's
    final chunk.  Jit with ``donate_argnums`` on ``state``.
    """
    rt = rt or {}
    logits_dec, state = decode_step(cfg, params, state, tokens, ctx, rt)
    state = dict(state)
    state["seq_lens"] = state["seq_lens"] + active.astype(jnp.int32)
    cache = cache_from_state(state)
    logits_chunk, cache = prefill_chunk(
        cfg, params, cache, chunk_tokens, chunk_block_table, pos_offset,
        total_len, ctx, rt)
    state.update(cache_to_state(cache))
    with jax.named_scope("sample"):
        logits = jnp.concatenate([logits_dec, logits_chunk], axis=0)
        nxt = sample_from_logits(
            logits, sampling["keys"], sampling["counts"], sampling["temps"],
            sampling["top_ks"], sampling["top_ps"],
            poison=sampling.get("poison"),
            guard=bool((rt or {}).get("sampling_guard")))
    return nxt, state


def unified_step_chained(cfg: ModelConfig, params: Params,
                         state: Dict[str, jnp.ndarray],
                         prev_tokens: jnp.ndarray, chain_idx: jnp.ndarray,
                         use_prev: jnp.ndarray, tokens: jnp.ndarray,
                         sampling: Dict[str, jnp.ndarray],
                         active: jnp.ndarray, chunk_tokens: jnp.ndarray,
                         chunk_block_table: jnp.ndarray,
                         pos_offset: jnp.ndarray, total_len: jnp.ndarray,
                         ctx: Optional[ParallelCtx] = None,
                         rt: Optional[dict] = None
                         ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """``unified_step`` with on-device feed-token chaining — the async
    pipelined engine's executable (one dispatch perpetually in flight).

    When dispatch N+1 is enqueued, dispatch N's sampled tokens are still
    on device: row ``r``'s feed token is gathered from the *previous
    dispatch's output buffer* (``prev_tokens[chain_idx[r]]``, an
    ``[B + 1]`` buffer whose row B is the chunk sample) when
    ``use_prev[r]``, and from the host-known ``tokens[r]`` otherwise
    (pipeline restart after a flush, or a slot whose last token was
    absorbed on the host).  The gathered token is clamped at 0: a row
    the non-finite guard sampled as ``-1`` must not index the embedding
    — its successor token is garbage the engine discards at reconcile,
    exactly the megastep's clamped-placeholder-forward contract.

    Jit WITHOUT donation: the pipeline's whole point is that enqueueing
    N+1 must not wait for N, and donating a buffer that is still being
    produced by the in-flight dispatch forces the XLA CPU client to
    execute synchronously (measured: zero host/device overlap).  The
    non-donated state copy is the price of the overlap — ~2 MB on the
    reduced serving configs, well under one step of host time.
    """
    with jax.named_scope("chain_gather"):
        fed = jnp.where(use_prev,
                        jnp.clip(prev_tokens[chain_idx], 0, None), tokens)
    return unified_step(cfg, params, state, fed, sampling, active,
                        chunk_tokens, chunk_block_table, pos_offset,
                        total_len, ctx, rt)


def attn_prefill_ring(cfg, p, x, ctx, *, kind, cache, layer,
                      block_table, ctx_lens, rt):
    """Sliding-window prefill: compute flash-SWA attention, then write each
    token's K/V at ring slot pos % cache_len (later tokens overwrite).
    bf16-only: int8 KV is rejected for sliding archs at state creation."""
    from repro.models.attention import _qkv, _slopes
    from repro.kernels import ops as kops
    B, S, _ = x.shape
    positions = jnp.arange(S)
    q, k, v = _qkv(cfg, p, x, positions, ctx, rt)
    o = kops.flash_attention(q, k, v, _slopes(cfg), causal=True,
                             sliding_window=cfg.sliding_window,
                             use_pallas=rt.get("use_pallas"),
                             interpret=rt.get("interpret"))
    cache_len = block_table.shape[1] * cache.block_size
    # keep only the last cache_len tokens per sequence: token at position p
    # lands at ring slot p % cache_len; older tokens in the same slot must
    # be dropped, so mask tokens with p < ctx_len - cache_len.
    keep = ((positions[None] >= ctx_lens[:, None] - cache_len)
            & (positions[None] < ctx_lens[:, None]))
    # token at position p lands at ring slot p % cache_len; the keep window
    # spans at most cache_len positions, so slots are collision-free.
    cache = cache._replace(
        k=_write_ring(cache.k, layer, k, block_table, positions, keep,
                      cache_len),
        v=_write_ring(cache.v, layer, v, block_table, positions, keep,
                      cache_len))
    y = linear(o.reshape(B, S, -1), p["wo"], rt)
    return y, cache


def _write_ring(pool, layer, k, block_table, positions, keep, cache_len):
    """Scatter the kept tokens' K (or V) [B, S, KV, D] into their ring
    slots of the stacked [L, NB, KV, BS, D] pool; the rest are dropped."""
    bs = pool.shape[3]
    slot = positions % cache_len                              # [S]
    blk = block_table[:, slot // bs]                          # [B, S]
    blk = jnp.where(keep, blk, pool.shape[1])                 # OOB -> dropped
    off = (slot % bs)[None, :]
    return pool.at[layer, blk, :, off].set(k.astype(pool.dtype), mode="drop")
