"""Qwen1.5 / Qwen2 dense decoder: seeded weights and the plain reference.

Two halves, both driven by a configuration file under ``bench/configs``:

* ``make_params`` draws the weights from the run's seed on the device in
  one jitted call, in the layout the serving program takes (layer-stacked
  dicts; int4 GPTQ dicts for ``rtn-int4``) and in the type they are
  served in (bfloat16, or int4 codes with float32 scales and zeros).
* ``reference_gaps`` is the plain float32 forward pass of the published
  architecture (RMSNorm, rotary embeddings, grouped-query attention with
  q/k/v biases, SwiGLU MLP, tied LM head), written in ``jax.numpy`` with
  ``precision="highest"``, no cache, no kernels and no batching.  It
  imports nothing of the program: it regenerates the weights from the
  seed itself and dequantizes the int4 codes with its own arithmetic.
  Its ``control`` mode runs the same forward with every matmul input and
  the K/V rounded to the next precision below the configuration's.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

PACK = 8                       # int4 codes per int32 word (low nibble first)
HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0                # largest finite float8_e4m3fn
LOGIT_BLOCK = 512              # positions per LM-head block in the reference


# --------------------------------------------------------------------------
# sizes
# --------------------------------------------------------------------------

SIZE_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
             "num_hidden_layers", "intermediate_size", "vocab_size",
             "rope_theta", "rms_norm_eps")


def sizes(cfg: dict) -> dict:
    """The published sizes the weights and the reference are built from."""
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    return {"L": int(cfg["num_hidden_layers"]), "d": d, "H": h,
            "KV": int(cfg["num_key_value_heads"]), "Dh": d // h,
            "F": int(cfg["intermediate_size"]), "V": int(cfg["vocab_size"]),
            "theta": float(cfg["rope_theta"]),
            "eps": float(cfg["rms_norm_eps"])}


def program_config(cfg: dict):
    """The serving program's ``ModelConfig`` for this file: the registry
    entry named in ``program.arch`` with every size set from the
    published keys, so the program runs exactly the sizes of the file."""
    import dataclasses
    from repro.configs.registry import get_config
    s = sizes(cfg)
    base = get_config(cfg["program"]["arch"])
    paging = dataclasses.replace(base.paging,
                                 block_size=int(cfg["engine"]["block_size"]))
    if not cfg.get("tie_word_embeddings", False):
        raise ValueError("qwen_dense expects tied embeddings")
    return base.replace(
        paging=paging, num_layers=s["L"], d_model=s["d"], num_heads=s["H"],
        num_kv_heads=s["KV"], head_dim=s["Dh"], d_ff=s["F"],
        vocab_size=s["V"], rope_theta=s["theta"], norm_eps=s["eps"],
        qkv_bias=True, tie_embeddings=True, pos_emb="rope", act="silu",
        norm="rmsnorm", dtype="bfloat16")


# --------------------------------------------------------------------------
# weights
# --------------------------------------------------------------------------

def weight_key(seed: int) -> jax.Array:
    """A 32-bit PRNG key from any whole-number seed, also one above
    2**31."""
    word = np.random.SeedSequence([int(seed) % (1 << 64), 0x5eed]
                                  ).generate_state(1)[0]
    return jax.random.PRNGKey(int(word) & 0x7FFFFFFF)


def _quant_of(cfg: dict):
    q = dict(cfg.get("quantization") or {})
    return q.get("method"), int(q.get("group_size", 128))


def _rtn_int4(w: jnp.ndarray, group: int) -> Dict[str, jnp.ndarray]:
    """Asymmetric round-to-nearest int4 of one ``[K, N]`` float weight,
    per group of ``group`` input rows, packed 8 codes per int32 along K."""
    K, N = w.shape
    G = K // group
    wg = w.reshape(G, group, N)
    wmax = jnp.maximum(wg.max(axis=1), 0.0)
    wmin = jnp.minimum(wg.min(axis=1), 0.0)
    scale = jnp.where(wmax > wmin, (wmax - wmin) / 15.0, 1.0)
    zero = jnp.round(-wmin / scale)
    q = jnp.clip(jnp.round(wg / scale[:, None] + zero[:, None]), 0, 15)
    q = q.reshape(K // PACK, PACK, N).astype(jnp.uint32)
    shifts = (4 * jnp.arange(PACK, dtype=jnp.uint32))[None, :, None]
    packed = (q << shifts).sum(axis=1, dtype=jnp.uint32).astype(jnp.int32)
    return {"qweight": packed, "scales": scale.astype(jnp.float32),
            "zeros": zero.astype(jnp.float32),
            "g_idx": jnp.arange(K, dtype=jnp.int32) // group}


def _linear_shapes(s: dict) -> Dict[str, tuple]:
    """(in dims, out dims) of every matmul weight of one layer."""
    d, H, KV, Dh, F = s["d"], s["H"], s["KV"], s["Dh"], s["F"]
    return {"wq": ((d,), (H, Dh)), "wk": ((d,), (KV, Dh)),
            "wv": ((d,), (KV, Dh)), "wo": ((H, Dh), (d,)),
            "w_gate": ((d,), (F,)), "w_up": ((d,), (F,)),
            "w_down": ((F,), (d,))}


def _layer(key, s: dict, method, group: int) -> dict:
    """One layer's weights: normal(0, fan_in^-1/2) matmuls, small biases,
    norm gains near 1."""
    ks = jax.random.split(key, 12)
    out = {"attn": {}, "mlp": {}}
    for i, (name, (din, dout)) in enumerate(_linear_shapes(s).items()):
        fan_in = math.prod(din)
        w = jax.random.normal(ks[i], (fan_in, math.prod(dout)),
                              jnp.float32) * fan_in ** -0.5
        grp = "attn" if name in ("wq", "wk", "wv", "wo") else "mlp"
        if method == "rtn-int4":
            out[grp][name] = _rtn_int4(w, group)
        else:
            out[grp][name] = w.reshape(*din, *dout).astype(jnp.bfloat16)
    H, KV, Dh = s["H"], s["KV"], s["Dh"]
    for i, (name, heads) in enumerate((("bq", H), ("bk", KV), ("bv", KV))):
        out["attn"][name] = (0.1 * jax.random.normal(
            ks[7 + i], (heads, Dh), jnp.float32)).astype(jnp.bfloat16)
    for i, name in enumerate(("attn_norm", "mlp_norm")):
        out[name] = {"w": (1.0 + 0.1 * jax.random.normal(
            ks[10 + i], (s["d"],), jnp.float32)).astype(jnp.bfloat16)}
    return out


@functools.partial(jax.jit, static_argnames=("frozen",))
def _make(key, frozen):
    cfg = dict(frozen)
    s = sizes(cfg)
    method, group = _quant_of(cfg)
    k_emb, k_norm, k_layers = jax.random.split(key, 3)
    layers = jax.vmap(lambda k: _layer(k, s, method, group))(
        jax.random.split(k_layers, s["L"]))
    return {"embed": (0.02 * jax.random.normal(k_emb, (s["V"], s["d"]),
                                               jnp.float32)
                      ).astype(jnp.bfloat16),
            "final_norm": {"w": (1.0 + 0.1 * jax.random.normal(
                k_norm, (s["d"],), jnp.float32)).astype(jnp.bfloat16)},
            "layers": layers}


def _frozen(cfg: dict) -> tuple:
    """The keys the weights depend on, as a hashable static argument."""
    q = cfg.get("quantization") or {}
    return tuple((k, cfg[k]) for k in SIZE_KEYS) + (
        ("quantization", tuple(sorted(q.items()))),)


def make_params(cfg: dict, seed: int) -> dict:
    """The serving weights from ``seed``, made on the device in one call."""
    return jax.block_until_ready(_make(weight_key(seed), _frozen(cfg)))


def param_shapes(cfg: dict):
    """Shapes and dtypes of ``make_params`` without making anything."""
    return jax.eval_shape(lambda k: _make(k, _frozen(cfg)),
                          jax.random.PRNGKey(0))


# --------------------------------------------------------------------------
# the plain reference
# --------------------------------------------------------------------------

def _dequant(w, din: int) -> jnp.ndarray:
    """A layer's weight as float32 ``[din, dout]``: int4 codes times their
    group scale after the zero point, or the float weight itself."""
    if not isinstance(w, dict):
        return w.reshape(din, -1).astype(jnp.float32)
    qw = w["qweight"]
    shifts = 4 * jnp.arange(PACK, dtype=jnp.int32)
    codes = (qw[:, None, :] >> shifts[None, :, None]) & 0xF
    codes = codes.reshape(din, qw.shape[-1]).astype(jnp.float32)
    group = din // w["scales"].shape[0]
    s = jnp.repeat(w["scales"], group, axis=0)
    z = jnp.repeat(w["zeros"], group, axis=0)
    return (codes - z) * s


def _round_fp8(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Round to float8_e4m3fn with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _round_int_block(x: jnp.ndarray, bits: int, block: int) -> jnp.ndarray:
    """Symmetric integer rounding of K or V ``[S, KV, D]`` with one scale
    per ``block`` positions and head (the paged pools' scale layout)."""
    S = x.shape[0]
    pad = (-S) % block
    xp = jnp.pad(x, ((0, pad), (0, 0), (0, 0)))
    xb = xp.reshape(-1, block, *x.shape[1:])
    qmax = float(2 ** (bits - 1) - 1)
    amax = jnp.max(jnp.abs(xb), axis=(1, 3), keepdims=True)
    s = jnp.where(amax > 0, amax / qmax, 1.0)
    q = jnp.clip(jnp.round(xb / s), -qmax, qmax) * s
    return q.reshape(-1, *x.shape[1:])[:S]


def _rounders(control: dict | None):
    """(activation rounder, weight rounder, kv rounder) for a mode."""
    ident = lambda x: x                                    # noqa: E731
    if not control:
        return ident, ident, ident
    act = ((lambda x: _round_fp8(x, -1))
           if control.get("activations") == "fp8_e4m3" else ident)
    wt = ((lambda w: _round_fp8(w, 0))
          if control.get("weights") == "fp8_e4m3" else ident)
    kv = control.get("kv")
    if kv == "fp8_e4m3":
        kvr = lambda x: _round_fp8(x, -1)                  # noqa: E731
    elif kv and kv.startswith("int"):
        bits = int(kv[3:].split("_")[0])
        block = int(kv.split("block")[1]) if "block" in kv else 1
        kvr = lambda x: _round_int_block(x, bits, block)   # noqa: E731
    else:
        kvr = ident
    return act, wt, kvr


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, pos, theta):
    """Rotary embedding, rotate-half form: x [S, heads, D]."""
    d2 = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(d2, dtype=jnp.float32) / d2)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, n_live, q_block: int):
    """Causal grouped-query attention in query blocks.  q [S, H, D],
    k/v [S, KV, D]; positions >= n_live are padding."""
    S, H, D = q.shape
    KV = k.shape[1]
    G = H // KV
    qg = q.reshape(S // q_block, q_block, KV, G, D)
    kpos = jnp.arange(S)

    def block(args):
        qb, start = args
        s = jnp.einsum("qkgd,skd->kgqs", qb, k, precision=HI) * D ** -0.5
        qpos = start + jnp.arange(q_block)
        ok = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] < n_live)
        s = jnp.where(ok[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v, precision=HI)

    out = jax.lax.map(block, (qg, jnp.arange(S // q_block) * q_block))
    return out.reshape(S, H, D)


def _hidden(cfg_items, params, tokens, n_live, control):
    """Final-norm hidden states [S, d] of the reference forward."""
    cfg = dict(cfg_items)
    s = sizes(cfg)
    act, wt, kvr = _rounders(dict(control) if control else None)
    S = tokens.shape[0]
    pos = jnp.arange(S)
    x = params["embed"][tokens].astype(jnp.float32)
    shapes = _linear_shapes(s)

    def mm(h, w, name):
        din = math.prod(shapes[name][0])
        return jnp.dot(act(h), wt(_dequant(w, din)), precision=HI)

    def layer(x, lp):
        a = lp["attn"]
        h = _rms(x, lp["attn_norm"]["w"], s["eps"])
        q = (mm(h, a["wq"], "wq").reshape(S, s["H"], s["Dh"])
             + a["bq"].astype(jnp.float32))
        k = (mm(h, a["wk"], "wk").reshape(S, s["KV"], s["Dh"])
             + a["bk"].astype(jnp.float32))
        v = (mm(h, a["wv"], "wv").reshape(S, s["KV"], s["Dh"])
             + a["bv"].astype(jnp.float32))
        q, k = _rope(q, pos, s["theta"]), _rope(k, pos, s["theta"])
        k, v = kvr(k), kvr(v)
        o = _attention(q, k, v, n_live, min(S, 512))
        x = x + mm(o.reshape(S, -1), a["wo"], "wo")
        m = lp["mlp"]
        h = _rms(x, lp["mlp_norm"]["w"], s["eps"])
        g = mm(h, m["w_gate"], "w_gate")
        u = mm(h, m["w_up"], "w_up")
        return x + mm(jax.nn.silu(g) * u, m["w_down"], "w_down"), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return _rms(x, params["final_norm"]["w"], s["eps"])


@functools.partial(jax.jit, static_argnames=("cfg_items", "control"))
def _gaps(cfg_items, control, params, tokens, n_live, first):
    """Per-position logit gaps of one sequence.

    Position ``p`` predicts ``tokens[p + 1]``.  For the positions from
    ``first`` (the last prompt token) up to ``n_live - 2`` returns the
    gap between the reference's best logit and its logit for the served
    token and, when ``control`` is set, for the control's top token;
    other positions read -1."""
    h_ref = _hidden(cfg_items, params, tokens, n_live, None)
    h_ctl = (_hidden(cfg_items, params, tokens, n_live, control)
             if control else h_ref)
    emb = params["embed"].astype(jnp.float32)
    S = tokens.shape[0]
    nxt = jnp.concatenate([tokens[1:], tokens[:1]])
    nb = S // LOGIT_BLOCK

    def block(args):
        hr, hc, t = args
        lr = jnp.dot(hr, emb.T, precision=HI)
        best = lr.max(-1)
        served = jnp.take_along_axis(lr, t[:, None], -1)[:, 0]
        lc = jnp.dot(hc, emb.T, precision=HI) if control else lr
        ctl = jnp.take_along_axis(lr, jnp.argmax(lc, -1)[:, None], -1)[:, 0]
        return best - served, best - ctl

    g_srv, g_ctl = jax.lax.map(block, (
        h_ref.reshape(nb, LOGIT_BLOCK, -1), h_ctl.reshape(nb, LOGIT_BLOCK, -1),
        nxt.reshape(nb, LOGIT_BLOCK)))
    p = jnp.arange(S)
    live = (p >= first) & (p <= n_live - 2)
    return (jnp.where(live, g_srv.reshape(S), -1.0),
            jnp.where(live, g_ctl.reshape(S), -1.0))


def reference_gaps(cfg: dict, seed: int, seqs: Sequence[tuple],
                   pad_to: int, control: dict | None = None
                   ) -> List[dict]:
    """Compare served greedy tokens with the reference.

    ``seqs``: ``(prompt ids, served ids)`` pairs.  Every sequence is
    padded to ``pad_to`` positions (one compiled shape).  Returns, per
    sequence, the widest gap by which a served token's reference logit
    lies below the reference's best (``served``) and, with ``control``,
    the same for the control's top tokens (``control``)."""
    params = make_params(cfg, seed)
    items = tuple((k, cfg[k]) for k in SIZE_KEYS)
    ctl = tuple(sorted(control.items())) if control else None
    pad_to = -(-pad_to // LOGIT_BLOCK) * LOGIT_BLOCK
    out = []
    for prompt, served in seqs:
        toks = np.zeros(pad_to, np.int32)
        full = list(prompt) + list(served)
        toks[:len(full)] = full
        g_srv, g_ctl = _gaps(items, ctl, params, jnp.asarray(toks),
                             jnp.int32(len(full)), jnp.int32(len(prompt) - 1))
        g_srv, g_ctl = np.asarray(g_srv), np.asarray(g_ctl)
        row = {"tokens": len(served), "served": float(g_srv.max())}
        if control:
            row["control"] = float(g_ctl.max())
        out.append(row)
    del params
    return out
