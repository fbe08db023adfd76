"""Sharding rules: DP/FSDP/TP/EP over the (pod, data, model) mesh.

``ParallelCtx`` is threaded through model code; ``None`` means single-device
(smoke tests). Rules are conditional on divisibility: dimensions that do not
divide the axis size are replicated (e.g. 12 q-heads or 2 kv-heads on a
16-way model axis) — see DESIGN.md §4 and the hillclimb log for the cost.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class ParallelCtx:
    mesh: Mesh
    dp_axes: Tuple[str, ...] = ("data",)     # ("pod","data") multi-pod
    tp_axis: Optional[str] = "model"         # None => dp_only policy
    fsdp: bool = True                        # shard params/opt over dp too
    # serving: paged pools + block tables are manual (shard_map) over dp
    # so decode attention is collective-free (DESIGN.md §4).

    @property
    def dp_size(self) -> int:
        n = 1
        for a in self.dp_axes:
            n *= self.mesh.shape[a]
        return n

    @property
    def tp_size(self) -> int:
        return self.mesh.shape[self.tp_axis] if self.tp_axis else 1

    def axis_size(self, axes) -> int:
        if isinstance(axes, str):
            return self.mesh.shape[axes]
        n = 1
        for a in axes:
            n *= self.mesh.shape[a]
        return n


def make_ctx(mesh: Optional[Mesh], policy: str = "2d") -> Optional[ParallelCtx]:
    """policy: "2d" = DP/FSDP x TP (default); "dp_only" = the model axis
    joins data parallelism (no TP) — the right call for small dense models
    whose TP all-reduces dominate the roofline (EXPERIMENTS.md §Perf)."""
    if mesh is None:
        return None
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    if policy == "dp_only":
        return ParallelCtx(mesh=mesh, dp_axes=dp + ("model",), tp_axis=None)
    return ParallelCtx(mesh=mesh, dp_axes=dp)


def _div(n: int, size: int) -> bool:
    return n % size == 0 and n >= size


def shard_map(f, mesh, in_specs, out_specs, axis_names=None,
              check_vma=True):
    """``jax.shard_map`` with ``axis_names`` (the axes made Manual)
    optional.  ``check_vma`` defaults to True like upstream — the island
    call sites that opt out of replication checking say so explicitly.
    """
    kw = {} if axis_names is None else {"axis_names": axis_names}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kw)


def shard(ctx: Optional[ParallelCtx], x: jnp.ndarray, spec: P) -> jnp.ndarray:
    """with_sharding_constraint if a mesh is present, else identity."""
    if ctx is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec))


def act_spec(ctx: ParallelCtx, *rest) -> P:
    """[B, ...] activation spec: batch over dp."""
    return P(ctx.dp_axes, *rest)


# --------------------------------------------------------------------------
# Parameter partition specs, keyed by param-tree path.
# --------------------------------------------------------------------------

def param_spec(ctx: ParallelCtx, path: str, shape: Tuple[int, ...],
               cfg) -> P:
    """PartitionSpec for one parameter, by path name + shape.

    Layer-stacked params have a leading L dim (never sharded). TP shards
    head/ffn/expert/vocab dims over `model` when divisible; FSDP shards the
    largest remaining dim over dp when divisible.
    """
    tp, dp = ctx.tp_axis, ctx.dp_axes
    tpn = ctx.tp_size
    dpn = ctx.dp_size
    name = path.split("/")[-1]
    stacked = path.startswith("layers") or "_layers" in path.split("/")[0]
    off = 1 if stacked else 0                  # leading L dim
    dims: list = [None] * len(shape)

    def fsdp_on(i):
        if ctx.fsdp and dims[i] is None and _div(shape[i], dpn):
            dims[i] = dp

    if name in ("w", "b", "A_log", "D", "a_param"):       # norms / small vecs
        pass
    elif name == "embed" or name == "head":
        # [V, d] / [d, V]
        v_dim = off + (0 if name == "embed" else 1)
        d_dim = off + (1 if name == "embed" else 0)
        if _div(shape[v_dim], tpn):
            dims[v_dim] = tp
        fsdp_on(d_dim)
    elif name in ("wq",):                                  # [L, d, H, Dh]
        if _div(shape[off + 1], tpn):
            dims[off + 1] = tp
        fsdp_on(off)
    elif name in ("wk", "wv"):                             # [L, d, KV, Dh]
        if _div(shape[off + 1], tpn):
            dims[off + 1] = tp
        fsdp_on(off)
    elif name == "wo":                                     # [L, H, Dh, d]
        if _div(shape[off], tpn):
            dims[off] = tp
        fsdp_on(off + 2)
    elif name in ("bq",):                                  # [L, H, Dh]
        if _div(shape[off], tpn):
            dims[off] = tp
    elif name in ("bk", "bv"):
        if _div(shape[off], tpn):
            dims[off] = tp
    elif name in ("w_gate", "w_up"):                       # [L, d, f]
        if _div(shape[off + 1], tpn):
            dims[off + 1] = tp
        fsdp_on(off)
    elif name == "w_down":                                 # [L, f, d]
        if _div(shape[off], tpn):
            dims[off] = tp
        fsdp_on(off + 1)
    elif name == "router":                                 # [L, d, E]
        pass                                               # small, replicated
    elif name in ("we_gate", "we_up"):                     # [L, E, d, f] routed
        if _div(shape[off], tpn):
            dims[off] = tp                                 # EP over experts
        fsdp_on(off + 1)
    elif name == "we_down":                                # [L, E, f, d]
        if _div(shape[off], tpn):
            dims[off] = tp
        fsdp_on(off + 2)
    elif name in ("ws_gate", "ws_up"):                     # [L, d, fs] shared
        if _div(shape[off + 1], tpn):
            dims[off + 1] = tp
        fsdp_on(off)
    elif name == "ws_down":
        if _div(shape[off], tpn):
            dims[off] = tp
        fsdp_on(off + 1)
    elif name in ("in_proj",):                             # [L, d, 2*din] ssm
        if _div(shape[off + 1], tpn):
            dims[off + 1] = tp
        fsdp_on(off)
    elif name in ("out_proj",):                            # [L, din, d]
        if _div(shape[off], tpn):
            dims[off] = tp
        fsdp_on(off + 1)
    elif name in ("x_proj", "dt_proj"):                    # [L, din, *], [L, R, din]
        i = off if name == "x_proj" else off + 1
        if _div(shape[i], tpn):
            dims[i] = tp
    elif name in ("conv_w",):                              # [L, din, W]
        if _div(shape[off], tpn):
            dims[off] = tp
    elif name in ("dt_bias", "conv_b"):
        if _div(shape[off], tpn):
            dims[off] = tp
    elif name in ("w_in", "w_gate_rec"):                   # [L, d, w] rg-lru
        if _div(shape[off + 1], tpn):
            dims[off + 1] = tp
        fsdp_on(off)
    elif name == "w_out_rec":                              # [L, w, d]
        if _div(shape[off], tpn):
            dims[off] = tp
        fsdp_on(off + 1)
    elif name in ("wr", "wi"):                             # [L, w, w] lru gates
        if _div(shape[off + 1], tpn):
            dims[off + 1] = tp
    # quantized artifacts mirror their float parents via path suffix
    elif name in ("qweight", "scales", "zeros"):
        # [*, K', N]: shard N over tp when divisible
        if _div(shape[-1], tpn):
            dims[-1] = tp
    elif name == "g_idx":
        pass
    return P(*dims)


def batch_shardings(ctx: Optional[ParallelCtx], batch: Any) -> Any:
    """Data batch: leading (batch) dim over dp when divisible."""
    if ctx is None:
        return jax.tree.map(lambda _: None, batch)

    def one(x):
        shape = x.shape
        dp = ctx.dp_axes if shape and shape[0] % ctx.dp_size == 0 else None
        return NamedSharding(ctx.mesh,
                             P(dp, *([None] * (len(shape) - 1))))

    return jax.tree.map(one, batch)


def state_shardings(ctx: Optional[ParallelCtx], state: Any, cfg) -> Any:
    """Decode-state shardings: pools over dp on the blocks/seq dim, KV heads
    over model when divisible (DESIGN.md §4)."""
    if ctx is None:
        return {k: None for k in state}
    tp, dp = ctx.tp_axis, ctx.dp_axes
    tpn, dpn = ctx.tp_size, ctx.dp_size

    def dp_if(n):
        return dp if n % dpn == 0 else None

    def tp_if(n):
        return tp if n % tpn == 0 else None

    out = {}
    for k, v in state.items():
        s = v.shape
        if k in ("k_pool", "v_pool"):            # [L, NB, KV, BS, D]
            spec = P(None, dp_if(s[1]), tp_if(s[2]), None, None)
        elif k in ("k_scales", "v_scales"):      # [L, NB, KV] (int8 KV mode)
            spec = P(None, dp_if(s[1]), tp_if(s[2]))
        elif k == "block_table":                 # [B, MB]
            spec = P(dp_if(s[0]), None)
        elif k == "seq_lens":                    # [B]
            spec = P(dp_if(s[0]))
        elif k in ("ssm_h", "ssm_conv"):         # [L, B, din, *]
            spec = P(None, dp_if(s[1]), tp_if(s[2]),
                     *([None] * (len(s) - 3)))
        elif k in ("lru_h", "rec_conv"):         # [nr, B, w, *]
            spec = P(None, dp_if(s[1]), tp_if(s[2]),
                     *([None] * (len(s) - 3)))
        else:
            spec = P()
        out[k] = NamedSharding(ctx.mesh, spec)
    return out


def param_shardings(ctx: Optional[ParallelCtx], params: Any, cfg) -> Any:
    """Pytree of NamedShardings (or None ctx -> None tree)."""
    if ctx is None:
        return jax.tree.map(lambda _: None, params)

    def walk(tree, prefix):
        if isinstance(tree, dict):
            return {k: walk(v, f"{prefix}/{k}" if prefix else k)
                    for k, v in tree.items()}
        shape = tree.shape if hasattr(tree, "shape") else ()
        return NamedSharding(ctx.mesh, param_spec(ctx, prefix, shape, cfg))

    return walk(params, "")
