"""Roofline-term extraction from compiled dry-run artifacts.

compute term    = HLO_FLOPs / peak_FLOP/s            (per-chip: post-SPMD
memory term     = HLO_bytes / HBM_bw                  modules are per-device)
collective term = collective_bytes / link_bw

collective_bytes are parsed from the (per-device) optimized HLO: for every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
we count the *result* shard bytes, scaled by the ring-traffic factor of the
op (all-reduce moves ~2x its payload over the slowest link; the others ~1x).

Scan-over-layers caveat: XLA's cost_analysis counts a while-loop body ONCE
(verified empirically), so costs for L-layer models are derived from two
small *unrolled* lowers (L_a, L_b = L_a + period) and extrapolated
linearly: C(L) = C(L_a) + (L - L_a)/P * (C(L_b) - C(L_a)). The full-config
compile is still performed — it is the sharding/memory proof.
"""
from __future__ import annotations

import re
from dataclasses import asdict, dataclass, field
from typing import Dict, Optional

import numpy as _np

from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
}

_COLL_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|(\S+))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")

_SHAPE_RE = re.compile(
    r"(pred|[sub]\d+|bf16|f8e4m3fn|f8e5m2|f8e4m3b11fnuz|f\d+|c\d+)"
    r"\[([\d,]*)\]")

_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
           "all-to-all": 1.0, "collective-permute": 1.0}


def _shape_bytes(s: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(s):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-op-kind result bytes (per device), ring-factor scaled."""
    out: Dict[str, float] = {}
    for m in _COLL_RE.finditer(hlo_text):
        shapes = m.group(1) or m.group(2)
        kind = m.group(3)
        b = _shape_bytes(shapes) * _FACTOR[kind]
        out[kind] = out.get(kind, 0.0) + b
    return out


# Ops that necessarily touch HBM on TPU (elementwise chains fuse into their
# neighbours and are excluded — the CPU backend fuses far less than the TPU
# backend, so raw cost_analysis() "bytes accessed" overestimates traffic by
# ~5-10x; see EXPERIMENTS.md §Roofline methodology).
_HEAVY_OPS = {
    "dot", "convolution", "fusion", "scatter", "gather",
    "dynamic-update-slice", "dynamic-slice", "reduce", "sort", "copy",
    "transpose", "concatenate", "pad", "reduce-window",
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
}

_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?(%?[\w.-]+)\s*=\s*((?:\([^)]*\)|\S+))\s+([\w-]+)"
    r"(?:-start|-done)?\((.*?)\)", re.M)
_OPERAND_RE = re.compile(r"%[\w.-]+")
_COMP_RE = re.compile(r"^(%?[\w.-]+)\s*(?:\([^)]*\))?\s*->.*{\s*$")


def hbm_bytes_fusion_aware(hlo_text: str) -> float:
    """Estimate per-device HBM traffic from optimized HLO.

    Unique-buffer accounting: every buffer produced or consumed by a
    _HEAVY_OPS instruction (outside fusion bodies) crosses HBM twice —
    once written, once read — regardless of how many consumers it has.
    This (a) drops elementwise chains that a TPU backend would fuse, and
    (b) avoids multi-consumer double counting from the CPU backend's
    slice-happy SPMD lowering. It approximates the traffic of a
    well-fused TPU lowering of the same program.
    """
    defs: Dict[str, int] = {}
    touched: Dict[str, int] = {}
    sliced = 0.0
    in_fused = False
    # donated inputs (params in train, KV pools in decode) alias their
    # outputs: in-place update fusions on them move only the update, not
    # the buffer. Track the alias chain across the program.
    aliased: set = set()
    in_entry = False
    for line in hlo_text.splitlines():
        # computation headers start at column 0 (signatures may wrap over
        # several lines; the header line carries the name).
        if line and not line[0].isspace() and ("(" in line or
                                               line.startswith("ENTRY")):
            head = line.split("(")[0]
            in_entry = line.startswith("ENTRY")
            in_fused = (not in_entry) and ("fused" in head or
                                           "region" in head or
                                           "wide." in head)
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, shapes, op, operands = m.groups()
        name = name.lstrip("%")
        b_out = _shape_bytes(shapes)
        defs[name] = b_out
        if op == "parameter" and in_entry and b_out >= 1e6:
            # Seed alias roots from all large entry params (donated pools /
            # weights): in-place same-element update chains on them don't
            # move the buffer; genuine full reads still count via the
            # consuming dot/reduce operands. XLA sometimes drops the
            # input_output_alias annotation (e.g. f8 pools), so we don't
            # rely on it.
            aliased.add(name)
        if in_fused:
            continue
        ops_list = [o.lstrip("%") for o in _OPERAND_RE.findall(operands)]
        # sliced-access ops touch only the moved slice, not the whole
        # buffer (paged-pool writes/gathers would otherwise count the
        # full pool per layer): gather/dynamic-slice move ~their output;
        # dynamic-update-slice/scatter move ~their update operand.
        if op in ("gather", "dynamic-slice"):
            sliced += 2.0 * b_out
            continue
        if op == "dynamic-update-slice":
            upd = defs.get(ops_list[1], 0) if len(ops_list) > 1 else 0
            sliced += 2.0 * upd
            if ops_list and ops_list[0] in aliased:
                aliased.add(name)
            continue
        if op == "scatter":
            upd = defs.get(ops_list[2], 0) if len(ops_list) > 2 else b_out
            sliced += 2.0 * upd
            if ops_list and ops_list[0] in aliased:
                aliased.add(name)
            continue
        # in-place update chain on donated buffers: a fusion/copy/convert
        # whose output is the same logical buffer (same element count;
        # bf16<->f32 legalization on CPU changes bytes 2x) moves only the
        # small non-aliased operands. TPU scatters bf16 in place.
        al = [o for o in ops_list if o in aliased]
        if al and any(b_out in (defs[o], 2 * defs[o], defs[o] // 2,
                                4 * defs[o], defs[o] // 4)
                      for o in al):
            aliased.add(name)
            if op in _HEAVY_OPS:
                for o in ops_list:
                    if o not in aliased and o in defs:
                        touched[o] = defs[o]
            continue
        if op not in _HEAVY_OPS:
            continue
        touched[name] = b_out
        for o in ops_list:
            if o in defs:
                touched[o] = defs[o]
    return 2.0 * sum(touched.values()) + sliced


@dataclass
class RooflineTerms:
    flops: float = 0.0                 # per device
    hbm_bytes: float = 0.0             # fusion-aware estimate
    hbm_bytes_upper: float = 0.0       # raw cost_analysis bound
    coll_bytes: float = 0.0            # factor-scaled
    coll_breakdown: Dict[str, float] = field(default_factory=dict)

    @property
    def t_compute(self) -> float:
        return self.flops / PEAK_FLOPS_BF16

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / ICI_BW

    @property
    def bottleneck(self) -> str:
        ts = {"compute": self.t_compute, "memory": self.t_memory,
              "collective": self.t_collective}
        return max(ts, key=ts.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    def as_dict(self) -> Dict:
        return {**asdict(self), "t_compute": self.t_compute,
                "t_memory": self.t_memory, "t_collective": self.t_collective,
                "bottleneck": self.bottleneck}


def terms_from_compiled(compiled) -> RooflineTerms:
    ca = compiled.cost_analysis() or {}
    text = compiled.as_text()
    cb = collective_bytes(text)
    return RooflineTerms(
        flops=float(ca.get("flops", 0.0)),
        hbm_bytes=hbm_bytes_fusion_aware(text),
        hbm_bytes_upper=float(ca.get("bytes accessed", 0.0)),
        coll_bytes=sum(cb.values()),
        coll_breakdown=cb,
    )


def extrapolate(t_a: RooflineTerms, t_b: RooflineTerms, l_a: int, l_b: int,
                L: int) -> RooflineTerms:
    """Linear layer-count extrapolation (see module docstring)."""
    k = (L - l_a) / max(l_b - l_a, 1)

    def ex(a, b):
        return a + k * (b - a)

    keys = set(t_a.coll_breakdown) | set(t_b.coll_breakdown)
    return RooflineTerms(
        flops=ex(t_a.flops, t_b.flops),
        hbm_bytes=ex(t_a.hbm_bytes, t_b.hbm_bytes),
        hbm_bytes_upper=ex(t_a.hbm_bytes_upper, t_b.hbm_bytes_upper),
        coll_bytes=ex(t_a.coll_bytes, t_b.coll_bytes),
        coll_breakdown={k2: ex(t_a.coll_breakdown.get(k2, 0.0),
                               t_b.coll_breakdown.get(k2, 0.0))
                        for k2 in keys},
    )


def mixer_terms(cfg, shape, chips: int, block_q: int = 512,
                bpe: int = 2, dp_size: Optional[int] = None) -> RooflineTerms:
    """Analytic kernel-accurate terms for the mixer cores that the
    ``skip_mixer_core`` lower removed (Pallas flash/paged attention, SSM /
    RG-LRU time scans). Traffic is the kernels' HBM traffic: score tiles /
    recurrent states stay in VMEM by construction (BlockSpec), so only
    q/k/v/o streaming, KV-cache reads, and chunk-boundary state spills
    count.
    """
    B, S = shape.global_batch, shape.seq_len
    H, KV, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    train = shape.kind == "train"
    decode = shape.kind == "decode"
    # decode caches shard over dp only (shard_map island, DESIGN §4) and
    # replicate over the model axis: per-chip traffic = global / dp.
    dp = dp_size or max(chips // 16, 1)
    kv_div = dp if decode else chips
    try:
        bpe_kv = _np.dtype(cfg.paging.cache_dtype).itemsize
    except TypeError:                      # float8 etc: 1 byte
        bpe_kv = 1 if "8" in cfg.paging.cache_dtype else 2
    passes = 3.5 if train else 1.0          # 1 fwd + ~2.5 flash bwd
    io_passes = 3.0 if train else 1.0
    flops = 0.0
    bts = 0.0
    for i in range(cfg.num_layers):
        kind = cfg.layer_kind(i)
        if kind in ("full", "sliding"):
            W = cfg.sliding_window if kind == "sliding" else 0
            if decode:
                kvlen = min(S, W) if W else S
                flops += 4.0 * B * H * kvlen * Dh
                if not W:                    # paged: pool read not in lower
                    bts += 2.0 * B * KV * kvlen * Dh * bpe_kv * (chips / kv_div)
                continue
            if cfg.is_encoder:
                pairs = float(S) * S
            elif W and W < S:
                pairs = float(S) * W - W * W / 2.0
            else:
                pairs = float(S) * S / 2.0
            flops += passes * 4.0 * B * H * pairs * Dh
            nqb = max(1, S // block_q)
            kv_reread = pairs / max(float(S) * S, 1.0) * 2.0   # causal frac
            bts += io_passes * B * Dh * bpe * (
                2.0 * S * H                  # q read + o write
                + 2.0 * S * KV * nqb * kv_reread)
        elif kind == "ssm":
            din = cfg.ssm_expand * cfg.d_model
            N = cfg.ssm_state
            steps = 1 if decode else S
            flops += passes * 9.0 * B * steps * din * N
            if decode:
                bts += B * din * N * 4 * 2.0          # state read+write
            else:
                bts += io_passes * B * steps * (3 * din + 2 * N) * 4
                bts += io_passes * (steps / 128.0) * B * din * N * 4 * 2
        elif kind == "recurrent":
            w = cfg.lru_width or cfg.d_model
            steps = 1 if decode else S
            flops += passes * 8.0 * B * steps * w
            bts += (B * w * 4 * 2.0 if decode
                    else io_passes * 3.0 * B * steps * w * 4)
        if cfg.num_experts and kind != "ssm":
            # routed experts (ragged grouped matmuls; skip-lowered because
            # XLA cost-counts ragged_dot as dense): 3 matmuls over
            # capacity-bounded rows, capacity factor 2.0 (models/moe.py).
            from repro.models.moe import CAPACITY_FACTOR, padded_experts
            d, f, k = cfg.d_model, cfg.moe_d_ff, cfg.moe_top_k
            tokens = B if decode else B * S
            rows = tokens * k * CAPACITY_FACTOR
            flops += passes * 6.0 * rows * d * f
            # expert weights stream once per step per chip (EP over the
            # 16-way model axis when divisible); bts is global here and is
            # divided by chips on return.
            e_pad = padded_experts(cfg, 16)
            ep = 16 if e_pad % 16 == 0 else 1
            w_pass = io_passes if not decode else 1.0
            bts += (e_pad / ep) * 3.0 * d * f * bpe * chips * w_pass
            bts += io_passes * rows * (2 * d + f) * bpe   # row activations
    return RooflineTerms(flops=flops / chips, hbm_bytes=bts / chips)


def combine(base: RooflineTerms, mixer: RooflineTerms) -> RooflineTerms:
    return RooflineTerms(
        flops=base.flops + mixer.flops,
        hbm_bytes=base.hbm_bytes + mixer.hbm_bytes,
        hbm_bytes_upper=base.hbm_bytes_upper + mixer.hbm_bytes,
        coll_bytes=base.coll_bytes,
        coll_breakdown=dict(base.coll_breakdown),
    )


def model_flops_per_step(cfg, shape, chips: int) -> float:
    """MODEL_FLOPS = 6·N·D (train) / 2·N·D (inference), per chip.

    N = active params, D = tokens processed this step."""
    n = cfg.num_active_params()
    if shape.kind == "train":
        d = shape.global_batch * shape.seq_len
        f = 6.0 * n * d
    elif shape.kind == "prefill":
        d = shape.global_batch * shape.seq_len
        f = 2.0 * n * d
    else:  # decode: one token per sequence
        d = shape.global_batch
        f = 2.0 * n * d
    return f / chips
