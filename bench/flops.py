"""Operations and bytes the traffic requires, from the configuration's
shapes and the harness's own records of what was served.

These count the work a request needs, not the work an implementation
happens to do: padding rows, unused table entries and recomputation are
never counted.  A roofline share is the least time the chip could take
for that work (the larger of operations over peak and bytes over
bandwidth) divided by the kernel's measured time, so a share above 100%
means a count here is too high.
"""
from __future__ import annotations

from typing import Iterable, Tuple

BF16 = 2
F32 = 4


def matmul_shapes(s: dict):
    """(K, N) of every matmul of one layer (``s`` from the config's sizes)."""
    d, H, KV, Dh, F = s["d"], s["H"], s["KV"], s["Dh"], s["F"]
    return [(d, H * Dh), (d, KV * Dh), (d, KV * Dh), (H * Dh, d),
            (d, F), (d, F), (F, d)]


def layer_matmul_params(s: dict) -> int:
    return sum(k * n for k, n in matmul_shapes(s))


def forward_flops(s: dict, ctx: int, sampled: bool) -> float:
    """Model operations of one token at context length ``ctx`` (positions
    it attends, itself included): every layer's matmuls, QK and PV over
    the live context, and the LM head when the token's logits are
    sampled."""
    f = s["L"] * (2.0 * layer_matmul_params(s) + 4.0 * s["H"] * s["Dh"] * ctx)
    if sampled:
        f += 2.0 * s["d"] * s["V"]
    return f


def prompt_flops(s: dict, n: int) -> float:
    """Model operations of prefilling an ``n``-token prompt (only its last
    position's logits are sampled)."""
    per_layer = 2.0 * layer_matmul_params(s) * n \
        + 4.0 * s["H"] * s["Dh"] * n * (n + 1) / 2.0
    return s["L"] * per_layer + 2.0 * s["d"] * s["V"]


def paged_decode_work(s: dict, ctxs: Iterable[int], itemsize: int,
                      scale_bytes: int, block: int) -> Tuple[float, float]:
    """(operations, bytes) of the paged decode attention kernel for decode
    tokens at the given context lengths, over all layers: each token's
    query reads the live K and V of its context once (plus one scale per
    page and head when the pool is quantized) and writes its output."""
    flops = nbytes = 0.0
    kv, h, dh = s["KV"], s["H"], s["Dh"]
    for c in ctxs:
        flops += 4.0 * h * dh * c
        pages = -(-c // block)
        nbytes += (2.0 * c * kv * dh * itemsize
                   + 2.0 * pages * kv * scale_bytes
                   + 2.0 * h * dh * BF16)
    return flops * s["L"], nbytes * s["L"]


def int4_matmul_work(s: dict, passes: int, tokens: int, group: int
                     ) -> Tuple[float, float]:
    """(operations, bytes) of the W4A16 matmuls: ``passes`` reads of every
    layer's int4 codes with their float32 scales and zeros, and the
    bf16 activations in and out of ``tokens`` rows."""
    w_bytes = a_bytes = flops = 0.0
    for k, n in matmul_shapes(s):
        w_bytes += k * n / 2.0 + 2.0 * (k // group) * n * F32
        a_bytes += (k + n) * BF16
        flops += 2.0 * k * n
    L = s["L"]
    return L * flops * tokens, L * (w_bytes * passes + a_bytes * tokens)


def least_time(flops: float, nbytes: float, peak_flops: float,
               peak_bw: float) -> Tuple[float, str]:
    """The roofline bound in seconds and which side sets it."""
    tc, tm = flops / peak_flops, nbytes / peak_bw
    return (tc, "compute") if tc >= tm else (tm, "memory")
