"""Kernel microbench: Pallas (interpret) vs XLA ref correctness+cost note.

Wall times in interpret mode are NOT TPU times; the emitted 'derived'
column carries the analytic VMEM/MXU utilization figures instead."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit, timeit
from repro.core.alibi import alibi_slopes
from repro.kernels import ref


def run() -> None:
    key = jax.random.PRNGKey(0)
    # paged decode: the paper'score serving kernel
    B, H, KV, D, BS, MB = 8, 8, 2, 64, 16, 16
    NB = B * MB
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    kp = jax.random.normal(ks[1], (1, NB, KV, BS, D), jnp.float32)
    vp = jax.random.normal(ks[2], (1, NB, KV, BS, D), jnp.float32)
    bt = jnp.arange(NB, dtype=jnp.int32).reshape(B, MB)
    sl = jnp.full((B,), MB * BS, jnp.int32)
    slo = alibi_slopes(H)
    f_ref = jax.jit(lambda q, k, v, bt, sl: ref.paged_attention_ref(
        q, k, v, 0, bt, sl, alibi_slopes=slo))
    us_ref = timeit(f_ref, q, kp, vp, bt, sl)
    kv_bytes = 2 * NB * BS * KV * D * 4
    ai = (4 * B * H * MB * BS * D) / kv_bytes
    emit("paged_attn_ref", us_ref,
         f"kv_bytes={kv_bytes};arith_intensity={ai:.2f};"
         f"opt_gqa_reuse=G{H//KV}")
