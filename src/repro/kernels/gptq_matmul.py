"""Pallas TPU fused dequant-matmul for GPTQ int4 weights (W4A16).

TPU adaptation of the paper's quantized-linear DCU kernel:

* Packed weights stay int32 in HBM (4.0 bits/weight moved — the memory-
  bound decode matmul speeds up by ~4x over bf16 weight traffic).
* The k-tile spans whole GPTQ groups and at least 128 columns of ``x``
  (``lcm(group_size, 128)``): the TPU tiles a block's last dim in 128
  lanes, so a group-sized tile (32) does not lower.  No gather on g_idx
  inside the kernel (GPTQ act_order keeps groups contiguous in the
  original column order).
* The (scale, zero) rows of every group stay in VMEM for the whole K
  walk (their block is ``[n_groups, block_n]``, fetched once per output
  tile); each step slices the ``block_k // group_size`` rows it needs.
* Unpack = shift/mask in VREGs -> f32 tile -> MXU matmul; f32
  accumulator in VMEM scratch across k-tiles.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

PACK = 8
LANES = 128


def _gptq_mm_kernel(x_ref, qw_ref, s_ref, z_ref, o_ref, acc_ref, *,
                    nk: int, group_size: int, groups_per_tile: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)                  # [Tm, Tk]
    qw = qw_ref[...]                                    # [Tk//8, Tn] int32
    tn = qw.shape[-1]
    # unpack nibbles: [Tk//8, 8, Tn] -> [Tk, Tn].  Signed shifts are
    # exact here: the mask keeps the 4 bits below any sign fill (Mosaic
    # has no uint32 -> f32 cast).
    shifts = (4 * jax.lax.broadcasted_iota(jnp.int32, (1, PACK, 1), 1))
    codes = (qw[:, None, :] >> shifts) & 0xF
    codes = codes.reshape(groups_per_tile, group_size, tn).astype(jnp.float32)
    rows = pl.ds(pl.multiple_of(ik * groups_per_tile, groups_per_tile),
                 groups_per_tile)
    s = s_ref[rows, :][:, None, :]                      # [g, 1, Tn]
    z = z_ref[rows, :][:, None, :]
    w = ((codes - z) * s).reshape(groups_per_tile * group_size, tn)
    acc_ref[...] += jax.lax.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _final():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "interpret"))
def gptq_matmul(
    x: jnp.ndarray,            # [M, K] activations
    qweight: jnp.ndarray,      # [K//8, N] int32 packed codes
    scales: jnp.ndarray,       # [K//group_size, N] f32
    zeros: jnp.ndarray,        # [K//group_size, N] f32
    *,
    block_m: int = 128,
    block_n: int = 128,
    interpret: bool,
) -> jnp.ndarray:
    M, K = x.shape
    N = qweight.shape[1]
    n_groups = scales.shape[0]
    assert K % n_groups == 0
    group_size = K // n_groups
    assert group_size % PACK == 0
    block_k = math.lcm(group_size, LANES)
    block_m = min(block_m, M)
    block_n = min(block_n, N)
    pm, pn, pk = (-M) % block_m, (-N) % block_n, (-K) % block_k
    if pm or pk:
        x = jnp.pad(x, ((0, pm), (0, pk)))
    if pn or pk:
        # padded K rows carry code 0 with scale 0: they dequantize to 0
        qweight = jnp.pad(qweight, ((0, pk // PACK), (0, pn)))
        scales = jnp.pad(scales, ((0, pk // group_size), (0, pn)))
        zeros = jnp.pad(zeros, ((0, pk // group_size), (0, pn)))
    nm, nn, nk = (M + pm) // block_m, (N + pn) // block_n, (K + pk) // block_k
    gpt = block_k // group_size

    out = pl.pallas_call(
        functools.partial(_gptq_mm_kernel, nk=nk, group_size=group_size,
                          groups_per_tile=gpt),
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda m, n, k: (m, k)),
            pl.BlockSpec((block_k // PACK, block_n), lambda m, n, k: (k, n)),
            pl.BlockSpec((nk * gpt, block_n), lambda m, n, k: (0, n)),
            pl.BlockSpec((nk * gpt, block_n), lambda m, n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda m, n, k: (m, n)),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((M + pm, N + pn), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="gptq_matmul",
    )(x, qweight, scales, zeros)
    return out[:M, :N]
