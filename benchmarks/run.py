"""Benchmark harness: one table per paper figure/claim.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.py).
  fig2/fig3        -> paper Fig.2 / Fig.3  (bench_serving)
  attn_*           -> §II.C GQA compute/memory claims (bench_attention)
  paging_*         -> §III.A paged memory management (bench_paging)
  gptq_*, w4a16_*  -> GPTQ quantization quality + W4A16 (bench_gptq)
  paged_attn_*     -> custom-kernel microbench (bench_kernels)
"""
from __future__ import annotations

from benchmarks import (bench_attention, bench_gptq, bench_kernels,
                        bench_paging, bench_serving)
from repro.runtime.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    print("name,us_per_call,derived")
    for mod in (bench_attention, bench_paging, bench_gptq, bench_kernels,
                bench_serving):
        mod.run()


if __name__ == "__main__":
    main()
