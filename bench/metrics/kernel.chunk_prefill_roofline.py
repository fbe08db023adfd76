"""Roofline share of the chunk-prefill attention kernel
(``flash_attention_chunk``): the least time of the work the chunks
dispatched in the window require, over the kernel's device time.  Each
chunk (its dispatch span's ``start`` and ``length``) attends its
prefix in the pool and itself causally; it reads the prefix's K and V at
the pool's element size (with one scale per page and head when the pool
is quantized), its own bf16 K and V, and its bf16 queries, and writes
its bf16 output, in every layer.  None when no chunk ran."""
from bench import flops, readings, trace_reduce

KERNEL = ("flash_attention_chunk",)
CHUNK_SPANS = ("dispatch:unified", "dispatch:unified_chained",
               "dispatch:chunk")


def chunk_work(s: dict, chunks, itemsize: int, scale_bytes: int,
               block: int):
    """(operations, bytes) of chunks given as (start, length) pairs."""
    ops = nbytes = 0.0
    h, kv, dh = s["H"], s["KV"], s["Dh"]
    for start, n in chunks:
        ops += 4.0 * h * dh * n * (start + (n + 1) / 2.0)
        pages = -(-start // block)
        nbytes += (2.0 * start * kv * dh * itemsize
                   + 2.0 * pages * kv * scale_bytes
                   + 2.0 * n * kv * dh * flops.BF16
                   + 2.0 * n * h * dh * flops.BF16)
    return ops * s["L"], nbytes * s["L"]


def read(run):
    if run.trace is None:
        return None
    chunks = [(int(sp.args["start"]), int(sp.args["length"]))
              for sp in run.engine_spans if sp.name in CHUNK_SPANS
              and int(sp.args["length"]) > 0]
    if not chunks:
        return None
    t0, t1 = readings.trace_window(run)
    secs = trace_reduce.kernel_ns(run.trace, KERNEL, t0, t1) / 1e9
    if secs <= 0:
        return None
    quant = run.pool["quantized"]
    work = chunk_work(run.sizes, chunks, run.pool["itemsize"],
                      4 if quant else 0, run.cfg["engine"]["block_size"])
    least, _ = flops.least_time(work[0], work[1], run.peaks["bf16_flops"],
                                run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
