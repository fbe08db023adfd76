"""Roofline share of the paged decode attention kernel (``_pa_kernel``,
dense and int8 pools): the least time of the live context every decode
token of the window reads, at the pool's element size, over the
kernel's device time."""
from bench import flops, readings


def read(run):
    quant = run.pool["quantized"]
    work = flops.paged_decode_work(
        run.sizes, readings.decode_contexts(run), run.pool["itemsize"],
        4 if quant else 0, run.cfg["engine"]["block_size"])
    return readings.roofline_percent(run, "paged_decode", work)
