"""Model FLOP utilization of the whole step: the model operations of
every token served in the window (decode tokens at their live context,
prompts whose prefill finished in it, the LM head only for sampled
rows) over the window and the chip's bf16 peak."""
from bench import flops, readings


def read(run):
    if run.trace is None:
        return None
    s = run.sizes
    f = sum(flops.forward_flops(s, c, True)
            for c in readings.decode_contexts(run))
    f += sum(flops.prompt_flops(s, n) for n in readings.prompts_done(run))
    if f <= 0:
        return None
    return 100.0 * f / (run.t1 - run.t0) / run.peaks["bf16_flops"]
