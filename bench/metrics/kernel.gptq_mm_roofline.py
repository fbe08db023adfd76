"""Roofline share of the W4A16 matmul kernel (``_gptq_mm_kernel``): one
read of every layer's int4 weights per forward pass dispatched in the
window (a mixed step's decode rows and chunk share one pass; a megastep
makes one per step of its horizon) and the bf16 activations of the rows
served (decode tokens and the prompts whose prefill finished in the
window), over the kernel's device time."""
from bench import flops, readings


def read(run):
    q = run.cfg.get("quantization") or {}
    if q.get("method") != "rtn-int4":
        return None
    tokens = (sum(1 for _ in readings.decode_contexts(run))
              + sum(readings.prompts_done(run)))
    work = flops.int4_matmul_work(run.sizes, readings.forward_passes(run),
                                  int(tokens), int(q["group_size"]))
    return readings.roofline_percent(run, "gptq_mm", work)
