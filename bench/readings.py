"""Quantities several metric readers share, computed from the harness's
records (``RunData``) of one window."""
from __future__ import annotations

from typing import Iterator, List, Tuple

from bench import flops, trace_reduce


def decode_contexts(run) -> Iterator[int]:
    """Context length (positions attended, itself included) of every
    decode token returned in the window.  Output token ``j >= 1`` of a
    request is decoded from position ``prompt + j - 1``; token 0 comes
    from the prompt's prefill."""
    for r in run.requests:
        p = len(r.prompt)
        for t, first, n in r.events:
            if run.t0 <= t < run.t1:
                for j in range(max(first, 1), first + n):
                    yield p + j


def prompts_done(run) -> List[int]:
    """Lengths of the prompts whose first token was returned in the
    window (their prefill is the window's prefill work)."""
    return [len(r.prompt) for r in run.requests
            if r.first_t is not None and run.t0 <= r.first_t < run.t1]


# the program's dispatch spans, each one forward pass over its rows (a
# mixed step's decode rows and chunk share one); a megastep runs n_steps
ONE_PASS = ("dispatch:unified", "dispatch:unified_chained", "dispatch:chunk",
            "dispatch:prefill", "dispatch:decode")


def forward_passes(run) -> int:
    """Forward passes of the model dispatched in the window, from the
    program's dispatch spans: one per unified, chained, chunk, prefill or
    decode dispatch, ``n_steps`` per megastep."""
    n = 0
    for s in run.engine_spans:
        if s.name == "dispatch:megastep":
            n += int(s.args["n_steps"])
        elif s.name in ONE_PASS:
            n += 1
    return n


def trace_window(run) -> Tuple[float, float]:
    """The window on the trace's clock (ns)."""
    return run.to_trace_ns(run.t0), run.to_trace_ns(run.t1)


def kernel_seconds(run, label: str) -> float:
    t0, t1 = trace_window(run)
    return trace_reduce.kernel_ns(run.trace, run.kernels[label], t0, t1) / 1e9


def roofline_percent(run, label: str, work: Tuple[float, float]):
    """Least time of ``work`` (operations, bytes) over the kernel's
    device time, in percent; None when the trace holds no such kernel."""
    if run.trace is None:
        return None
    secs = kernel_seconds(run, label)
    if secs <= 0 or work[0] <= 0:
        return None
    least, _ = flops.least_time(work[0], work[1], run.peaks["bf16_flops"],
                                run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
