"""Device busy milliseconds per engine step: the union of device-op
intervals of the traced window over the ``step()`` calls in it."""
from bench import readings, trace_reduce


def read(run):
    if run.trace is None:
        return None
    t0, t1 = readings.trace_window(run)
    steps = trace_reduce.count(run.trace, "bench.step", t0, t1)
    if not steps:
        return None
    return trace_reduce.total(trace_reduce.busy(run.trace, t0, t1)) / 1e6 / steps
