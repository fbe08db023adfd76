"""Share of the traced window in which no operation ran on the device."""
from bench import readings, trace_reduce


def read(run):
    if run.trace is None:
        return None
    t0, t1 = readings.trace_window(run)
    busy = trace_reduce.total(trace_reduce.busy(run.trace, t0, t1))
    return 100.0 * (1.0 - busy / (t1 - t0))
