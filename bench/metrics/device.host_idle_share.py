"""Share of the traced window in which the device idled while the
program's own host code ran: idle gaps whose midpoint lies inside an
``engine.step`` annotation and outside every dispatch and ``readback``
annotation (the program mirrors its spans into the profiler's trace).
None when the trace holds no ``engine.step`` annotation."""
import bisect

from bench import readings, trace_reduce

# profiler labels of the program's dispatches (the prefixes the harness's
# idle breakdown matches) and of its token readback
DEVICE_WAIT = ("unified", "megastep", "prefill", "decode", "sample",
               "copy_cow", "readback")


def _inside(ivs, starts, t):
    i = bisect.bisect_right(starts, t) - 1
    return i >= 0 and t < ivs[i][1]


def read(run):
    if run.trace is None:
        return None
    host = trace_reduce.host_events(run.trace)
    steps = trace_reduce.union((e["ts"], e["ts"] + e["dur"]) for e in host
                               if e["name"] == "engine.step")
    if not steps:
        return None
    waits = trace_reduce.union((e["ts"], e["ts"] + e["dur"]) for e in host
                               if e["name"].startswith(DEVICE_WAIT))
    s0, w0 = [a for a, _ in steps], [a for a, _ in waits]
    t0, t1 = readings.trace_window(run)
    idle = 0.0
    for a, b in trace_reduce.gaps(trace_reduce.busy(run.trace, t0, t1),
                                  t0, t1):
        mid = (a + b) / 2
        if _inside(steps, s0, mid) and not _inside(waits, w0, mid):
            idle += b - a
    return 100.0 * idle / (t1 - t0)
