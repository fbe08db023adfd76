"""90th percentile, over every request due in the window, of due time to
the return of its first token; a request still without one when the run
stops waiting counts with the time it had waited."""
from bench import stats


def read(run):
    return stats.percentile(stats.ttft_ms(run.window_requests(), run.t_end),
                            90)
