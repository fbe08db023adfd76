"""90th percentile of the program's queue-wait samples of the window
(``repro_request_queue_wait_ms``: arrival to first admission)."""
from bench import stats


def read(run):
    return stats.percentile(run.queue_wait_ms, 90)
