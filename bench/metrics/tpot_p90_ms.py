"""90th percentile, over requests that finished in the window, of
(last token time - first token time) / (output tokens - 1)."""
from bench import stats


def read(run):
    return stats.percentile(stats.tpot_ms(run.finished_in_window()), 90)
