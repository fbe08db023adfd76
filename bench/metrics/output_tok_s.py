"""Generated tokens returned by ``step()`` in the window over its length."""
from bench import stats


def read(run):
    return stats.tokens_in(run.requests, run.t0, run.t1) / (run.t1 - run.t0)
