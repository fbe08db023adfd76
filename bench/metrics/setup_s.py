"""Set-up time: process start to window start (loading, weights, compile
or cache loads, warm-up)."""


def read(run):
    return run.setup_s
