"""Seconds from process start to the first timed request: weights, compile
or cache load, warm-up traffic (host clock)."""


def read(run, metric):
    return run.setup_s
