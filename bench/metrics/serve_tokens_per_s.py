"""Output tokens emitted inside the window over the window (host clock)."""


def read(run, metric):
    lo, hi = run.window
    return len(run.emitted(lo, hi)) / (hi - lo)
