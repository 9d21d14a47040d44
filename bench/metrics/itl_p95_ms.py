"""95th percentile of the gaps between consecutive emitted tokens of one
request, over every gap that closes inside the window (host clock)."""

from harness import percentile


def read(run, metric):
    gaps = run.token_gaps()
    return percentile(gaps, 95) * 1e3 if gaps else None
