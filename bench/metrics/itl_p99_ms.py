"""99th percentile of the gaps between consecutive emitted tokens of one
request, over every gap that closes inside the window (host clock).  In
granite-3-2b.chat it falls among the gaps that a whole prefill, run
between two decode steps, opens in every request in flight."""

from harness import percentile


def read(run, metric):
    gaps = run.token_gaps()
    return percentile(gaps, 99) * 1e3 if gaps else None
