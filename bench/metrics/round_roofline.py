"""A decode round's least time on this chip, the larger of its operations
over peak FLOP/s and its bytes over HBM bandwidth (the weights once, each
shared block once per invocation, and every row's cache and state at the
cell's cache length: the reference's ``round_cost`` at the engine's
default number of rows), as a share of the mean device time of the
program's round (``jit_decode``) in the trace."""

import inspect

DECODE = "jit_decode"


def read(run, metric):
    times = (run.trace or {}).get("modules", {}).get(DECODE)
    if not times or not hasattr(run.ref, "round_cost"):
        return None
    from repro.serving.engine import ServingEngine
    rows = inspect.signature(ServingEngine).parameters["slots"].default
    flops, nbytes = run.ref.round_cost(run.mc, run.cache_len, rows)
    least = max(flops / run.peak["bf16_flops_per_s"],
                nbytes / run.peak["hbm_bytes_per_s"])
    return 100.0 * least * len(times) / sum(times)
