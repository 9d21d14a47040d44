"""The decode step's least time on this chip, the larger of its operations
over peak FLOP/s and its bytes (weights and the cache read whole at the
cell's cache length) over HBM bandwidth, as a share of the mean device
time of the program's decode step (``jit_decode``) in the trace."""

DECODE = "jit_decode"


def read(run, metric):
    times = (run.trace or {}).get("modules", {}).get(DECODE)
    if not times:
        return None
    flops, nbytes = run.ref.decode_cost(run.mc, run.cache_len)
    least = max(flops / run.peak["bf16_flops_per_s"],
                nbytes / run.peak["hbm_bytes_per_s"])
    return 100.0 * least * len(times) / sum(times)
