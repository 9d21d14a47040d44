"""The prefill attention kernel's least time on this chip (operations and
bytes from shapes, bench/kernels/flash_attention.py) as a share of its mean
device time per call in the trace."""

import importlib


def read(run, metric):
    kernel = importlib.import_module("kernels.flash_attention")
    times = [s for name, s in (run.trace or {}).get("custom_calls", [])
             if kernel.matches(name, run.mc, run.prompt_len)]
    if not times:
        return None
    flops, nbytes = kernel.cost(run.mc, run.prompt_len)
    least = max(flops / run.peak["bf16_flops_per_s"],
                nbytes / run.peak["hbm_bytes_per_s"])
    return 100.0 * least * len(times) / sum(times)
