"""The prefill attention kernel's least time on this chip, in Zamba2's
shared blocks, as a share of its mean device time per call in the trace.
The blocks attend over the concatenation of the layer's input and the
embeddings, so the kernel (bench/kernels/flash_attention.py) is matched
and costed at twice ``d_model``: ``n_heads`` heads of ``2 d_model /
n_heads``."""

import importlib


def read(run, metric):
    kernel = importlib.import_module("kernels.flash_attention")
    mc = dict(run.mc, d_model=2 * run.mc["d_model"])
    times = [s for name, s in (run.trace or {}).get("custom_calls", [])
             if kernel.matches(name, mc, run.prompt_len)]
    if not times:
        return None
    flops, nbytes = kernel.cost(mc, run.prompt_len)
    least = max(flops / run.peak["bf16_flops_per_s"],
                nbytes / run.peak["hbm_bytes_per_s"])
    return 100.0 * least * len(times) / sum(times)
