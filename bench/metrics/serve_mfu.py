"""Model operations served per second over the chip's peak bf16 FLOP/s,
over the window: a prefill's operations for every first token emitted,
and a decode step's at the cell's cache length for every later token
(host clock; operations from shapes).  The whole step's share of the
peak, which bounds what any kernel's roofline share can win."""


def read(run, metric):
    lo, hi = run.window
    firsts = len(run.emitted(lo, hi, first=True))
    later = len(run.emitted(lo, hi, first=False))
    flops = firsts * run.ref.prefill_cost(run.mc, run.prompt_len)[0] \
        + later * run.ref.decode_cost(run.mc, run.cache_len)[0]
    return 100.0 * flops / (hi - lo) / run.peak["bf16_flops_per_s"]
