"""Operations of one prefill at the cell's prompt length over the mean
device time of the program's prefill (``jit_prefill``) in the trace, as a
share of the chip's peak bf16 FLOP/s."""

PREFILL = "jit_prefill"


def read(run, metric):
    times = (run.trace or {}).get("modules", {}).get(PREFILL)
    if not times:
        return None
    flops, _ = run.ref.prefill_cost(run.mc, run.prompt_len)
    return 100.0 * flops * len(times) / sum(times) \
        / run.peak["bf16_flops_per_s"]
