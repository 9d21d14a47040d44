#!/usr/bin/env python3
"""Find an open-loop cell's knee once, on the chip: serve its mix at a
series of rates in one process and print, per rate, what the engine
completed and whether the queue grew.

  python bench/sweep.py --workload granite-3-2b.chat --seconds 20 \
      --rates 0.3 0.4 0.5 0.6

The knee is the highest rate whose queue wait does not grow through the
window; the cell's file then fixes its rate at about 0.8 of it.  Runs of
the benchmark never search for a rate.
"""

from __future__ import annotations

import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]


def main(argv=None) -> int:
    import argparse
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args(argv)

    import numpy as np
    import harness
    import traffic
    from repro.launch.cache import enable_compile_cache

    cell = harness.load_cell(args.workload)
    harness.device_info(int(cell.entry["chips"]))
    enable_compile_cache()
    plans = [traffic.plan(cell.mix, {"rate_per_s": r}, args.seed,
                          args.seconds) for r in args.rates]
    program = harness.build(cell, seed=args.seed,
                            n_prompts=max(pl.n_prompts for pl in plans),
                            max_output=plans[0].max_output, annotate=False)
    for rate, plan in zip(args.rates, plans):
        served = harness.serve(program, plan, trace=False)
        lo, hi = plan.window
        due = [r for r in served.requests if lo <= r.arrival_s < hi]
        waits = [(r.admitted_at if r.admitted_at is not None else served.end)
                 - r.arrival_s for r in due]
        half = len(waits) // 2
        toks = sum(lo <= t < hi for e in served.emits.values() for _, t in e)
        ttft = [e[0][1] - r.arrival_s for r in due
                if (e := served.emits.get(r.rid))]
        print(json.dumps({
            "workload": cell.name, "rate_per_s": rate, "due": len(due),
            "tokens_per_s": toks / (hi - lo),
            "queue_wait_first_half_ms": 1e3 * float(np.mean(waits[:half])),
            "queue_wait_second_half_ms": 1e3 * float(np.mean(waits[half:])),
            "ttft_p90_ms": 1e3 * float(np.percentile(ttft, 90)),
            "drain_s": served.end - hi}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
