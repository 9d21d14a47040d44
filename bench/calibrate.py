#!/usr/bin/env python3
"""The readings a cell's ``logit_gap`` limit is set from, on the chip.

  python bench/calibrate.py --workload granite-3-2b.chat --seconds 15 \
      --seeds 101 102 103 ...

For each seed, in one process: build the program over that seed's weights,
serve the cell's traffic for ``--seconds`` (its own load, long enough to
finish the mix's longest requests), then on the same sample of served
requests that a benchmark run compares, read

* ``program``: the widest gap of the served tokens (the lower reading is
  the largest over the seeds), and
* ``control``: the widest gap of the tokens that the float8 control ranks
  first at the same positions (the upper reading is the smallest).

The benchmark's own runs never run the control.  One JSON line per seed.
"""

from __future__ import annotations

import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]


def main(argv=None) -> int:
    import argparse
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)

    import numpy as np
    import check
    import harness
    import traffic
    from repro.launch.cache import enable_compile_cache

    cell = harness.load_cell(args.workload)
    harness.device_info(int(cell.entry["chips"]))
    enable_compile_cache()
    ref = harness.reference(cell)
    pol = cell.mix["check"]
    for seed in args.seeds:
        t = time.monotonic()
        plan = traffic.plan(cell.mix, cell.params, seed, args.seconds)
        program = harness.build(cell, seed=seed, n_prompts=plan.n_prompts,
                                max_output=plan.max_output, annotate=False)
        served = harness.serve(program, plan, trace=False)
        outputs = {r.rid: list(r.tokens) for r in served.requests}
        states = {r.rid: r.state.name for r in served.requests}
        gen = {r.rid: r.gen_len for r in served.requests}
        withdrawn = served.withdrawn
        wkey, prompts = program.wkey, program.prompts
        del program, served
        gc.collect()
        bad = check.emission_faults(outputs, states, gen)
        ok = [r for r, s in states.items() if s == "DONE" and r not in bad]
        rids = check.sample(outputs, ok, seed=seed, **pol)
        seqs, positions, tokens = check.inputs(prompts, outputs, rids)
        shape = check.ref_shape(cell.mix)
        logits = ref.forward(cell.mc, wkey, seqs, positions, shape=shape)
        prog = np.concatenate([check.gaps(lg, tk)
                               for lg, tk in zip(logits, tokens)])
        ctrl = check.control_gaps(ref, cell.mc, wkey, seqs, positions, logits,
                                  shape)
        print(json.dumps({
            "workload": cell.name, "seed": seed,
            "requests": len(rids), "tokens": int(prog.size),
            "unfinished": sum(s != "DONE" and r not in withdrawn
                              for r, s in states.items()),
            "emission_faults": len(bad),
            "program": float(prog.max()),
            "program_p99": float(np.percentile(prog, 99)),
            "program_nonzero": int((prog > 0).sum()),
            "control": float(ctrl.max()),
            "control_p99": float(np.percentile(ctrl, 99)),
            "control_nonzero": int((ctrl > 0).sum()),
            "seconds": time.monotonic() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
