"""The control of the ``logit_gap`` check, at a size a test run can hold.

The control is the float32 reference with every weight matmul rounded to
float8, one precision step below the configuration's bfloat16, put in the
program's place.  On three seeds per configuration, at smoke width, the
program's widest gap stays under the cell's limit and the control's goes
over it.  The same readings at the cells' own sizes come from
``calibrate.py`` on the chip; PERF.md lists them.
"""

from __future__ import annotations

import gc

import numpy as np
import pytest

import check
import harness
import traffic


@pytest.mark.parametrize("workload", ["granite-3-2b.chat",
                                      "granite-3-2b.batch"])
@pytest.mark.parametrize("seed", [21, 22, 2**34 + 23])
def test_control_fails_where_program_passes(smoke_root, workload, seed):
    cell = harness.load_cell(workload, root=smoke_root)
    plan = traffic.plan(cell.mix, cell.params, seed, 1.0)
    program = harness.build(cell, seed=seed, n_prompts=plan.n_prompts,
                            max_output=plan.max_output, annotate=False)
    served = harness.serve(program, plan, trace=False)
    outputs = {r.rid: list(r.tokens) for r in served.requests}
    ok = [r.rid for r in served.requests if r.state.name == "DONE"]
    wkey, prompts = program.wkey, program.prompts
    del program, served
    gc.collect()
    pol = cell.mix["check"]
    rids = check.sample(outputs, ok, seed=seed, **pol)
    ref = harness.reference(cell)
    seqs, positions, tokens = check.inputs(prompts, outputs, rids)
    logits = ref.forward(cell.mc, wkey, seqs, positions)
    prog = max(check.gaps(lg, tk).max() for lg, tk in zip(logits, tokens))
    ctrl = check.control_gaps(ref, cell.mc, wkey, seqs, positions,
                              logits).max()
    limit = cell.params["limits"]["logit_gap"]
    assert prog <= limit < ctrl, (prog, limit, ctrl)
    assert np.isfinite(ctrl)
