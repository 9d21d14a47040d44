"""The harness end to end on the CPU, sound and with the timed path broken.

Each test skips only the harness's look for a chip (``no_chip_check``) and
drives the rest of a run at smoke width: the benchmark's weights and
prompts, the program's ``LMAdapter`` behind ``ServingEngine``, the metric
readers and the comparison with the float32 reference.  A fault planted
in the program's decode step, or in the engine, has to turn ``correct``
false.
"""

from __future__ import annotations

import json
import time

import pytest

import harness

SEED = 2**33 + 17          # larger than 32 bits, as the driver's are


def run(root, workload, **kw):
    cell = harness.load_cell(workload, root=root)
    result, _ = harness.run_cell(cell, seed=kw.pop("seed", SEED),
                                 seconds=kw.pop("seconds", 1.0), trace=False,
                                 t_process=time.monotonic(),
                                 log=lambda m: None, **kw)
    return result


@pytest.mark.parametrize("workload", ["granite-3-2b.chat",
                                      "granite-3-2b.batch"])
def test_sound_run_is_correct(smoke_root, no_chip_check, workload):
    res = run(smoke_root, workload)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    cell = harness.load_cell(workload, root=smoke_root)
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["device"]["kind"] == "TPU v5 lite"
    json.dumps(res, allow_nan=False)


def _patch_decode(monkeypatch, fn):
    from repro.serving.lm import LMAdapter
    real = LMAdapter.decode
    monkeypatch.setattr(LMAdapter, "decode",
                        lambda self, req, state, step:
                        fn(real, self, req, state, step))


def test_altered_token_fails(smoke_root, no_chip_check, monkeypatch):
    """A token altered where it is produced: every third decode step
    returns another token than its argmax."""
    def bad(real, self, req, state, step):
        tok, st = real(self, req, state, step)
        if step % 3 == 0:
            tok = (tok + 1) % self.cfg.vocab
        return tok, (st[0], tok)
    _patch_decode(monkeypatch, bad)
    res = run(smoke_root, "granite-3-2b.chat")
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]


def test_state_returned_unchanged_fails(smoke_root, no_chip_check,
                                        monkeypatch):
    """A step that returns its state unchanged: decode hands back the
    cache it was given, so later steps never see the new keys and
    values."""
    def stale(real, self, req, state, step):
        tok, st = real(self, req, state, step)
        return tok, (state[0], tok)
    _patch_decode(monkeypatch, stale)
    res = run(smoke_root, "granite-3-2b.chat")
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]


def test_half_the_requests_left_out_fails(smoke_root, no_chip_check,
                                          monkeypatch):
    """Half of the batch left out: the engine drops every other request
    it is given, and the ones it serves look sound."""
    from repro.serving import ServingEngine
    real = ServingEngine.run
    monkeypatch.setattr(ServingEngine, "run",
                        lambda self, reqs: real(self, list(reqs)[::2]))
    res = run(smoke_root, "granite-3-2b.chat")
    assert not res["correct"]
    assert res["checks"]["unfinished"]["value"] > 0
    assert res["failed"] > 0


def test_token_misfiled_fails(smoke_root, no_chip_check, monkeypatch):
    """An answer altered where it is emitted: the engine's emission path
    records step 2's token as step 3's, so step 3 comes twice."""
    from repro.serving import ServingEngine
    real = ServingEngine._emit

    def misfiled(self, req, step, tok):
        real(self, req, step, tok)
        if step == 2:
            req.tokens[-1] = (3, req.tokens[-1][1])
    monkeypatch.setattr(ServingEngine, "_emit", misfiled)
    res = run(smoke_root, "granite-3-2b.batch")
    assert not res["correct"]
    assert res["checks"]["emission_faults"]["value"] > 0


def test_new_mix_is_found_by_name(smoke_root, no_chip_check):
    """A cell on a new traffic mix is a new mix file, a new cell file and
    a new entry in BENCHMARK.json; no file the harness already has
    changes."""
    mix = json.loads((smoke_root / "bench/mixes/chat.json").read_text())
    mix.update(prompt_len=16)
    mix["output"].update(median=3, max=5)
    (smoke_root / "bench/mixes/short.json").write_text(json.dumps(mix))
    (smoke_root / "bench/cells/granite-3-2b.short.json").write_text(
        json.dumps({"rate_per_s": 12.0, "limits": {"logit_gap": 1.0}}))
    spec = json.loads((smoke_root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "granite-3-2b.short",
                              "config": "granite-3-2b", "traffic": "short",
                              "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "itl_p95_ms":
            m["workloads"].append("granite-3-2b.short")
    (smoke_root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.load_cell("granite-3-2b.short", root=smoke_root)
    assert cell.mix["prompt_len"] == 16
    res = run(smoke_root, "granite-3-2b.short")
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"itl_p95_ms", "setup_s"}


def test_no_chip_prints_no_result(tmp_path):
    """Without a TPU the command exits non-zero and prints nothing on
    standard output; so it does in a directory that holds only the
    benchmark's own files."""
    import os
    import shutil
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "bench/run.py", "--workload",
           "granite-3-2b.chat", "--seed", "3", "--seconds", "1"]
    p = subprocess.run(cmd, cwd=harness.ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""
    bare = tmp_path / "bare"
    shutil.copytree(harness.BENCH, bare / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(harness.ROOT / "BENCHMARK.json", bare)
    p = subprocess.run(cmd, cwd=bare, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""


@pytest.mark.parametrize("workload", ["granite-3-2b.chat",
                                      "granite-3-2b.batch"])
def test_traced_run_reports_per_layer_metrics(smoke_root, no_chip_check,
                                              monkeypatch, workload):
    """A --trace 1 run on the CPU: the profiler over slices of the window,
    the runtime's tracer and registry and the benchmark's annotations all
    run; the CPU trace has no TPU plane, so the device reduction of each
    slice is replaced by a fixed one."""
    import xplane
    calls = []

    def fake_reduce(path):
        calls.append(path)
        return {"window_s": 2.0, "busy_s": 1.5,
                "modules": {"jit_decode": [0.010] * 20,
                            "jit_prefill": [0.050] * 2},
                "custom_calls": [("%c = bf16[1,4,32,16]{3,2,1,0} "
                                  "custom-call(%a)", 1e-4)] * 4,
                "idle_gaps": [("bench.detok", 0.3), ("none", 0.2)],
                "top_modules": [("jit_decode", 0.2), ("jit_prefill", 0.1)]}
    monkeypatch.setattr(xplane, "reduce_file", fake_reduce)
    monkeypatch.setattr(harness, "TRACE_SLICE_S", 0.25)
    seen = []
    real_serve = harness.serve

    def serve(*a, **k):
        served = real_serve(*a, **k)
        served.traced["slices_windows"] = [sl["window"]
                                           for sl in served.traced["slices"]]
        seen.append(served)
        return served
    monkeypatch.setattr(harness, "serve", serve)
    cell = harness.load_cell(workload, root=smoke_root)
    res, ok = harness.run_cell(cell, seed=SEED, seconds=4.0, trace=True,
                               t_process=time.monotonic(),
                               log=lambda m: None)
    assert ok and res["correct"], res["checks"]
    n = len(calls)
    assert 2 <= n <= harness.TRACE_SLICES and len(set(calls)) == n
    # each slice holds a whole prefill: a request admitted inside it
    # emitted its first token inside it
    for a, b in seen[0].traced["slices_windows"]:
        assert any(r.admitted_at is not None and a <= r.admitted_at
                   and seen[0].emits[r.rid][0][1] <= b
                   for r in seen[0].requests), (a, b)
    assert set(res["metrics"]) == {m["name"] for m in cell.per_layer}
    assert all(0 < m["value"] for m in res["metrics"].values())
    assert res["device"]["busy_s"] == pytest.approx(1.5 * n)
    assert res["device"]["window_s"] == pytest.approx(2.0 * n)
    assert res["breakdown"]["idle_gaps"][0] == ["host:bench.detok",
                                                pytest.approx(0.3 * n)]
    json.dumps(res, allow_nan=False)


@pytest.mark.parametrize("earliest, arrivals, start", [
    (10.0, [], 10.0),                       # closed loop: where it may
    (10.0, [9.0, 10.1, 12.0], 11.75),       # open: lead before an arrival
    (10.0, [9.0, 10.25, 12.0], 10.0),       # an arrival just far enough
    (10.0, [3.0, 9.9], float("inf")),       # no arrival left
])
def test_slice_start(earliest, arrivals, start):
    assert harness.TRACE_LEAD_S == 0.25
    assert harness.slice_start(earliest, arrivals) == start
