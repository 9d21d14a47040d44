"""Zamba2's float32 reference, its weights, its costs, and its cell.

At smoke width on the CPU: the weights made in one call equal the ones the
reference regenerates layer by layer and fit the program's parameter
tree; the program, in float32, gives the reference's logits through
prefill and decode; the operation and byte counts match counts by hand at
the published widths; the scan kernel's reader finds a recorded call; and
the harness runs the ``zamba2-2.7b.chat`` cell whole, plain and traced,
with the float8 control failing where the program passes.

This module also registers zamba2's smoke width and limit in the shared
smoke set-up (``conftest.SMOKE`` and ``conftest.SMOKE_LIMIT``), which every
cell file of the smoke root needs.
"""

from __future__ import annotations

import gc
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import check
import conftest
import harness
import traffic
from conftest import BENCH
from refs import common, zamba2

NAME, CELL = "zamba2-2.7b", "zamba2-2.7b.chat"
# Every feature at a tiny width: two invocations of each shared block and
# an irregular gap (3, then 2) like the published tail.
conftest.SMOKE[NAME] = dict(
    n_layers=9, d_model=64, n_heads=4, n_kv_heads=4, d_head=32, d_ff=128,
    vocab=128, hybrid_layers=[1, 3, 6, 8], adapter_rank=8, ssm_state=16,
    ssm_heads=8, ssm_chunk=16)
# Set as the chip limit is, from CPU readings at smoke width over five
# seeds (21, 22, 31, 32, 2**34 + 23): the program at most 0.0680, the
# float8 control at least 0.343.
conftest.SMOKE_LIMIT[NAME] = 0.15
SEED = 2**33 + 17


def smoke_mc(**kw):
    conf = json.loads((BENCH / "configs" / f"{NAME}.json").read_text())
    return dict(conf["model"], **conftest.SMOKE[NAME], **kw)


def full_mc():
    return json.loads((BENCH / "configs" / f"{NAME}.json").read_text())[
        "model"]


def test_layers_and_top_regenerate_bitwise():
    mc = smoke_mc()
    key = common.seed_key(2**35 + 1, 1)
    params = jax.jit(lambda k: zamba2.init(mc, k))(key)
    keys = common.unit_keys(jax.random.fold_in(key, 1), mc)
    for r in (0, mc["hybrid_layers"][1], mc["n_layers"] - 1):
        unit = jax.jit(lambda k: zamba2.init_unit(mc, k))(keys[r])
        stacked = jax.tree.map(lambda x: x[r], params["layers"])
        for a, b in zip(jax.tree.leaves(unit), jax.tree.leaves(stacked)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    top = jax.jit(lambda k: zamba2.init_top(mc, k))(jax.random.fold_in(key,
                                                                        0))
    for name in ("embed", "final_norm", "shared"):
        for a, b in zip(jax.tree.leaves(top[name]),
                        jax.tree.leaves(params[name])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_weights_fit_the_program():
    from repro.runtime import steps
    for mc in (smoke_mc(), full_mc()):
        cell = harness.Cell(CELL, {}, {"name": NAME, "source": "",
                                       "model": mc}, {}, {}, [], [])
        want = steps.abstract_params(harness.model_config(cell))
        got = jax.eval_shape(lambda k: zamba2.init(mc, k),
                             jax.random.PRNGKey(0))
        assert jax.tree.structure(want) == jax.tree.structure(got)
        assert [(a.shape, a.dtype) for a in jax.tree.leaves(want)] == \
            [(a.shape, a.dtype) for a in jax.tree.leaves(got)]


def test_program_matches_reference_in_float32():
    """Prefill of the prompt, then decode through the cache, against the
    reference's one pass over the whole sequence.  Both in float32, so
    they differ by the order of float32 sums: 1e-4 of the largest logit
    (the program differs by about 3e-6 of it; its bfloat16 serving
    precision, by about 8e-2)."""
    from repro.models import model
    from repro.models.config import ModelConfig
    mc = smoke_mc(dtype="float32")
    cfg = ModelConfig(name=NAME, **dict(mc, kernel_impl="pallas_interpret"))
    key = common.seed_key(5, 1)
    params = jax.jit(lambda k: zamba2.init(mc, k))(key)
    P, G, T = 40, 5, 48
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (P + G,), 0,
                                         mc["vocab"]), np.int32)
    with jax.default_matmul_precision("highest"):
        logits, cache, _ = model.apply(params, cfg, {"tokens": toks[None, :P]},
                                       mode="prefill")
        cache = model.pad_cache(cfg, cache, T)
        got = [np.asarray(logits[0, -1])]
        for i in range(G):
            lg, cache, _ = model.apply(
                params, cfg, {"tokens": toks[None, P + i:P + i + 1]},
                mode="decode", cache=cache, cache_index=jnp.int32(P + i))
            got.append(np.asarray(lg[0, -1]))
    want = zamba2.forward(mc, key, [toks], [np.arange(P - 1, P + G)])[0]
    np.testing.assert_allclose(np.stack(got), want, rtol=0,
                               atol=1e-4 * np.abs(want).max())


def test_costs_by_hand():
    """At the published widths: 2.663 B parameters; 7.71 GB of weights a
    token reads (the shared blocks once per invocation); a row's state."""
    mc = full_mc()
    d, din, H, P, n, K = 2560, 5120, 80, 64, 64, 4
    mamba = d * (2 * din + 2 * n + H) + K * (din + 2 * n) + din * d \
        + 3 * H + (din + 2 * n) + din + d
    block = 2 * d * 3 * 5120 + 5120 * d + 3 * d * 10240 + 3 * d
    adapter = d * 128 + 128 * 2 * 10240 + d * d
    total = 54 * mamba + 2 * block + 9 * adapter + 32000 * d + d
    assert zamba2.param_count(mc) == total
    assert abs(total - 2.663e9) < 0.005 * 2.663e9
    from repro.models import model
    from repro.runtime import steps
    cell = harness.Cell(CELL, {}, {"name": NAME, "source": "", "model": mc},
                        {}, {}, [], [])
    assert model.param_count(steps.abstract_params(
        harness.model_config(cell))) == total
    w = zamba2.weight_bytes(mc)
    f32 = 54 * (3 * H + din + 2 * n + din + d) + 9 * 3 * d + d
    assert w == 2 * (54 * (mamba - (3 * H + din + 2 * n + din + d))
                     + 9 * (block - 3 * d + adapter) + 32000 * d) + 4 * f32
    assert abs(w - 7.71e9) < 0.005 * 7.71e9
    kv, ssm = zamba2.row_state_bytes(mc, 1536)
    assert kv == 9 * 2 * 1536 * 32 * 160 * 2
    assert ssm == 54 * (H * P * n * 4 + (K - 1) * (din + 2 * n) * 2)
    tok = 2 * 54 * (mamba - (3 * H + din + 2 * n + din + d)) \
        + 2 * 9 * (block - 3 * d + adapter)
    f, b = zamba2.decode_cost(mc, 1536)
    assert f == tok + 54 * 4 * H * P * n + 9 * 4 * 32 * 160 * 1536 \
        + 2 * d * 32000
    assert b == w + kv + 2 * ssm
    f4, b4 = zamba2.round_cost(mc, 1536, 4)
    assert (f4, b4) == (4 * f, w + 4 * (kv + 2 * ssm))
    S = 1024
    pairs = 4 * 256 * 257 // 2
    f, b = zamba2.prefill_cost(mc, S)
    assert f == S * tok + 54 * (2 * pairs * (n + H * P) + 4 * S * H * P * n) \
        + 9 * 4 * 32 * 160 * S * (S + 1) // 2 + 2 * d * 32000
    assert 7.5e12 < f < 8.2e12
    assert b == w + sum(zamba2.row_state_bytes(mc, S))


def test_mamba2_ssd_kernel_cost_and_match():
    import importlib
    kernel = importlib.import_module("kernels.mamba2_ssd")
    mc = full_mc()
    hlo = ("%mamba2.6 = (bf16[1,80,1024,64]{3,2,1,0:T(8,128)(2,1)S(1)}, "
           "f32[1,80,64,64]{3,2,1,0:T(8,128)}) custom-call(bf16[1,80,1024,"
           "64]{3,2,1,0} %a)")
    assert kernel.matches(hlo, mc, 1024)
    assert not kernel.matches(hlo, mc, 2048)
    assert not kernel.matches(hlo.replace("[1,80,", "[1,64,"), mc, 1024)
    flash = importlib.import_module("kernels.flash_attention")
    assert not flash.matches(hlo, dict(mc, d_model=5120), 1024)
    assert flash.matches(hlo.split(" = (")[0] + " = bf16[1,32,1024,160]{3,2,"
                         "1,0} custom-call(", dict(mc, d_model=5120), 1024)
    f, b = kernel.cost(mc, 1024)
    assert f == 2 * (4 * 256 * 257 // 2) * (64 + 80 * 64) \
        + 4 * 1024 * 80 * 64 * 64
    assert b == 2 * 1024 * 80 * 64 * 2 + 4 * 1024 * 80 + 2 * 1024 * 64 * 2 \
        + 2 * 4 * 80 * 64 * 64


def _run(root, **kw):
    cell = harness.load_cell(CELL, root=root)
    return harness.run_cell(cell, seed=kw.pop("seed", SEED),
                            seconds=kw.pop("seconds", 1.0),
                            t_process=time.monotonic(), log=lambda m: None,
                            **kw)


def test_sound_run_is_correct(smoke_root, no_chip_check):
    res, ok = _run(smoke_root, trace=False)
    assert ok and res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"itl_p95_ms", "setup_s"}


def test_traced_run_reports_per_layer_metrics(smoke_root, no_chip_check,
                                              monkeypatch):
    """A --trace 1 run on the CPU, with each slice's device reduction
    replaced by a fixed one that holds calls of both kernels at the smoke
    width; the row's state comes from the program's gauges."""
    import xplane
    ssd = ("%m = (bf16[1,8,32,16]{3,2,1,0}, f32[1,8,16,16]{3,2,1,0}) "
           "custom-call(%a)")
    attn = "%s = bf16[1,4,32,32]{3,2,1,0} custom-call(%a)"
    monkeypatch.setattr(xplane, "reduce_file", lambda path: {
        "window_s": 2.0, "busy_s": 1.5,
        "modules": {"jit_decode": [0.010] * 20, "jit_prefill": [0.050] * 2},
        "custom_calls": [(ssd, 1e-4)] * 9 + [(attn, 1e-4)] * 4,
        "idle_gaps": [("none", 0.5)],
        "top_modules": [("jit_decode", 0.2), ("jit_prefill", 0.1)]})
    monkeypatch.setattr(harness, "TRACE_SLICE_S", 0.25)
    res, ok = _run(smoke_root, seconds=4.0, trace=True)
    assert ok and res["correct"], res["checks"]
    cell = harness.load_cell(CELL, root=smoke_root)
    assert set(res["metrics"]) == {m["name"] for m in cell.per_layer}
    assert all(0 < m["value"] for m in res["metrics"].values())
    mc = cell.mc
    kv, ssm = zamba2.row_state_bytes(mc, 32 + 24)
    assert res["metrics"]["row_state_mb.z2chat"]["value"] == \
        pytest.approx((kv + ssm) / 1e6)


@pytest.mark.parametrize("seed", [21, 2**34 + 23])
def test_control_fails_where_program_passes(smoke_root, seed):
    cell = harness.load_cell(CELL, root=smoke_root)
    plan = traffic.plan(cell.mix, cell.params, seed, 1.0)
    program = harness.build(cell, seed=seed, n_prompts=plan.n_prompts,
                            max_output=plan.max_output, annotate=False)
    served = harness.serve(program, plan, trace=False)
    outputs = {r.rid: list(r.tokens) for r in served.requests}
    ok = [r.rid for r in served.requests if r.state.name == "DONE"]
    wkey, prompts = program.wkey, program.prompts
    del program, served
    gc.collect()
    rids = check.sample(outputs, ok, seed=seed, **cell.mix["check"])
    seqs, positions, tokens = check.inputs(prompts, outputs, rids)
    logits = zamba2.forward(cell.mc, wkey, seqs, positions)
    prog = max(check.gaps(lg, tk).max() for lg, tk in zip(logits, tokens))
    ctrl = check.control_gaps(zamba2, cell.mc, wkey, seqs, positions,
                              logits).max()
    limit = cell.params["limits"]["logit_gap"]
    assert prog <= limit < ctrl, (prog, limit, ctrl)
