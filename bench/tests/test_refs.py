"""The float32 references, the benchmark's weights and the shape costs.

At smoke width on the CPU: the weights made in one call equal the ones the
reference regenerates unit by unit; they fit the program's parameter tree;
the program, run in float32 through prefill and then decode through its
cache, gives the reference's logits; and the operation and byte counts
match a count by hand.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from conftest import BENCH, SMOKE
from refs import common, dense

REFS = {"granite-3-2b": dense}


def smoke_mc(name, **kw):
    conf = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    return dict(conf["model"], **SMOKE[name], **kw)


@pytest.mark.parametrize("name", sorted(REFS))
def test_units_regenerate_bitwise(name):
    ref, mc = REFS[name], smoke_mc(name)
    key = common.seed_key(2**35 + 1, 1)
    params = jax.jit(lambda k: ref.init(mc, k))(key)
    keys = common.unit_keys(jax.random.fold_in(key, 1), mc)
    for r in (0, common.repeats(mc) - 1):
        unit = jax.jit(lambda k: ref.init_unit(mc, k))(keys[r])
        stacked = jax.tree.map(lambda x: x[r], params["layers"])
        for a, b in zip(jax.tree.leaves(unit), jax.tree.leaves(stacked)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("name", sorted(REFS))
def test_program_matches_reference_in_float32(name):
    """Prefill of the prompt, then decode through the cache, against the
    reference's one pass over the whole sequence."""
    from repro.models import model
    from repro.models.config import ModelConfig
    ref, mc = REFS[name], smoke_mc(name, dtype="float32")
    mcp = dict(mc, pattern=tuple(tuple(x) for x in mc["pattern"]),
               kernel_impl="pallas_interpret")
    cfg = ModelConfig(name=name, **mcp)
    key = common.seed_key(5, 1)
    params = jax.jit(lambda k: ref.init(mc, k))(key)
    P, G, T = 32, 6, 40
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
    want = ref.forward(mc, key, [toks], [np.arange(P - 1, P + G)])[0]
    np.testing.assert_allclose(np.stack(got), want, rtol=2e-3,
                               atol=2e-3 * np.abs(want).max())


@pytest.mark.parametrize("name", sorted(REFS))
def test_weights_fit_the_program(name):
    from repro.runtime import steps
    cell = harness.Cell(name, {}, {"name": name, "source": "",
                                   "model": smoke_mc(name)}, {}, {}, [], [])
    want = steps.abstract_params(harness.model_config(cell))
    got = jax.eval_shape(lambda k: REFS[name].init(cell.mc, k),
                         jax.random.PRNGKey(0))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert [(a.shape, a.dtype) for a in jax.tree.leaves(want)] == \
        [(a.shape, a.dtype) for a in jax.tree.leaves(got)]


def test_dense_costs_by_hand():
    mc = smoke_mc("granite-3-2b")      # d 64, 4 heads of 16, 2 kv, ff 128
    d, H, Hkv, D, ff, V, L = 64, 4, 2, 16, 128, 128, 2
    per_tok = 2 * (d * H * D * 2 + d * Hkv * D * 2 + 3 * d * ff)
    S, T = 32, 40
    f, b = dense.prefill_cost(mc, S)
    assert f == L * (S * per_tok + 4 * H * D * S * (S + 1) // 2) + 2 * d * V
    weights = L * ((d * d * 2 + d * Hkv * D * 2 + 3 * d * ff) * 2
                   + 2 * d * 4) + V * d * 2 + d * 4
    assert b == weights + L * 2 * S * Hkv * D * 2
    f, b = dense.decode_cost(mc, T)
    assert f == L * (per_tok + 4 * H * D * T) + 2 * d * V
    assert b == weights + L * 2 * T * Hkv * D * 2


def test_flash_attention_cost_and_match():
    import importlib
    kernel = importlib.import_module("kernels.flash_attention")
    mc = json.loads((BENCH / "configs/granite-3-2b.json").read_text())["model"]
    f, b = kernel.cost(mc, 1024)
    assert f == 4 * 32 * 64 * 1024 * 1025 // 2
    assert b == 2 * 1024 * 64 * (2 * 32 + 2 * 8)
    hlo = ("%closed_call.11 = bf16[1,32,1024,64]{3,2,1,0:T(8,128)(2,1)S(1)} "
           "custom-call(bf16[1,32,1024,64]{3,2,1,0} %a)")
    assert kernel.matches(hlo, mc, 1024)
    assert not kernel.matches(hlo, mc, 2048)
    assert not kernel.matches(hlo.replace("[1,32,", "[1,80,"), mc, 1024)
