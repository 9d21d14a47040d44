"""Batched decode on the real model path, at smoke width on the CPU.

A decode round of :class:`repro.serving.lm.LMAdapter` runs every row of
its stacked cache in one program; each row has to keep exactly the
batch-1 arithmetic of :func:`repro.runtime.steps.build_decode_step`
(``batch=1``) for every architecture the adapter serves: dense GQA,
sliding-window attention with MoE, and the Mamba2 and xLSTM hybrids.
"""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.launch import mesh as meshlib
from repro.models import model
from repro.runtime import steps
from repro.runtime.sharding import ShardingPolicy
from repro.serving import Request, RequestState, ServingEngine
from repro.serving.lm import LMAdapter

PROMPT, GEN = 8, 6


def _setup(arch):
    cfg = configs.smoke(arch)
    params = jax.jit(lambda k: model.init(cfg, k))(jax.random.PRNGKey(0))
    mesh = meshlib.local_mesh(model=1)
    policy = ShardingPolicy(fsdp=False, tp=False, sp=False, remat=None)
    return cfg, params, mesh, policy


def batch1_greedy(cfg, params, mesh, policy):
    """``greedy(prompt, n)``: tokens and logits of ``n`` greedy steps,
    batch 1 — prefill, the cache padded to the decode length, then one
    decode per step."""
    with mesh:
        prefill = steps.build_prefill_step(cfg, mesh, policy)
        decode, _ = steps.build_decode_step(
            cfg, mesh, policy, batch=1, cache_len=PROMPT + GEN,
            donate=False)

    def greedy(prompt, n):
        with mesh:
            logits, cache = prefill(params, prompt)
            cache = model.pad_cache(cfg, cache, PROMPT + GEN)
            toks, lgs = [], []
            for step in range(n):
                if step:
                    logits, cache = decode(params, cache,
                                           {"tokens": tok[:, None]},
                                           jnp.int32(PROMPT + step - 1))
                tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                toks.append(int(tok[0]))
                lgs.append(np.asarray(logits[0, -1], np.float32))
        return toks, lgs
    return greedy


@pytest.mark.parametrize("arch", ["granite-3-2b", "mixtral-8x22b",
                                  "zamba2-2.7b", "xlstm-350m"])
def test_round_matches_batch1_decode(arch):
    """Three requests prefilled into rows 0, 2 and 3 at different
    rounds, row 1 empty: every round's tokens equal each request's
    batch-1 greedy tokens, and its logits agree within bf16 rounding."""
    cfg, params, mesh, policy = _setup(arch)
    ad = LMAdapter(cfg, mesh, policy, params, prompt_len=PROMPT,
                   gen_len=GEN, slots=4)
    rows = {}                       # row -> [request, step, tokens, logits]

    def admit(rid, row):
        req = Request(rid=rid, prompt=100 + rid, gen_len=GEN)
        req.slot = row
        first = ad.prefill(req)
        rows[row] = [req, 0, [int(np.asarray(first)[0])], [None]]

    admit(0, 0)
    for late in ((1, 2), (2, 3), None):
        row_steps = [None] * 4
        for row, entry in rows.items():
            entry[1] += 1
            row_steps[row] = entry[1]
        toks = np.asarray(ad.decode_round(row_steps))
        logits = np.asarray(ad.logits, np.float32)
        assert logits.shape == (4, cfg.vocab)
        for row, entry in rows.items():
            entry[2].append(int(toks[row]))
            entry[3].append(logits[row])
        if late:
            admit(*late)
    assert sorted(rows) == [0, 2, 3]
    greedy = batch1_greedy(cfg, params, mesh, policy)
    for row, (req, _, got, got_logits) in rows.items():
        want, want_logits = greedy(ad.prompt(req), len(got))
        assert got == want, (arch, row)
        for step in range(1, len(got)):
            w = want_logits[step]
            np.testing.assert_allclose(
                got_logits[step], w, rtol=0,
                atol=2 * float(jnp.finfo(jnp.bfloat16).eps)
                * float(np.max(np.abs(w))))


def test_engine_over_lm_adapter_matches_batch1():
    """``ServingEngine`` over ``LMAdapter`` (granite-3-2b, smoke): both
    completion legs emit identical tokens, equal to a batch-1 greedy
    loop, each request's tokens once each and in step order."""
    cfg, params, mesh, policy = _setup("granite-3-2b")
    ad = LMAdapter(cfg, mesh, policy, params, prompt_len=PROMPT,
                   gen_len=GEN, slots=4)
    ad.warmup()
    gens = [6, 3, 5, 1, 4, 6]
    outputs = {}
    for completion in ("event", "blocking"):
        reqs = [Request(rid=i, prompt=200 + i, gen_len=g,
                        arrival_s=0.01 * i) for i, g in enumerate(gens)]
        rep = ServingEngine(ad, slots=4, completion=completion,
                            num_workers=3).run(reqs)
        for r in reqs:
            assert r.state is RequestState.DONE
            assert [s for s, _ in r.tokens] == list(range(r.gen_len))
        outputs[completion] = rep.outputs
    assert outputs["event"] == outputs["blocking"]
    greedy = batch1_greedy(cfg, params, mesh, policy)
    for i, g in enumerate(gens):
        want, _ = greedy(ad.prompt(Request(rid=i, prompt=200 + i,
                                           gen_len=g)), g)
        assert outputs["event"][i] == want, i


def test_round_reads_cache_without_repeating_it_per_query_head():
    """The lowered decode round (granite smoke: 4 query heads over 2 KV
    heads of 16) holds no float32 tensor of the cache's length with
    slots x T x H x D elements: the cache is not repeated per query head
    for decode attention."""
    cfg = configs.smoke("granite-3-2b")
    slots, T = 4, 64
    assert cfg.n_heads // cfg.n_kv_heads > 1
    mesh = meshlib.local_mesh(model=1)
    policy = ShardingPolicy(fsdp=False, tp=False, sp=False, remat=None)
    with mesh:
        decode, _, a_cache = steps.build_round_step(
            cfg, mesh, policy, slots=slots, cache_len=T)
        rows = jax.ShapeDtypeStruct((slots,), jnp.int32)
        text = decode.lower(steps.abstract_params(cfg), a_cache, rows,
                            rows).as_text()
    repeated = slots * T * cfg.n_heads * cfg.head_dim
    found = [s for s in re.findall(r"tensor<([\dx]+)xf32>", text)
             if T in (dims := [int(d) for d in s.split("x")])
             and math.prod(dims) == repeated]
    assert not found, sorted(set(found))
