"""Zamba2 as published, against its plain float32 reference, on the CPU.

At the smoke width of ``configs.zamba2_2p7b.smoke()`` (two invocations of
each shared block and an irregular gap), with seeded random weights made
by the reference (``bench/refs/zamba2.py``, loaded as the benchmark's own
tests load it): the program's prefill, and its decode rounds through
``LMAdapter`` with rows at different positions, give the reference's
logits; each invocation uses block ``i mod 2`` and its own adapter; the
full width holds 2.663 B parameters; the attention kernel runs at head
dim 160 with the shared blocks' score scale; the handoff pads the shared
blocks' KV caches.

Tolerances: the program in float32 differs from the reference only in
the order of float32 sums, about 3e-6 of the largest logit at this
width.  TOL, 1e-4 of the largest logit, leaves thirty times that;
the serving precision (bfloat16) misses by about 8e-2 of it, which
``test_tolerance_fails_in_bfloat16`` checks.
"""

import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.kernels import ref as kref
from repro.kernels.flash_attention import flash_attention
from repro.launch import mesh as meshlib
from repro.models import model
from repro.runtime.sharding import ShardingPolicy
from repro.serving import Request
from repro.serving.lm import LMAdapter

sys.path.append(str(pathlib.Path(__file__).resolve().parents[1] / "bench"))
from refs import common, zamba2  # noqa: E402

TOL = 1e-4
P = 40


def _mc(cfg):
    return {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "d_head": cfg.d_head, "d_ff": cfg.d_ff, "vocab": cfg.vocab,
            "pattern": [list(layer) for layer in cfg.pattern],
            "hybrid_layers": list(cfg.hybrid_layers),
            "shared_blocks": cfg.shared_blocks,
            "adapter_rank": cfg.adapter_rank, "ssm_state": cfg.ssm_state,
            "ssm_expand": cfg.ssm_expand, "ssm_heads": cfg.ssm_heads,
            "ssm_conv": cfg.ssm_conv, "ssm_chunk": cfg.ssm_chunk,
            "norm_eps": cfg.norm_eps, "dtype": cfg.dtype}


def _setup(dtype="float32", **kw):
    cfg = configs.smoke("zamba2-2.7b").scaled(dtype=dtype, **kw)
    mc = _mc(cfg)
    key = common.seed_key(2**33 + 5, 1)
    params = jax.jit(lambda k: zamba2.init(mc, k))(key)
    return cfg, mc, key, params


def _prefill_error(dtype):
    cfg, mc, key, params = _setup(dtype, kernel_impl="pallas_interpret")
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(7), (1, P), 0,
                                         cfg.vocab), np.int32)
    with jax.default_matmul_precision("highest"):
        got, _, _ = model.apply(params, cfg, {"tokens": toks}, mode="prefill")
    want = zamba2.forward(mc, key, [toks[0]], [np.arange(P)])[0]
    return np.abs(np.asarray(got[0], np.float32) - want).max() \
        / np.abs(want).max()


def test_prefill_matches_reference():
    """Every position's logits of a prefill, through the Pallas kernels
    (interpreted), against the reference's forward."""
    assert _prefill_error("float32") < TOL


def test_tolerance_fails_in_bfloat16():
    """The same comparison with the program at its serving precision:
    TOL is tight enough to see it."""
    assert _prefill_error("bfloat16") > 10 * TOL


def test_decode_rounds_match_reference():
    """Rows 0, 2 and 3 prefilled at different rounds (so at different
    positions), row 1 empty, through ``LMAdapter.decode_round``: at every
    served position the row's logits are the reference's over the prompt
    and the tokens served before it."""
    cfg, mc, key, params = _setup()
    gen = 6
    ad = LMAdapter(cfg, meshlib.local_mesh(model=1),
                   ShardingPolicy(fsdp=False, tp=False, sp=False, remat=None),
                   params, prompt_len=P, gen_len=gen, slots=4)
    rows = {}                      # row -> [request, step, tokens, logits]

    def admit(rid, row):
        req = Request(rid=rid, prompt=300 + rid, gen_len=gen)
        req.slot = row
        first = int(np.asarray(ad.prefill(req))[0])
        lg = np.asarray(ad.prefill_logits(req), np.float32)[0, -1]
        rows[row] = [req, 0, [first], [lg]]

    with jax.default_matmul_precision("highest"):
        admit(0, 0)
        for late in ((1, 2), None, (2, 3), None):
            steps = [None] * 4
            for row, entry in rows.items():
                entry[1] += 1
                steps[row] = entry[1]
            toks = np.asarray(ad.decode_round(steps))
            logits = np.asarray(ad.logits, np.float32)
            for row, entry in rows.items():
                entry[2].append(int(toks[row]))
                entry[3].append(logits[row])
            if late:
                admit(*late)
    assert sorted(rows) == [0, 2, 3]
    for row, (req, _, toks, got) in rows.items():
        prompt = np.asarray(ad.prompt(req)["tokens"])[0]
        seq = np.concatenate([prompt, toks[:-1]])
        want = zamba2.forward(mc, key, [seq],
                              [np.arange(P - 1, P - 1 + len(toks))])[0]
        np.testing.assert_allclose(np.stack(got), want, rtol=0,
                                   atol=TOL * np.abs(want).max())


def _swapped(params, part, a, b):
    tree = params["shared"][part]
    swap = jax.tree.map(lambda w: w.at[a].set(w[b]).at[b].set(w[a]), tree)
    return dict(params, shared=dict(params["shared"], **{part: swap}))


def test_invocations_use_their_block_and_adapter():
    """Invocation i runs block i mod 2 with adapter i: the reference
    follows that schedule independently and the program matches it
    (above); here, exchanging two invocations' adapters, or the two
    blocks, changes the logits, so each of them is in use."""
    cfg, _, _, params = _setup()
    assert cfg.hybrid_layers == (1, 3, 6, 8) and cfg.shared_blocks == 2
    toks = jnp.asarray(np.arange(16, dtype=np.int32)[None] % cfg.vocab)

    def logits(p):
        return np.asarray(model.apply(p, cfg, {"tokens": toks},
                                      mode="train")[0])

    base = logits(params)
    scale = np.abs(base).max()
    for part, a, b in (("adapters", 0, 1), ("adapters", 1, 3),
                       ("blocks", 0, 1)):
        moved = np.abs(logits(_swapped(params, part, a, b)) - base).max()
        assert moved > 100 * TOL * scale, (part, a, b)


def test_published_parameter_count():
    cfg = configs.get("zamba2-2.7b")
    assert cfg.hybrid_layers == (6, 12, 18, 24, 30, 36, 42, 47, 51)
    assert (cfg.shared_blocks, cfg.adapter_rank, cfg.head_dim) == \
        (2, 128, 160)
    assert cfg.n_heads * cfg.head_dim == 2 * cfg.d_model
    assert cfg.n_ssm_heads == 80 and cfg.d_inner // cfg.n_ssm_heads == 64
    params = jax.eval_shape(lambda k: model.init(cfg, k),
                            jax.random.PRNGKey(0))
    n = model.param_count(params)
    assert abs(n - 2.663e9) <= 0.005 * 2.663e9, n
    assert n == zamba2.param_count(_mc(cfg))


def test_flash_attention_head_dim_160():
    """The kernel at the shared blocks' head width and score scale."""
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q, k, v = (jax.random.normal(kk, (1, 256, 2, 160)) for kk in ks)
    scale = (160 / 2) ** -0.5
    out = flash_attention(q, k, v, causal=True, scale=scale, interpret=True)
    want = kref.attention(q, k, v, causal=True, scale=scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_pad_cache_pads_shared_kv():
    """A prefill's cache, grown to the decode length: the shared blocks'
    KV (invocations, B, T, Hkv, dh) along T, the Mamba2 states not."""
    cfg, _, _, params = _setup()
    toks = jnp.zeros((1, 16), jnp.int32)
    _, cache, _ = model.apply(params, cfg, {"tokens": toks}, mode="prefill")
    padded = model.pad_cache(cfg, cache, 24)
    kv = padded[model.SHARED_KV]
    assert kv["k"].shape == (4, 1, 24, cfg.n_kv_heads, cfg.head_dim)
    assert kv["v"].shape == kv["k"].shape
    np.testing.assert_array_equal(np.asarray(kv["k"][:, :, :16]),
                                  np.asarray(cache[model.SHARED_KV]["k"]))
    assert not np.asarray(kv["k"][:, :, 16:]).any()
    state = padded["L0_0_mamba2"]["state"]
    assert state.shape == cache["L0_0_mamba2"]["state"].shape
    assert state.shape[:2] == (cfg.n_layers, 1)
