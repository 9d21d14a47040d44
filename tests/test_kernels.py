"""Per-kernel validation: Pallas (interpret=True) vs the pure-jnp oracle.

Sweeps shapes and dtypes per the deliverable: every kernel is asserted
allclose against ref.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref, ops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba2_ssd import mamba2_ssd
from repro.kernels.moe_gmm import moe_gmm


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,H,Hkv,D,causal,window",
    [
        (1, 256, 4, 2, 64, True, None),     # GQA causal
        (2, 128, 8, 8, 128, False, None),   # MHA bidirectional (encoder)
        (1, 256, 4, 1, 64, True, 64),       # MQA + sliding window
        (2, 512, 2, 2, 32, True, None),     # long-ish causal
        (1, 128, 6, 2, 80, True, None),     # non-128 head dim (zamba2/hubert)
    ])
def test_flash_attention_vs_ref(B, S, H, Hkv, D, causal, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_k=64, interpret=True)
    exp = ref.attention(q, k, v, causal=causal, window=window)
    assert out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


def test_flash_attention_blocks_sweep():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 256, 2, 64))
    k = jax.random.normal(ks[1], (1, 256, 2, 64))
    v = jax.random.normal(ks[2], (1, 256, 2, 64))
    exp = ref.attention(q, k, v, causal=True)
    for bq, bk in [(32, 32), (64, 128), (128, 64), (256, 256)]:
        out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# grouped-query decode attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("vmapped", [False, True])
@pytest.mark.parametrize("mask", ["full", "window", "partial",
                                  "full-S2", "window-S2", "partial-S2"])
@pytest.mark.parametrize("H,Hkv,D,scale", [
    (32, 8, 64, None),                 # granite
    (32, 32, 160, (160 / 2) ** -0.5),  # zamba2's shared block
    (8, 1, 64, None),                  # MQA
    (48, 8, 128, None),                # mixtral
])
def test_decode_attention_vs_ref(H, Hkv, D, scale, mask, vmapped):
    """New positions over a bf16 cache, as ``attn_apply`` decodes them:
    their k/v come beside the cache, whose own entries there are stale,
    and the grouped contraction gives the oracle's float32 result over the
    cache with them written in place, alone and mapped over 4 rows at
    different positions as the decode round runs it.  ``full``: the last
    cache positions; ``window``: a sliding window; ``partial``: positions
    below T, one row at position 0.  ``-S2``: two new positions, causal
    among themselves; else one."""
    T, rows = 128, 4
    kind, _, s = mask.partition("-")
    S = int(s[1:]) if s else 1
    window = 16 if kind == "window" else None
    pos = [min(p, T - S) for p in {"full": [T - 1] * rows,
                                   "window": [100, 37, 127, 5],
                                   "partial": [60, 3, 99, 0]}[kind]]
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    # q holds bf16 values in float32, so that both sides return float32
    q = jax.random.normal(ks[0], (rows, 1, S, H, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (rows, 1, T, Hkv, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (rows, 1, T, Hkv, D), jnp.bfloat16)
    k_new = jax.random.normal(ks[3], (rows, 1, S, Hkv, D), jnp.bfloat16)
    v_new = jax.random.normal(ks[4], (rows, 1, S, Hkv, D), jnp.bfloat16)

    def decode(q, k, v, kn, vn, p):
        return ops.decode_attention(q, k, v, kn, vn,
                                    cache_index=jnp.full((1,), p, jnp.int32),
                                    causal=True, window=window, scale=scale)

    def oracle(q, k, v, kn, vn, p):
        k = jax.lax.dynamic_update_slice(k, kn, (0, p, 0, 0))
        v = jax.lax.dynamic_update_slice(v, vn, (0, p, 0, 0))
        return ref.attention(q, k, v, causal=True, window=window,
                             scale=scale,
                             kv_valid_len=jnp.full((1,), p + S, jnp.int32),
                             q_offset=jnp.full((1,), p, jnp.int32))

    def run(fn, q):
        args = (q, k, v, k_new, v_new, jnp.asarray(pos))
        if vmapped:
            return jax.vmap(fn)(*args)
        return jnp.stack([fn(*(a[r] for a in args)) for r in range(rows)])

    out = run(decode, q.astype(jnp.float32))
    exp = run(oracle, q.astype(jnp.float32))
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-5, atol=1e-6)
    # bf16 queries, as the model passes them: a bf16 result within one
    # rounding of the oracle's
    out16 = run(decode, q)
    assert out16.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out16, np.float32),
                               np.asarray(exp), rtol=2 ** -8, atol=1e-6)


# ---------------------------------------------------------------------------
# mamba2 ssd
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (2, 128, 4, 32, 16, 32),
    (1, 256, 2, 64, 64, 64),
    (1, 64, 8, 16, 32, 64),     # chunk == s
])
def test_mamba2_ssd_vs_ref(b, s, h, p, n, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(ks[0], (b, s, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    B = jax.random.normal(ks[3], (b, s, n), dtype)
    C = jax.random.normal(ks[4], (b, s, n), dtype)
    y, st = mamba2_ssd(x, dt, A, B, C, chunk=chunk, interpret=True)
    y_ref, st_ref = ref.ssd_chunked(x, dt, A, B, C, chunk=chunk)
    assert y.dtype == dtype
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref),
                               atol=1e-3, rtol=1e-3)


def test_mamba2_ssd_init_state_chaining():
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    b, s, h, p, n = 1, 128, 2, 16, 16
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    B = jax.random.normal(ks[3], (b, s, n))
    C = jax.random.normal(ks[4], (b, s, n))
    y_full, st_full = mamba2_ssd(x, dt, A, B, C, chunk=32, interpret=True)
    y1, st1 = mamba2_ssd(x[:, :64], dt[:, :64], A, B[:, :64], C[:, :64],
                         chunk=32, interpret=True)
    y2, st2 = mamba2_ssd(x[:, 64:], dt[:, 64:], A, B[:, 64:], C[:, 64:],
                         chunk=32, init_state=st1, interpret=True)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(st2), np.asarray(st_full),
                               atol=1e-4, rtol=1e-4)


def test_ssd_matches_sequential_decode():
    """Chunked train path == step-by-step decode recurrence (cache parity)."""
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    b, s, h, p, n = 2, 64, 2, 8, 8
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    B = jax.random.normal(ks[3], (b, s, n))
    C = jax.random.normal(ks[4], (b, s, n))
    y_seq, st_seq = ref.ssd_sequential(x, dt, A, B, C)
    y_chk, st_chk = mamba2_ssd(x, dt, A, B, C, chunk=16, interpret=True)
    np.testing.assert_allclose(np.asarray(y_chk), np.asarray(y_seq),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(st_chk), np.asarray(st_seq),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# moe gmm
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("E,C,K,N", [(4, 64, 32, 48), (8, 128, 128, 256),
                                     (2, 32, 64, 32)])
def test_moe_gmm_vs_ref(E, C, K, N, dtype):
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    x = jax.random.normal(ks[0], (E, C, K), dtype)
    w = jax.random.normal(ks[1], (E, K, N), dtype)
    out = moe_gmm(x, w, block_c=32, block_n=16, block_k=32, interpret=True)
    exp = jnp.einsum("eck,ekn->ecn", x.astype(jnp.float32),
                     w.astype(jnp.float32)).astype(dtype)
    assert out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


def test_moe_gmm_ref_matches_ragged_oracle():
    """The fixed-capacity layout must agree with the ragged gmm oracle."""
    E, C, K, N = 3, 8, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(6), 2)
    x = jax.random.normal(ks[0], (E, C, K))
    w = jax.random.normal(ks[1], (E, K, N))
    out = ops.moe_gmm(x, w, impl="ref")
    ragged = ref.gmm(x.reshape(E * C, K), w,
                     jnp.full((E,), C, jnp.int32)).reshape(E, C, N)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ragged),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# mlstm (pure-jnp chunked vs sequential oracle)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,s,h,d,chunk", [(2, 64, 4, 8, 16),
                                           (1, 128, 2, 16, 32)])
def test_mlstm_chunked_vs_sequential(b, s, h, d, chunk):
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    q = jax.random.normal(ks[0], (b, s, h, d)) * 0.5
    k = jax.random.normal(ks[1], (b, s, h, d)) * 0.5
    v = jax.random.normal(ks[2], (b, s, h, d))
    ig = jax.random.normal(ks[3], (b, s, h))
    fg = jax.random.normal(ks[4], (b, s, h)) + 2.0
    y1, (C1, n1, m1) = ref.mlstm_chunked(q, k, v, ig, fg, chunk=chunk)
    y2, (C2, n2, m2) = ref.mlstm_sequential(q, k, v, ig, fg)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=2e-5, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(C1), np.asarray(C2),
                               atol=2e-5, rtol=2e-4)


def test_mlstm_decode_parity():
    b, s, h, d = 1, 32, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(8), 5)
    q = jax.random.normal(ks[0], (b, s, h, d)) * 0.5
    k = jax.random.normal(ks[1], (b, s, h, d)) * 0.5
    v = jax.random.normal(ks[2], (b, s, h, d))
    ig = jax.random.normal(ks[3], (b, s, h))
    fg = jax.random.normal(ks[4], (b, s, h)) + 2.0
    y_seq, _ = ref.mlstm_sequential(q, k, v, ig, fg)
    state = None
    outs = []
    import jax.numpy as jnp
    C = jnp.zeros((b, h, d, d)); n = jnp.zeros((b, h, d))
    m = jnp.full((b, h), -jnp.inf)
    state = (C, n, m)
    for t in range(s):
        state, yt = ref.mlstm_decode_step(state, q[:, t], k[:, t], v[:, t],
                                          ig[:, t], fg[:, t])
        outs.append(yt)
    y_dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(y_dec), np.asarray(y_seq),
                               atol=2e-5, rtol=2e-4)


# ---------------------------------------------------------------------------
# mlstm Pallas kernel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,d,chunk", [(2, 64, 2, 16, 16),
                                           (1, 128, 4, 32, 64)])
def test_mlstm_kernel_vs_sequential(b, s, h, d, chunk, dtype):
    from repro.kernels.mlstm_chunk import mlstm_chunk
    ks = jax.random.split(jax.random.PRNGKey(9), 5)
    q = (jax.random.normal(ks[0], (b, s, h, d)) * 0.5).astype(dtype)
    k = (jax.random.normal(ks[1], (b, s, h, d)) * 0.5).astype(dtype)
    v = jax.random.normal(ks[2], (b, s, h, d)).astype(dtype)
    ig = jax.random.normal(ks[3], (b, s, h))
    fg = jax.random.normal(ks[4], (b, s, h)) + 2.0
    y, (C, n, m) = mlstm_chunk(q, k, v, ig, fg, chunk=chunk,
                               interpret=True)
    y_ref, (C_ref, n_ref, m_ref) = ref.mlstm_sequential(q, k, v, ig, fg)
    assert y.dtype == dtype
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(C), np.asarray(C_ref),
                               atol=2e-2 if dtype == jnp.bfloat16 else 2e-4,
                               rtol=2e-2)
    np.testing.assert_allclose(np.asarray(m), np.asarray(m_ref),
                               atol=1e-3, rtol=1e-3)


# ---------------------------------------------------------------------------
# fused collective-stage kernels (Pallas executor tier)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m", [64, 257, 1031, 4096])   # incl. odd sizes
@pytest.mark.parametrize("wire", ["fp32", "bf16", "int8"])
def test_fused_combine_stage_parity(m, wire):
    rng = np.random.default_rng(m)
    acc = jnp.asarray(rng.standard_normal(m), jnp.float32)
    got32 = jnp.asarray(rng.standard_normal(m), jnp.float32)
    if wire == "fp32":
        got, scale = got32, None
    elif wire == "bf16":
        got, scale = got32.astype(jnp.bfloat16), None
    else:
        got, scale = ops.quantize_stage(got32, impl="ref")
    want = ref.combine_stage(acc, got, scale)
    fused = ops.combine_stage(acc, got, scale, impl="pallas_interpret")
    assert fused.dtype == want.dtype == jnp.float32
    if wire == "int8":
        # the dequant multiply-add may contract to an FMA inside the
        # kernel but not in the XLA oracle — 1 ULP of fp32 slack
        np.testing.assert_allclose(np.asarray(fused), np.asarray(want),
                                   atol=2e-6)
    else:
        # fp32 (and the widening bf16 cast) must be bit-identical
        np.testing.assert_array_equal(np.asarray(fused), np.asarray(want))
    inst = ops.combine_stage(acc, got, scale, accumulate=False,
                             impl="pallas_interpret")
    want_inst = ref.combine_stage(acc, got, scale, accumulate=False)
    if wire == "int8":
        np.testing.assert_allclose(np.asarray(inst), np.asarray(want_inst),
                                   atol=2e-6)
    else:
        np.testing.assert_array_equal(np.asarray(inst),
                                      np.asarray(want_inst))


@pytest.mark.parametrize("m", [63, 640, 2049])
def test_quantize_dequantize_stage_parity(m):
    rng = np.random.default_rng(m)
    x = jnp.asarray(rng.standard_normal(m) * 11.0, jnp.float32)
    q, scale = ops.quantize_stage(x, impl="pallas_interpret")
    q_ref, scale_ref = ops.quantize_stage(x, impl="ref")
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q_ref))
    np.testing.assert_array_equal(np.asarray(scale), np.asarray(scale_ref))
    assert q.dtype == jnp.int8
    deq = ops.dequantize_stage(q, scale, impl="pallas_interpret")
    np.testing.assert_array_equal(
        np.asarray(deq), np.asarray(ref.dequantize_stage(q, scale,
                                                         jnp.float32)))
    # round-trip error bounded by the uniform quantization step
    step = float(scale)
    assert np.max(np.abs(np.asarray(deq) - np.asarray(x))) <= step


@pytest.mark.parametrize("H,W", [(16, 16), (8, 32), (17, 5)])
def test_gs_stencil_kernel_parity(H, W):
    rng = np.random.default_rng(H * W)
    block = jnp.asarray(rng.standard_normal((H, W)), jnp.float32)
    top = jnp.asarray(rng.standard_normal(W), jnp.float32)
    bottom = jnp.asarray(rng.standard_normal(W), jnp.float32)
    left = jnp.asarray(rng.standard_normal(H), jnp.float32)
    right = jnp.asarray(rng.standard_normal(H), jnp.float32)
    new, res, edges = ops.gs_stencil(block, top, left, bottom, right,
                                     impl="pallas_interpret")
    new_r, res_r, edges_r = ref.gs_stencil(block, top, left, bottom, right)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(new_r))
    np.testing.assert_array_equal(np.asarray(res), np.asarray(res_r))
    for e, er in zip(edges, edges_r):
        np.testing.assert_array_equal(np.asarray(e), np.asarray(er))
    # the edge tuple is (top, bottom, left, right) rows of the NEW block
    np.testing.assert_array_equal(np.asarray(edges[0]),
                                  np.asarray(new)[0])
    np.testing.assert_array_equal(np.asarray(edges[3]),
                                  np.asarray(new)[:, -1])
