"""Chunkwise mLSTM (xLSTM matrix-memory) Pallas-TPU kernel.

Same TPU shape as the SSD kernel: the chunk-quadratic gate/score matrices
live in VMEM, the (d×d) matrix memory C plus normalizer n and stabilizer m
are carried across the innermost (sequential) grid dimension in VMEM
scratch, and each chunk contributes three MXU matmuls (q·kᵀ, scores·v,
kᵀ·v).  Numerics follow the stabilised xLSTM recurrence exactly
(log-space forget-gate accumulation, running max stabiliser, |n·q| floor).

Validated in interpret mode against ``ref.mlstm_sequential``.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, igc_ref, igr_ref, lfc_ref, lfr_ref,
            y_ref, cf_ref, nf_ref, mf_ref,
            C_ref, n_ref, m_ref, *, chunk: int, n_chunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        C_ref[...] = jnp.zeros_like(C_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)

    q = q_ref[...].astype(jnp.float32)                  # (l, d)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    ig = igc_ref[...]                                   # (l, 1)
    ig_row = igr_ref[...]                               # (1, l)
    lf_cum = lfc_ref[...]                               # (l, 1)
    lf_cum_row = lfr_ref[...]                           # (1, l)

    C = C_ref[...]                                       # (d, d)
    n = n_ref[...]                                       # (1, d)
    m = m_ref[...]                                       # (1, 1)

    seg = lf_cum - lf_cum_row                            # sum_{j<k<=i} lf
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    a_loc = jnp.where(row >= col, seg + ig_row, NEG)
    m_local = jnp.max(a_loc, axis=-1, keepdims=True)     # (l, 1)
    m_in = lf_cum + m                                    # (l, 1)
    m_new = jnp.maximum(m_local, m_in)

    w = jnp.exp(a_loc - m_new)                           # (l, l)
    qk = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    scores = qk * w
    num = jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    den = jnp.sum(scores, axis=-1, keepdims=True)        # (l, 1)

    scale_in = jnp.exp(m_in - m_new)                     # (l, 1)
    num += jax.lax.dot_general(q, C, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32) \
        * scale_in
    den += jnp.sum(q * n, axis=-1, keepdims=True) * scale_in
    den = jnp.maximum(jnp.abs(den), jnp.exp(-m_new))
    y_ref[...] = (num / den).astype(y_ref.dtype)

    # carry to end of chunk
    # Chunk-end values as (1, 1) tiles by masked reductions: a static
    # slice at the last lane/sublane gives a layout Mosaic cannot
    # broadcast.
    last_col = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1) == chunk - 1
    last_row = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1
    total = jnp.sum(jnp.where(last_col, lf_cum_row, 0.0), axis=1,
                    keepdims=True)                       # (1, 1)
    m_end = jnp.sum(jnp.where(last_row, m_new, 0.0), axis=0,
                    keepdims=True)                       # (1, 1)
    w_end = jnp.exp(ig + total - lf_cum - m_end)         # (l, 1)
    # A scalar, not a (1, 1) tile: Mosaic broadcasts a tile along
    # sublanes or lanes, not both at once, and C is (d, d).
    decay = jnp.exp(total + m - m_end)[0, 0]
    C_ref[...] = C * decay + jax.lax.dot_general(
        k * w_end, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    n_ref[...] = n * decay + jnp.sum(k * w_end, axis=0, keepdims=True)
    m_ref[...] = m_end

    @pl.when(ic == n_chunks - 1)
    def _fin():
        cf_ref[...] = C_ref[...]
        nf_ref[...] = n_ref[...]
        mf_ref[...] = m_ref[...]


def mlstm_chunk(q: jax.Array, k: jax.Array, v: jax.Array,
                i_gate: jax.Array, f_gate: jax.Array, *, chunk: int = 128,
                interpret: bool = False
                ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array,
                                            jax.Array]]:
    """q,k,v: (b,s,h,d); i_gate,f_gate: (b,s,h) pre-activation logits.

    Returns (y, (C, n, m)) with C: (b,h,d,d), n: (b,h,d), m: (b,h) fp32.

    The kernel runs heads-major over (b, h, s, ·) arrays with squeezed
    batch/head block dims (TPU tiling rule).  The within-chunk
    ``cumsum(log_sigmoid(f))`` is an O(b·s·h) scan done here in XLA; it
    and the input gate reach the kernel as a column and as a row, which
    keeps every in-kernel operand 2-D.
    """
    b, s, h, d = q.shape
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    kernel = functools.partial(_kernel, chunk=chunk, n_chunks=nc)
    grid = (b, h, nc)

    lf = jax.nn.log_sigmoid(f_gate.astype(jnp.float32))
    lf_cum = jnp.cumsum(lf.reshape(b, nc, chunk, h), axis=2).reshape(b, s, h)
    ig = jnp.swapaxes(i_gate.astype(jnp.float32), 1, 2)      # (b,h,s)
    lf_cum = jnp.swapaxes(lf_cum, 1, 2)
    seq_spec = pl.BlockSpec((None, None, chunk, d),
                            lambda ib, ih, ic: (ib, ih, ic, 0))
    col_spec = pl.BlockSpec((None, None, chunk, 1),
                            lambda ib, ih, ic: (ib, ih, ic, 0))
    row_spec = pl.BlockSpec((None, None, 1, chunk),
                            lambda ib, ih, ic: (ib, ih, 0, ic))

    def carry_spec(r, c):
        return pl.BlockSpec((None, None, r, c),
                            lambda ib, ih, ic: (ib, ih, 0, 0))

    y, Cf, nf, mf = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[seq_spec, seq_spec, seq_spec, col_spec, row_spec,
                  col_spec, row_spec],
        out_specs=[seq_spec, carry_spec(d, d), carry_spec(1, d),
                   carry_spec(1, 1)],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, d, d), jnp.float32),
            jax.ShapeDtypeStruct((b, h, 1, d), jnp.float32),
            jax.ShapeDtypeStruct((b, h, 1, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((d, d), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*(jnp.swapaxes(a, 1, 2) for a in (q, k, v)),
      ig[..., None], ig[:, :, None, :], lf_cum[..., None],
      lf_cum[:, :, None, :])
    return jnp.swapaxes(y, 1, 2), (Cf, nf[:, :, 0], mf[:, :, 0, 0])
