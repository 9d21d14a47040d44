"""Mamba2 SSD (state-space dual) chunked-scan Pallas-TPU kernel.

The SSD hot loop is the sequence-mixing hot spot of the Mamba2/Zamba2
architectures.  The TPU-native formulation keeps the chunk-quadratic part on
the MXU — three (chunk × chunk/state) matmuls per chunk — and carries the
inter-chunk recurrent state in VMEM scratch across the innermost grid
dimension, which Pallas-TPU executes sequentially.  This mirrors how the
GPU algorithm's cross-chunk pass is replaced by a grid-carried accumulator
instead of a separate kernel launch: one HBM→VMEM pass over x/dt/B/C, no
intermediate state tensor in HBM.

Layout choices for the TPU memory hierarchy:
* chunk length and state width default to 128 (MXU-aligned);
* per-(batch, head) state tile (headdim × state) lives in VMEM scratch;
* fp32 accumulation throughout; inputs may be bf16.

Validated against ``ref.ssd_chunked`` / ``ref.ssd_sequential`` with
``interpret=True`` (tests/test_kernels.py).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, cc_ref, cr_ref, b_ref, c_ref, init_ref,
                y_ref, fin_ref, state_ref, *, chunk: int, n_chunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = init_ref[...].astype(jnp.float32)    # (p, n)

    x = x_ref[...].astype(jnp.float32)                        # (l, p)
    dt = dt_ref[...]                                          # (l, 1)
    cums = cc_ref[...]                                        # (l, 1)
    cums_row = cr_ref[...]                                    # (1, l)
    B = b_ref[...].astype(jnp.float32)                        # (l, n)
    C = c_ref[...].astype(jnp.float32)                        # (l, n)

    xd = x * dt                                               # (l, p)

    # intra-chunk: Y_diag = ((C B^T) ∘ L) xd,  L[i,j] = exp(sum_{j<k<=i} dA)
    seg = cums - cums_row                                     # (l, l)
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(row >= col, jnp.exp(seg), 0.0)              # (l, l)
    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    y = jax.lax.dot_general(cb * L, xd, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: contribution of the carried state
    state = state_ref[...]                                    # (p, n)
    y += jnp.exp(cums) * jax.lax.dot_general(
        C, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    # state update: state' = exp(sum dA) state + xd^T (B ∘ decay)
    last = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1) == chunk - 1
    total = jnp.sum(jnp.where(last, cums_row, 0.0), axis=1,
                    keepdims=True)                            # (1, 1)
    decay_states = jnp.exp(total - cums)                      # (l, 1)
    state_ref[...] = state * jnp.exp(total) + jax.lax.dot_general(
        xd, B * decay_states, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    y_ref[...] = y.astype(y_ref.dtype)

    @pl.when(ic == n_chunks - 1)
    def _fin():
        fin_ref[...] = state_ref[...]


def mamba2_ssd(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
               C: jax.Array, *, chunk: int = 128,
               init_state: Optional[jax.Array] = None,
               interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan.

    x: (b, s, h, p);  dt: (b, s, h);  A: (h,);  B, C: (b, s, n).
    Returns (y: (b, s, h, p), final_state: (b, h, p, n) fp32).

    The kernel runs heads-major over (b, h, s, ·) arrays with squeezed
    batch/head block dims, so every block is a tile that meets the TPU
    tiling rule.  The per-head decay log ``cumsum(dt·A)`` within each
    chunk is an O(b·s·h) scan done here in XLA and handed to the kernel
    as a column and as a row, which keeps every in-kernel operand 2-D.
    """
    b, s, h, p = x.shape
    n = B.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    if init_state is None:
        init_state = jnp.zeros((b, h, p, n), jnp.float32)

    dtf = dt.astype(jnp.float32)
    dA = (dtf * A.astype(jnp.float32)).reshape(b, nc, chunk, h)
    cums = jnp.cumsum(dA, axis=2).reshape(b, s, h)
    dt_col = jnp.swapaxes(dtf, 1, 2)[..., None]               # (b,h,s,1)
    cums_col = jnp.swapaxes(cums, 1, 2)[..., None]            # (b,h,s,1)
    cums_row = jnp.swapaxes(cums, 1, 2)[:, :, None, :]        # (b,h,1,s)

    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=nc)
    grid = (b, h, nc)
    seq_spec = pl.BlockSpec((None, None, chunk, p),
                            lambda ib, ih, ic: (ib, ih, ic, 0))
    col_spec = pl.BlockSpec((None, None, chunk, 1),
                            lambda ib, ih, ic: (ib, ih, ic, 0))
    row_spec = pl.BlockSpec((None, None, 1, chunk),
                            lambda ib, ih, ic: (ib, ih, 0, ic))
    bc_spec = pl.BlockSpec((None, chunk, n), lambda ib, ih, ic: (ib, ic, 0))
    state_spec = pl.BlockSpec((None, None, p, n),
                              lambda ib, ih, ic: (ib, ih, 0, 0))

    y, fin = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[seq_spec, col_spec, col_spec, row_spec, bc_spec, bc_spec,
                  state_spec],
        out_specs=[seq_spec, state_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(jnp.swapaxes(x, 1, 2), dt_col, cums_col, cums_row, B, C, init_state)
    return jnp.swapaxes(y, 1, 2), fin
