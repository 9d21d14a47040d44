"""Fused collective-stage Pallas kernels — the Level-B executor tier.

The explicit-round allreduce lowerings (:mod:`repro.core.lowering`) emit,
between ``lax.ppermute`` rounds, purely memory-bound elementwise stages:
the reduce-scatter combine (``recv_chunk + accum``), the allgather chunk
install, and — under a narrow wire dtype — the int8/bf16 cast and dequant.
Left to XLA these lower as separate elementwise ops whose intermediates
round-trip HBM once per stage.  The kernels here fuse each round's stage
into ONE VMEM pass:

* :func:`fused_combine` — ``out = acc + dequant(got)`` (or just
  ``dequant(got)`` with ``accumulate=False``): the received chunk is cast
  out of its wire dtype, optionally scaled (int8 symmetric quantisation),
  and accumulated in a single read of ``acc``/``got`` and a single write
  of ``out`` — no materialised fp32 copy of the wire payload.
* :func:`quantize_wire` — symmetric int8 quantisation of an outgoing
  chunk against a precomputed scale (round, clip, cast, store in one
  pass).
* :func:`dequantize_wire` — the standalone inverse for allgather-leg
  chunks that travelled the whole ring in wire dtype.
* :func:`gs_stencil` — the Gauss–Seidel block stage: 4-point interior
  update, L1 residual, and the four outgoing boundary edges
  (boundary-pack) produced in one pass over the block; the halo transfers
  themselves stay event-bound host tasks.

All kernels take 1-D payloads of ANY length (odd sizes included): the
wrappers pad to the fp32/bf16/int8 tile granularity and reshape to
``(rows, 128)`` lanes before entering ``pl.pallas_call``, then strip the
padding.  ``interpret=True`` runs the kernel bodies under the Pallas
interpreter on CPU — the parity mode ``tests/test_kernels.py`` pins
against the jnp oracles in :mod:`repro.kernels.ref`.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# One lane register row is 128 wide on every TPU generation; 32 sublanes
# cover the minimum tile height of fp32 (8), bf16 (16) and int8 (32), so
# padding to (32k, 128) keeps every wire dtype tile-aligned.
_LANE = 128
_SUBLANE = 32
_BLOCK_ROWS = 256          # (256, 128) fp32 block = 128 KiB of VMEM


def _pad_rows(flat: jax.Array) -> Tuple[jax.Array, int]:
    """Pad a flat vector to a (rows, 128) tile-aligned matrix."""
    m = flat.shape[0]
    pad = (-m) % (_SUBLANE * _LANE)
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(-1, _LANE), m


def _row_grid(rows: int) -> Tuple[int, int]:
    """(grid, block_rows) over the padded row dimension."""
    br = min(rows, _BLOCK_ROWS)
    return pl.cdiv(rows, br), br


# ---------------------------------------------------------------------------
# Fused combine (+ cast/dequant)
# ---------------------------------------------------------------------------
def _combine_kernel(acc_ref, got_ref, o_ref, *, accumulate: bool):
    got = got_ref[...].astype(o_ref.dtype)
    o_ref[...] = acc_ref[...] + got if accumulate else got


def _combine_scaled_kernel(scale_ref, acc_ref, got_ref, o_ref, *,
                           accumulate: bool):
    got = got_ref[...].astype(jnp.float32) * scale_ref[0]
    got = got.astype(o_ref.dtype)
    o_ref[...] = acc_ref[...] + got if accumulate else got


def fused_combine(acc: jax.Array, got: jax.Array,
                  scale: Optional[jax.Array] = None, *,
                  accumulate: bool = True,
                  interpret: bool = False) -> jax.Array:
    """``acc + dequant(got)`` in one VMEM pass (1-D operands).

    ``got`` may arrive in a narrower wire dtype (bf16, int8); it is cast
    to ``acc.dtype`` — via ``× scale`` for int8 symmetric quantisation —
    inside the kernel, so the fp32 copy of the wire payload never touches
    HBM.  ``accumulate=False`` skips the add (the allgather-leg chunk
    install).  Output dtype and shape follow ``acc``.
    """
    if acc.shape != got.shape:
        raise ValueError(f"acc/got shape mismatch: {acc.shape} vs "
                         f"{got.shape}")
    a2, m = _pad_rows(acc.reshape(-1))
    g2, _ = _pad_rows(got.reshape(-1))
    grid, br = _row_grid(a2.shape[0])
    row_spec = pl.BlockSpec((br, _LANE), lambda i: (i, 0))
    if scale is None:
        out = pl.pallas_call(
            functools.partial(_combine_kernel, accumulate=accumulate),
            grid=(grid,),
            in_specs=[row_spec, row_spec],
            out_specs=row_spec,
            out_shape=jax.ShapeDtypeStruct(a2.shape, acc.dtype),
            interpret=interpret,
        )(a2, g2)
    else:
        out = pl.pallas_call(
            functools.partial(_combine_scaled_kernel,
                              accumulate=accumulate),
            grid=(grid,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                row_spec, row_spec,
            ],
            out_specs=row_spec,
            out_shape=jax.ShapeDtypeStruct(a2.shape, acc.dtype),
            interpret=interpret,
        )(jnp.asarray(scale, jnp.float32).reshape(1), a2, g2)
    return out.reshape(-1)[:m].reshape(acc.shape)


# ---------------------------------------------------------------------------
# Wire quantisation
# ---------------------------------------------------------------------------
def _quant_kernel(scale_ref, x_ref, q_ref):
    inv = 1.0 / scale_ref[0]
    q = jnp.round(x_ref[...].astype(jnp.float32) * inv)
    q_ref[...] = jnp.clip(q, -127.0, 127.0).astype(jnp.int8)


def quantize_wire(x: jax.Array, scale: jax.Array, *,
                  interpret: bool = False) -> jax.Array:
    """Symmetric int8 quantisation against ``scale`` (one pass).

    ``scale`` is the caller-computed ``max|x|/127`` (a scalar reduction
    XLA already does in one read); the kernel fuses divide, round, clip
    and the int8 store so the quantised copy is the only write.
    """
    x2, m = _pad_rows(x.reshape(-1))
    grid, br = _row_grid(x2.shape[0])
    row_spec = pl.BlockSpec((br, _LANE), lambda i: (i, 0))
    q = pl.pallas_call(
        _quant_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), row_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct(x2.shape, jnp.int8),
        interpret=interpret,
    )(jnp.asarray(scale, jnp.float32).reshape(1), x2)
    return q.reshape(-1)[:m].reshape(x.shape)


def _dequant_kernel(scale_ref, q_ref, o_ref):
    o_ref[...] = (q_ref[...].astype(jnp.float32)
                  * scale_ref[0]).astype(o_ref.dtype)


def dequantize_wire(q: jax.Array, scale: jax.Array,
                    dtype=jnp.float32, *,
                    interpret: bool = False) -> jax.Array:
    """``q × scale`` cast to ``dtype`` in one pass (allgather-leg chunks
    that travelled the ring in wire dtype)."""
    q2, m = _pad_rows(q.reshape(-1))
    grid, br = _row_grid(q2.shape[0])
    row_spec = pl.BlockSpec((br, _LANE), lambda i: (i, 0))
    out = pl.pallas_call(
        _dequant_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), row_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct(q2.shape, jnp.dtype(dtype)),
        interpret=interpret,
    )(jnp.asarray(scale, jnp.float32).reshape(1), q2)
    return out.reshape(-1)[:m].reshape(q.shape)


# ---------------------------------------------------------------------------
# Fused Gauss–Seidel block stage: interior update + residual + boundary pack
# ---------------------------------------------------------------------------
def _gs_kernel(b_ref, t_ref, l_ref, btm_ref, r_ref,
               o_ref, res_ref, te_ref, be_ref, le_ref, re_ref):
    b = b_ref[...].astype(jnp.float32)
    up = jnp.concatenate([t_ref[...], b[:-1, :]], axis=0)
    down = jnp.concatenate([b[1:, :], btm_ref[...]], axis=0)
    left = jnp.concatenate([l_ref[...], b[:, :-1]], axis=1)
    right = jnp.concatenate([b[:, 1:], r_ref[...]], axis=1)
    new = 0.25 * (up + down + left + right)
    o_ref[...] = new.astype(o_ref.dtype)
    # (1, 1) vector tile: a scalar store into VMEM does not lower.
    res_ref[...] = jnp.sum(jnp.sum(jnp.abs(new - b), axis=1, keepdims=True),
                           axis=0, keepdims=True)
    te_ref[...] = new[:1, :].astype(te_ref.dtype)
    be_ref[...] = new[-1:, :].astype(be_ref.dtype)
    le_ref[...] = new[:, :1].astype(le_ref.dtype)
    re_ref[...] = new[:, -1:].astype(re_ref.dtype)


def gs_stencil(block: jax.Array, top: jax.Array, left: jax.Array,
               bottom: jax.Array, right: jax.Array, *,
               interpret: bool = False):
    """Fused Gauss–Seidel block stage.

    One pass over the (H, W) block producing the 4-point average update,
    the block's L1 residual ``sum|new - old|``, and the four NEW boundary
    edges packed for the next halo exchange — the separate residual
    re-read and edge-slice passes of the unfused path never happen.
    Returns ``(new_block, residual, (top, bottom, left, right))`` with
    edges shaped like the inputs (length W, W, H, H).

    The whole block lives in VMEM for the pass (a 512×512 fp32 block is
    1 MiB — comfortably resident); halo transfers stay event-bound tasks
    on the host runtime.
    """
    H, W = block.shape
    dt = block.dtype
    t2 = jnp.asarray(top, dt).reshape(1, W)
    b2 = jnp.asarray(bottom, dt).reshape(1, W)
    l2 = jnp.asarray(left, dt).reshape(H, 1)
    r2 = jnp.asarray(right, dt).reshape(H, 1)
    out_shapes = (
        jax.ShapeDtypeStruct((H, W), dt),          # new block
        jax.ShapeDtypeStruct((1, 1), jnp.float32),  # residual
        jax.ShapeDtypeStruct((1, W), dt),          # top edge
        jax.ShapeDtypeStruct((1, W), dt),          # bottom edge
        jax.ShapeDtypeStruct((H, 1), dt),          # left edge
        jax.ShapeDtypeStruct((H, 1), dt),          # right edge
    )
    new, res, te, be, le, re = pl.pallas_call(
        _gs_kernel,
        out_shape=out_shapes,
        interpret=interpret,
    )(block, t2, l2, b2, r2)
    return new, res[0, 0], (te.reshape(W), be.reshape(W),
                            le.reshape(H), re.reshape(H))
