"""Public kernel entry points with platform dispatch.

Models call these wrappers; each dispatches to the Pallas-TPU kernel on TPU
backends and to the pure-jnp oracle elsewhere (CPU dry-run / tests), unless
forced with ``impl=``:

* ``impl="pallas"``            — the TPU kernel (compiled)
* ``impl="pallas_interpret"``  — the TPU kernel body, interpreted (CPU)
* ``impl="ref"``               — the jnp oracle
* ``impl=None``                — auto: pallas on TPU else ref

On a TPU backend ``impl=None`` never falls back: the compiled kernel
runs, or its compile error surfaces.  The kernels keep the callers'
``(B, S, H, D)`` layout; each kernel module's wrapper moves heads in
front of the sequence for the TPU tiling rule.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import collective_stages as _stages
from . import ref
from .decode_attention import decode_attention as _decode_attention
from .flash_attention import flash_attention as _flash_pallas
from .mamba2_ssd import mamba2_ssd as _ssd_pallas
from .moe_gmm import moe_gmm as _gmm_pallas
from .mlstm_chunk import mlstm_chunk as _mlstm_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


IMPLS = ("pallas", "pallas_interpret", "ref")


def _resolve(impl: Optional[str]) -> str:
    if impl is None:
        return "pallas" if _on_tpu() else "ref"
    if impl not in IMPLS:
        raise ValueError(f"unknown kernel impl {impl!r}; one of {IMPLS}")
    return impl


def pallas_impl() -> str:
    """The Pallas kernel as this backend runs it: compiled on a TPU,
    interpreted elsewhere (for callers that must take the kernel path)."""
    return "pallas" if _on_tpu() else "pallas_interpret"


def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    impl: Optional[str] = None, constrain=None) -> jax.Array:
    """Scores are scaled by ``scale`` (default ``head_dim^-1/2``)."""
    impl = _resolve(impl)
    if impl == "ref":
        # Flash-style chunked jnp (O(S·chunk) memory) — dense ref.attention
        # stays the oracle for small-shape kernel tests.  Long causal
        # self-attention uses the one-level causal split (-25% flops).
        S, T = q.shape[1], k.shape[1]
        if causal and window is None and S == T and S >= 4096 \
                and S % 2 == 0:
            return ref.attention_causal_split(q, k, v, constrain=constrain,
                                              scale=scale)
        return ref.attention_chunked(q, k, v, causal=causal, window=window,
                                     constrain=constrain, scale=scale)
    return _flash_pallas(q, k, v, causal=causal, window=window, scale=scale,
                         block_q=block_q, block_k=block_k,
                         interpret=(impl == "pallas_interpret"))


def mamba2_ssd(x, dt, A, B, C, *, chunk: int = 128, init_state=None,
               impl: Optional[str] = None) -> Tuple[jax.Array, jax.Array]:
    s = x.shape[1]
    chunk = min(chunk, s) if s % chunk != 0 else chunk
    pad = (-s) % chunk
    if pad:
        # dt = 0 on padded steps: decay exp(0·A) = 1 and zero input, so the
        # final state is untouched; padded outputs are sliced off.
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    impl = _resolve(impl)
    if impl == "ref":
        y, st = ref.ssd_chunked(x, dt, A, B, C, chunk=chunk,
                                init_state=init_state)
    else:
        y, st = _ssd_pallas(x, dt, A, B, C, chunk=chunk,
                            init_state=init_state,
                            interpret=(impl == "pallas_interpret"))
    return (y[:, :s] if pad else y), st


def mlstm_chunked(q, k, v, i_gate, f_gate, *, chunk: int, init=None,
                  impl: Optional[str] = None):
    s = q.shape[1]
    chunk = min(chunk, s) if s % chunk != 0 else chunk
    pad = (-s) % chunk
    if pad:
        # i = -inf on padded steps (no insertion), f logits >> 0 (log-sigmoid
        # ≈ 0 ⇒ no decay): the carry state passes through unchanged.
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        i_gate = jnp.pad(i_gate, ((0, 0), (0, pad), (0, 0)),
                         constant_values=-1e30)
        f_gate = jnp.pad(f_gate, ((0, 0), (0, pad), (0, 0)),
                         constant_values=30.0)
    impl = _resolve(impl)
    if impl != "ref" and init is None:
        # Pallas kernel path (zero initial state only — prefill/train);
        # decode chaining goes through the jnp chunked implementation.
        y, st = _mlstm_pallas(q, k, v, i_gate, f_gate, chunk=chunk,
                              interpret=(impl == "pallas_interpret"))
    else:
        y, st = ref.mlstm_chunked(q, k, v, i_gate, f_gate, chunk=chunk,
                                  init=init)
    return (y[:, :s] if pad else y), st


def moe_gmm(x, w, *, impl: Optional[str] = None, **blocks) -> jax.Array:
    """Batched expert matmul: x (E, C, K) × w (E, K, N) -> (E, C, N)."""
    impl = _resolve(impl)
    if impl == "ref":
        E, C, K = x.shape
        sizes = jnp.full((E,), C, jnp.int32)
        return ref.gmm(x.reshape(E * C, K), w, sizes).reshape(
            E, C, w.shape[-1])
    return _gmm_pallas(x, w, interpret=(impl == "pallas_interpret"),
                       **blocks)


# ---------------------------------------------------------------------------
# Fused collective stages (the Level-B executor tier; see
# repro.kernels.collective_stages and repro.core.lowering stage_impl=)
# ---------------------------------------------------------------------------
def combine_stage(acc, got, scale=None, *, accumulate: bool = True,
                  impl: Optional[str] = None) -> jax.Array:
    """Fused reduce-scatter combine: ``acc + dequant(got)`` in one pass.

    ``got`` may be in a narrower wire dtype (bf16, or int8 with
    ``scale``); ``accumulate=False`` is the allgather-leg chunk install.
    """
    impl = _resolve(impl)
    if impl == "ref":
        return ref.combine_stage(acc, got, scale, accumulate=accumulate)
    return _stages.fused_combine(acc, got, scale, accumulate=accumulate,
                                 interpret=(impl == "pallas_interpret"))


def quantize_stage(x, *, impl: Optional[str] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """Symmetric int8 wire quantisation; returns ``(q, scale)``.

    The scalar ``max|x|/127`` reduction happens in XLA (one read); the
    round/clip/cast store is the fused single-pass kernel.
    """
    scale = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32))),
                        1e-20) / 127.0
    impl = _resolve(impl)
    if impl == "ref":
        return ref.quantize_stage(x, scale), scale
    return _stages.quantize_wire(
        x, scale, interpret=(impl == "pallas_interpret")), scale


def dequantize_stage(q, scale, dtype=jnp.float32, *,
                     impl: Optional[str] = None) -> jax.Array:
    impl = _resolve(impl)
    if impl == "ref":
        return ref.dequantize_stage(q, scale, dtype)
    return _stages.dequantize_wire(q, scale, dtype,
                                   interpret=(impl == "pallas_interpret"))


def gs_stencil(block, top, left, bottom, right, *,
               impl: Optional[str] = None):
    """Fused Gauss–Seidel block stage: 4-point update, L1 residual and
    the four outgoing boundary edges in one pass over the block.
    Returns ``(new_block, residual, (top, bottom, left, right))``."""
    impl = _resolve(impl)
    if impl == "ref":
        return ref.gs_stencil(block, top, left, bottom, right)
    return _stages.gs_stencil(block, top, left, bottom, right,
                              interpret=(impl == "pallas_interpret"))


# Pure-jnp layers with no Pallas variant:
mlstm_sequential = ref.mlstm_sequential
mlstm_decode_step = ref.mlstm_decode_step
ssd_decode_step = ref.ssd_decode_step
decode_attention = _decode_attention
