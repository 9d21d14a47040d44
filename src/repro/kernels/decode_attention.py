"""Decode attention over a bf16 KV cache, query heads grouped per KV head.

A decode step attends a few new query positions over the whole cache.  The
oracle (``ref.attention``) widens k and v to float32 and repeats them for
every query head of a KV head; under XLA that materialises the cache, per
row, ``H / Hkv`` times over in float32.  Here the queries are grouped as
``(B, S, Hkv, rep, D)`` and each contraction runs per KV head on the cache
as stored, so it is read once per call.

The arithmetic is the oracle's: q·k takes the bf16 operands with a float32
result (their products are exact in float32), the softmax is float32, and
p·v keeps the float32 probabilities at ``Precision.HIGHEST``.  Masks,
window and valid length are the oracle's, position for position.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .ref import NEG_INF


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     causal: bool = True,
                     window: Optional[int] = None,
                     kv_valid_len: Optional[jax.Array] = None,
                     q_offset: Optional[jax.Array] = None,
                     scale: Optional[float] = None) -> jax.Array:
    """q: (B, S, H, D); k, v: (B, T, Hkv, D) with H % Hkv == 0.

    Scores are scaled by ``scale`` (default ``D^-1/2``).
    ``kv_valid_len``: (B,) — only cache positions < len attend.
    ``q_offset``: (B,) — absolute position of q[:, 0] (the cache index).
    """
    B, S, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    qg = q.reshape(B, S, Hkv, H // Hkv, D)
    scores = jnp.einsum("bsgrd,btgd->bgrst", qg, k,
                        preferred_element_type=jnp.float32)
    if scale is None:
        scores = scores / jnp.sqrt(D)
    else:
        scores = scores * scale

    q_pos = jnp.arange(S)[None, :, None]                     # (1, S, 1)
    if q_offset is not None:
        q_pos = q_pos + q_offset[:, None, None]              # (B, S, 1)
    k_pos = jnp.arange(T)[None, None, :]                     # (1, 1, T)
    mask = jnp.ones((1, S, T), bool)
    if causal:
        mask = mask & (k_pos <= q_pos)
    if window is not None:
        mask = mask & (k_pos > q_pos - window)
    if kv_valid_len is not None:
        mask = mask & (k_pos < kv_valid_len[:, None, None])
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgrst,btgd->bsgrd", probs, v,
                     preferred_element_type=jnp.float32,
                     precision=jax.lax.Precision.HIGHEST)
    return out.reshape(B, S, H, D).astype(q.dtype)
