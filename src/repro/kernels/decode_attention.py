"""Decode attention over a bf16 KV cache, query heads grouped per KV head.

A decode step attends a few new query positions over the whole cache.  The
oracle (``ref.attention``) widens k and v to float32 and repeats them for
every query head of a KV head; under XLA that materialises the cache, per
row, ``H / Hkv`` times over in float32.  Here the queries are grouped as
``(B, S, Hkv, rep, D)`` and each contraction runs per KV head on the cache
as stored, so it is read once per call.

The new positions' k and v come beside the cache, not written into it:
the cache is read where it lies, at the positions before the new ones, and
the step writes the new entries once, where it stores the cache.  The
scores of the two parts are joined before one softmax, and the two p·v
contractions are summed.

The arithmetic is the oracle's over the cache with the new entries written
at ``cache_index``: q·k takes the bf16 operands with a float32 result
(their products are exact in float32), the softmax is float32, and p·v
keeps the float32 probabilities at ``Precision.HIGHEST``.  Masks and
window are the oracle's, position for position.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .ref import NEG_INF


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     k_new: jax.Array, v_new: jax.Array, *,
                     cache_index: jax.Array,
                     causal: bool = True,
                     window: Optional[int] = None,
                     scale: Optional[float] = None) -> jax.Array:
    """q: (B, S, H, D); k, v: the cache, (B, T, Hkv, D) with H % Hkv == 0;
    k_new, v_new: the S new positions', (B, S, Hkv, D).

    ``cache_index``: (B,) — the absolute position of q[:, 0] and of the
    first new entry; the cache attends only at positions before it.
    Scores are scaled by ``scale`` (default ``D^-1/2``).
    """
    B, S, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    qg = q.reshape(B, S, Hkv, H // Hkv, D)
    first = cache_index[:, None, None]                       # (B, 1, 1)
    q_pos = jnp.arange(S)[None, :, None] + first             # (B, S, 1)

    def scores(kp, k_pos, mask):
        s = jnp.einsum("bsgrd,btgd->bgrst", qg, kp,
                       preferred_element_type=jnp.float32)
        s = s / jnp.sqrt(D) if scale is None else s * scale
        if causal:
            mask = mask & (k_pos <= q_pos)
        if window is not None:
            mask = mask & (k_pos > q_pos - window)
        return jnp.where(mask[:, None, None], s, NEG_INF)

    cache_pos = jnp.arange(T)[None, None, :]                 # (1, 1, T)
    new_pos = jnp.arange(S)[None, None, :] + first           # (B, 1, S)
    probs = jax.nn.softmax(jnp.concatenate(
        [scores(k, cache_pos, cache_pos < first),
         scores(k_new, new_pos, jnp.ones((1, 1, S), bool))], axis=-1),
        axis=-1)

    def pv(p, vp):
        return jnp.einsum("bgrst,btgd->bsgrd", p, vp,
                          preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)

    out = pv(probs[..., :T], v) + pv(probs[..., T:], v_new)
    return out.reshape(B, S, H, D).astype(q.dtype)
