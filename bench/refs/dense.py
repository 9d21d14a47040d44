"""Plain float32 reference of a dense pre-norm GQA decoder (granite-3-2b).

Each layer is ``x += attn(rmsnorm(x))`` then ``x += swiglu(rmsnorm(x))``,
with split-half rotary embeddings, causal softmax attention and, where the
configuration ties them, the output head equal to the input embeddings.

Also here: the weights the benchmark serves the program (one jitted call
from the seed, in the program's parameter layout and serving dtype), and
the operations and bytes of one prefill and one decode step, computed from
shapes alone.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from . import common as C

Model = Dict[str, Any]


def check_pattern(mc: Model) -> None:
    if C.pattern(mc) != (("attn", "mlp"),):
        raise ValueError(f"dense reference needs the pattern "
                         f"[[attn, mlp]], got {mc['pattern']}")


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
def attn_init(key, mc: Model, dt) -> Dict[str, Any]:
    d, H, Hkv, D = mc["d_model"], mc["n_heads"], mc["n_kv_heads"], \
        C.head_dim(mc)
    k = jax.random.split(key, 4)
    return {"wq": C.dense(k[0], (d, H * D), dt),
            "wk": C.dense(k[1], (d, Hkv * D), dt),
            "wv": C.dense(k[2], (d, Hkv * D), dt),
            "wo": C.dense(k[3], (H * D, d), dt)}


def mlp_init(key, mc: Model, dt) -> Dict[str, Any]:
    d, ff = mc["d_model"], mc["d_ff"]
    k = jax.random.split(key, 3)
    return {"wg": C.dense(k[0], (d, ff), dt), "wu": C.dense(k[1], (d, ff), dt),
            "wd": C.dense(k[2], (ff, d), dt)}


def init_top(mc: Model, key) -> Dict[str, Any]:
    dt = jnp.dtype(mc["dtype"])
    k = jax.random.split(key, 3)
    top = {"embed": (0.02 * jax.random.normal(
               k[0], (mc["vocab"], mc["d_model"]), C.F32)).astype(dt),
           "final_norm": {"scale": C.around_one(k[1], (mc["d_model"],))}}
    if not mc["tie_embeddings"]:
        top["lm_head"] = C.dense(k[2], (mc["d_model"], mc["vocab"]), dt)
    return top


def init_unit(mc: Model, key) -> Dict[str, Any]:
    dt = jnp.dtype(mc["dtype"])
    k = jax.random.split(key, 4)
    return {"L0_0_attn": attn_init(k[0], mc, dt),
            "L0_0_norm": {"scale": C.around_one(k[1], (mc["d_model"],))},
            "L0_1_mlp": mlp_init(k[2], mc, dt),
            "L0_1_norm": {"scale": C.around_one(k[3], (mc["d_model"],))}}


def init(mc: Model, key) -> Dict[str, Any]:
    """The served weights (call under ``jax.jit``)."""
    check_pattern(mc)
    return C.init_params(mc, key, init_top, init_unit)


# ---------------------------------------------------------------------------
# the float32 forward
# ---------------------------------------------------------------------------
def apply_unit(mc: Model, up, x, x0, top, quant: bool):
    eps = mc["norm_eps"]
    x = x + C.causal_attention(
        up["L0_0_attn"], C.rmsnorm(x, up["L0_0_norm"]["scale"], eps), mc,
        quant)
    return x + C.swiglu(up["L0_1_mlp"],
                        C.rmsnorm(x, up["L0_1_norm"]["scale"], eps), quant)


def head(mc: Model, top, x, quant: bool):
    x = C.rmsnorm(x, top["final_norm"]["scale"], mc["norm_eps"])
    w = top["lm_head"] if "lm_head" in top else top["embed"].T
    return C.mm(x, w, quant)


def forward(mc: Model, key, seqs, positions, *, quant: bool = False,
            shape=(0, 0, 0)):
    """Float32 next-token logits of ``seqs`` at ``positions``."""
    check_pattern(mc)
    return C.forward(mc, key, seqs, positions, init_top=init_top,
                     init_unit=init_unit, apply_unit=apply_unit, head=head,
                     quant=quant, shape=shape)


# ---------------------------------------------------------------------------
# operations and bytes, from shapes
# ---------------------------------------------------------------------------
def _widths(mc: Model):
    d, H, Hkv, D = mc["d_model"], mc["n_heads"], mc["n_kv_heads"], \
        C.head_dim(mc)
    return d, H, Hkv, D, mc["d_ff"], mc["vocab"], mc["n_layers"]


def weight_bytes(mc: Model, tokens: int) -> int:
    """Bytes of weights that a pass over ``tokens`` tokens reads: every
    layer and the head once, and of an untied embedding table only the
    rows of those tokens."""
    d, H, Hkv, D, ff, V, L = _widths(mc)
    item = jnp.dtype(mc["dtype"]).itemsize
    per_layer = (d * (2 * H * D + 2 * Hkv * D) + 3 * d * ff) * item + 2 * d * 4
    heads = V * d * item + (0 if mc["tie_embeddings"] else tokens * d * item)
    return L * per_layer + heads + d * 4


def _layer_matmul_flops(mc: Model) -> int:
    """Weight-matmul operations of one layer for one token."""
    d, H, Hkv, D, ff, _, _ = _widths(mc)
    return 2 * (d * (2 * H * D + 2 * Hkv * D) + 3 * d * ff)


def prefill_cost(mc: Model, S: int):
    """(operations, bytes) of a prefill of ``S`` tokens: every position
    through every layer, causal attention, the head at the last position."""
    d, H, Hkv, D, ff, V, L = _widths(mc)
    attn = 4 * H * D * S * (S + 1) // 2
    flops = L * (S * _layer_matmul_flops(mc) + attn) + 2 * d * V
    item = jnp.dtype(mc["dtype"]).itemsize
    kv_write = L * 2 * S * Hkv * D * item
    return flops, weight_bytes(mc, S) + kv_write


def decode_cost(mc: Model, cache_len: int):
    """(operations, bytes) of one decode step against a cache of
    ``cache_len`` positions, which the step reads whole."""
    d, H, Hkv, D, ff, V, L = _widths(mc)
    flops = L * (_layer_matmul_flops(mc) + 4 * H * D * cache_len) + 2 * d * V
    item = jnp.dtype(mc["dtype"]).itemsize
    kv_read = L * 2 * cache_len * Hkv * D * item
    return flops, weight_bytes(mc, 1) + kv_read


def attention_layers(mc: Model) -> int:
    """Calls of the prefill attention kernel per prefill."""
    return mc["n_layers"]
