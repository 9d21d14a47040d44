"""Neural network blocks: norms, RoPE, GQA attention, MLP/MoE, SSM, xLSTM.

Functional style: every block has ``<block>_init(key, cfg) -> params`` and
``<block>_apply(params, x, ...) -> (y, new_cache)``.  Params are plain
pytrees (dicts of arrays) so sharding rules can be attached by path
(runtime/sharding.py) and stacked along a leading layer dimension for
``lax.scan`` over layers (models/model.py).

Numerics policy: weights and activations in ``cfg.dtype`` (bf16 by
default); norms, SSM decay/bias terms and recurrent states in fp32.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels import ops
from .config import ModelConfig

Params = Dict[str, Any]
Cache = Optional[Dict[str, Any]]


def _dense_init(key, shape, dtype, fan_in=None):
    fan_in = fan_in if fan_in is not None else shape[0]
    return (jax.random.normal(key, shape, jnp.float32)
            / jnp.sqrt(jnp.maximum(fan_in, 1))).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def norm_init(cfg: ModelConfig, d: Optional[int] = None) -> Params:
    d = d or cfg.d_model
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def norm_apply(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * p["scale"] + p["bias"]
    else:
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + cfg.norm_eps) * p["scale"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_angles(positions: jax.Array, dim: int, theta: float) -> jax.Array:
    """positions (...,) -> angles (..., dim // 2) fp32."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    return positions.astype(jnp.float32)[..., None] * inv


def rope_apply(x: jax.Array, angles: jax.Array) -> jax.Array:
    """x: (B, S, H, D); angles: (B, S, D//2) or (S, D//2)."""
    if angles.ndim == 2:
        angles = angles[None]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA; global or sliding window)
# ---------------------------------------------------------------------------
def attn_init(key, cfg: ModelConfig, d_in: Optional[int] = None) -> Params:
    d = d_in or cfg.d_model
    dh, H, Hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    dt = cfg.activation_dtype
    return {
        "wq": _dense_init(ks[0], (d, H * dh), dt),
        "wk": _dense_init(ks[1], (d, Hkv * dh), dt),
        "wv": _dense_init(ks[2], (d, Hkv * dh), dt),
        "wo": _dense_init(ks[3], (H * dh, cfg.d_model), dt),
    }


def attn_cache_init(cfg: ModelConfig, batch: int, cache_len: int) -> Params:
    dh, Hkv = cfg.head_dim, cfg.n_kv_heads
    dt = cfg.activation_dtype
    return {"k": jnp.zeros((batch, cache_len, Hkv, dh), dt),
            "v": jnp.zeros((batch, cache_len, Hkv, dh), dt)}


def attn_apply(p: Params, x: jax.Array, cfg: ModelConfig, *,
               angles: jax.Array, window: Optional[int] = None,
               cache: Cache = None, cache_index: Optional[jax.Array] = None,
               make_cache: bool = False, constrain=None
               ) -> Tuple[jax.Array, Cache]:
    B, S, d = x.shape
    dh, H, Hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    q = (x @ p["wq"]).reshape(B, S, H, dh)
    k = (x @ p["wk"]).reshape(B, S, Hkv, dh)
    v = (x @ p["wv"]).reshape(B, S, Hkv, dh)
    q = rope_apply(q, angles)
    k = rope_apply(k, angles)
    if constrain is not None:
        q, k, v = constrain.heads(q), constrain.heads(k), constrain.heads(v)

    new_cache: Cache = None
    if cache is not None and cache_index is not None:
        # decode: write new k/v at cache_index, attend over the cache
        kc = jax.lax.dynamic_update_slice(cache["k"], k, (0, cache_index, 0, 0))
        vc = jax.lax.dynamic_update_slice(cache["v"], v, (0, cache_index, 0, 0))
        new_cache = {"k": kc, "v": vc}
        valid = jnp.full((B,), cache_index + S, jnp.int32)
        offs = jnp.full((B,), cache_index, jnp.int32)
        o = ops.decode_attention(q, kc, vc, causal=cfg.causal,
                                 window=window, kv_valid_len=valid,
                                 q_offset=offs)
    else:
        o = ops.flash_attention(q, k, v, causal=cfg.causal, window=window,
                                impl=cfg.kernel_impl, constrain=constrain)
        if make_cache:
            new_cache = {"k": k, "v": v}
    y = o.reshape(B, S, H * dh) @ p["wo"]
    return y, new_cache


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GeLU)
# ---------------------------------------------------------------------------
def mlp_init(key, cfg: ModelConfig) -> Params:
    d, ff = cfg.d_model, cfg.d_ff
    dt = cfg.activation_dtype
    ks = jax.random.split(key, 3)
    if cfg.act == "swiglu":
        return {"wg": _dense_init(ks[0], (d, ff), dt),
                "wu": _dense_init(ks[1], (d, ff), dt),
                "wd": _dense_init(ks[2], (ff, d), dt)}
    return {"wu": _dense_init(ks[0], (d, ff), dt),
            "wd": _dense_init(ks[1], (ff, d), dt)}


def mlp_apply(p: Params, x: jax.Array, cfg: ModelConfig, *,
              constrain=None) -> jax.Array:
    if "wg" in p:
        h = jax.nn.silu(x @ p["wg"]) * (x @ p["wu"])
    else:
        h = jax.nn.gelu(x @ p["wu"])
    if constrain is not None:
        h = constrain.ffn(h)
    return h @ p["wd"]


# ---------------------------------------------------------------------------
# MoE (top-k routing, capacity-based dispatch, expert-parallel friendly)
# ---------------------------------------------------------------------------
def moe_init(key, cfg: ModelConfig) -> Params:
    d, ff, E = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    dt = cfg.activation_dtype
    ks = jax.random.split(key, 4)
    return {
        "router": _dense_init(ks[0], (d, E), jnp.float32),
        "wg": _dense_init(ks[1], (E, d, ff), dt, fan_in=d),
        "wu": _dense_init(ks[2], (E, d, ff), dt, fan_in=d),
        "wd": _dense_init(ks[3], (E, ff, d), dt, fan_in=ff),
    }


def moe_apply(p: Params, x: jax.Array, cfg: ModelConfig, *,
              constrain=None) -> Tuple[jax.Array, jax.Array]:
    """Returns (y, aux_loss).  Grouped sort-based dispatch into a
    fixed-capacity expert layout.

    Sharding-aware formulation (GShard-style local groups, adapted to the
    sort-based megablocks dispatch): tokens are split into G = batch
    dispatch groups (one per sequence), each group routes and sorts ONLY
    its own tokens — so the sort/scatter stay local to the data shard and
    the only cross-shard movement is the (G-sharded → E-sharded)
    redistribution of the dense (G, E, C, d) expert buffers, which GSPMD
    lowers to the MoE all-to-all.  A global-token sort would be
    unpartitionable (verified: it replicates the dispatch buffers and
    blows temp memory three orders of magnitude past HBM).

    The MXU-dense (E, C, d) capacity tiles are the TPU adaptation of
    megablocks' ragged CSR tiles; overflow beyond an expert's per-group
    capacity is dropped (Switch-style).  Cost: O(T·k·d·ff) expert compute,
    O(T·k log) local sorts, no one-hot dispatch einsum.
    """
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    G = B                       # one dispatch group per sequence
    Tg = S                      # tokens per group
    Tk = Tg * k                 # routed rows per group

    xt = x.reshape(G, Tg, d)
    logits = jnp.einsum("gtd,de->gte", xt.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, eidx = jax.lax.top_k(probs, k)                  # (G, Tg, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)

    # load-balancing auxiliary loss (Switch/olmoe style), global over tokens
    me = jnp.mean(probs, axis=(0, 1))                          # (E,)
    ce = jnp.mean(jax.nn.one_hot(eidx[..., 0], E, dtype=jnp.float32),
                  axis=(0, 1))
    aux = E * jnp.sum(me * ce)

    cap = int(Tk / E * cfg.capacity_factor)
    cap = max(8, -(-cap // 8) * 8)
    cap = min(cap, Tk)

    # --- gather-only dispatch (no scatters: XLA scatters materialise
    # row×d index tensors and partition poorly; every step below is a sort
    # or a take_along_axis whose index arrays have no feature dim) --------
    flat_e = eidx.reshape(G, Tk)                               # (G, Tk)
    order = jnp.argsort(flat_e, axis=-1)                       # per-group
    sorted_e = jnp.take_along_axis(flat_e, order, axis=-1)
    tok_of = order // k                                        # (G, Tk)
    # group-local histogram via binary search on the sorted ids (gather-
    # friendly; a one-hot here would be (G,Tg,k,E) ≈ TBs for 64 experts)
    starts = jax.vmap(
        lambda se: jnp.searchsorted(se, jnp.arange(E, dtype=se.dtype),
                                    side="left"))(sorted_e)    # (G, E)
    counts = jnp.diff(
        jnp.concatenate([starts, jnp.full((G, 1), Tk, starts.dtype)],
                        axis=-1), axis=-1)                     # (G, E)

    # expert e's capacity slot c holds sorted row starts[e] + c (if valid)
    slot_rows = starts[:, :, None] + jnp.arange(cap)[None, None]  # (G,E,cap)
    slot_valid = jnp.arange(cap)[None, None] < counts[:, :, None]
    slot_rows = jnp.clip(slot_rows, 0, Tk - 1).reshape(G, E * cap)
    src_tok = jnp.take_along_axis(tok_of, slot_rows, axis=-1)  # (G, E*cap)
    xe = jnp.take_along_axis(xt, src_tok[..., None], axis=1)   # (G,E*cap,d)
    xe = xe * slot_valid.reshape(G, E * cap, 1).astype(x.dtype)
    xe = xe.reshape(G, E, cap, d)
    if constrain is not None:
        xe = constrain.experts(xe)                             # G:dp, E:ep
    wg, wu, wd = p["wg"], p["wu"], p["wd"]
    if constrain is not None:
        # Gather FSDP-sharded expert weights before use: contracting over
        # an FSDP-sharded d/ff dim would otherwise emit partial-sum
        # all-reduces of the (G,E,C,ff) activations — orders of magnitude
        # more wire bytes than re-gathering the weights.
        wg, wu, wd = (constrain.expert_weights(w) for w in (wg, wu, wd))
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xe, wg)) \
        * jnp.einsum("gecd,edf->gecf", xe, wu)
    ye = jnp.einsum("gecf,efd->gecd", h, wd)                   # (G,E,cap,d)
    if constrain is not None:
        ye = constrain.experts(ye)

    # return path, also gather-only: row r (sorted) sits in buffer slot
    # sorted_e[r]*cap + (r - starts[sorted_e[r]]) when within capacity;
    # token t collects its k rows through the inverse permutation.
    slot_of_row = jnp.arange(Tk)[None] - \
        jnp.take_along_axis(starts, sorted_e, axis=-1)         # (G, Tk)
    keep = slot_of_row < cap
    buf_pos = jnp.clip(sorted_e * cap + slot_of_row, 0, E * cap - 1)
    inv = jnp.argsort(order, axis=-1)                          # (G, Tk)
    ye_flat = ye.reshape(G, E * cap, d)
    row_pos = jnp.take_along_axis(buf_pos, inv, axis=-1)       # by (t, j)
    row_keep = jnp.take_along_axis(keep, inv, axis=-1)
    contrib = jnp.take_along_axis(ye_flat, row_pos[..., None], axis=1)
    w = (gate_vals.reshape(G, Tk)
         * row_keep.astype(jnp.float32)).astype(x.dtype)
    y = jnp.sum((contrib * w[..., None]).reshape(G, Tg, k, d), axis=2)
    return y.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# Mamba2 block (SSD)
# ---------------------------------------------------------------------------
def mamba2_init(key, cfg: ModelConfig) -> Params:
    d, din, n, H = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.n_ssm_heads
    dt = cfg.activation_dtype
    ks = jax.random.split(key, 8)
    conv_ch = din + 2 * n
    return {
        "wz": _dense_init(ks[0], (d, din), dt),
        "wx": _dense_init(ks[1], (d, din), dt),
        "wB": _dense_init(ks[2], (d, n), dt),
        "wC": _dense_init(ks[3], (d, n), dt),
        "wdt": _dense_init(ks[4], (d, H), dt),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "A_log": jnp.zeros((H,), jnp.float32),      # A = -exp(A_log) = -1
        "D": jnp.ones((H,), jnp.float32),
        "conv_w": (jax.random.normal(ks[5], (cfg.ssm_conv, conv_ch))
                   * 0.1).astype(dt),
        "conv_b": jnp.zeros((conv_ch,), jnp.float32),
        "gn": jnp.ones((din,), jnp.float32),        # gated RMSNorm scale
        "out": _dense_init(ks[6], (din, d), dt),
    }


def mamba2_cache_init(cfg: ModelConfig, batch: int) -> Params:
    din, n, H = cfg.d_inner, cfg.ssm_state, cfg.n_ssm_heads
    P = din // H
    return {"state": jnp.zeros((batch, H, P, n), jnp.float32),
            "conv": jnp.zeros((batch, cfg.ssm_conv - 1, din + 2 * n),
                              cfg.activation_dtype)}


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 cache: Optional[jax.Array]
                 ) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal conv.  x: (B,S,C); w: (K,C).  Returns (y, new_cache)."""
    K = w.shape[0]
    if cache is None:
        xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([cache.astype(x.dtype), x], axis=1)
    y = sum(xp[:, i:i + x.shape[1], :] * w[i] for i in range(K))
    new_cache = xp[:, -(K - 1):, :] if K > 1 else None
    return (y + b.astype(x.dtype)), new_cache


def mamba2_apply(p: Params, x: jax.Array, cfg: ModelConfig, *,
                 cache: Cache = None, make_cache: bool = False,
                 constrain=None) -> Tuple[jax.Array, Cache]:
    B, S, d = x.shape
    din, n, H = cfg.d_inner, cfg.ssm_state, cfg.n_ssm_heads
    P = din // H
    z = x @ p["wz"]
    xin = x @ p["wx"]
    Bp = x @ p["wB"]
    Cp = x @ p["wC"]
    dt = jax.nn.softplus((x @ p["wdt"]).astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])

    xbc = jnp.concatenate([xin, Bp, Cp], axis=-1)
    conv_cache = cache.get("conv") if cache else None
    xbc, new_conv = _causal_conv(xbc, p["conv_w"], p["conv_b"], conv_cache)
    xbc = jax.nn.silu(xbc)
    xin, Bp, Cp = jnp.split(xbc, [din, din + n], axis=-1)

    xh = xin.reshape(B, S, H, P)
    if constrain is not None:
        xh = constrain.ssm_heads(xh)
    if cache is not None and S == 1:
        state, y = ops.ssd_decode_step(
            cache["state"], xh[:, 0], dt[:, 0], A, Bp[:, 0], Cp[:, 0])
        y = y[:, None].astype(x.dtype)
    else:
        init = cache["state"] if cache else None
        y, state = ops.mamba2_ssd(xh, dt, A, Bp, Cp,
                                  chunk=cfg.ssm_chunk, init_state=init,
                                  impl=cfg.kernel_impl)
    y = y + xh * p["D"][None, None, :, None].astype(x.dtype)
    y = y.reshape(B, S, din)
    # gated RMSNorm (Mamba2): norm(y * silu(z))
    g = (y * jax.nn.silu(z)).astype(jnp.float32)
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                          + cfg.norm_eps) * p["gn"]
    out = g.astype(x.dtype) @ p["out"]

    new_cache: Cache = None
    if make_cache or cache is not None:
        new_cache = {"state": state, "conv": new_conv}
    return out, new_cache


# ---------------------------------------------------------------------------
# xLSTM: mLSTM block (matrix memory, chunkwise parallel)
# ---------------------------------------------------------------------------
def mlstm_init(key, cfg: ModelConfig) -> Params:
    d = cfg.d_model
    din = int(cfg.mlstm_proj_factor * d)
    H = cfg.n_heads
    dt = cfg.activation_dtype
    ks = jax.random.split(key, 8)
    return {
        "wup": _dense_init(ks[0], (d, 2 * din), dt),
        "wq": _dense_init(ks[1], (din, din), dt),
        "wk": _dense_init(ks[2], (din, din), dt),
        "wv": _dense_init(ks[3], (din, din), dt),
        "wi": _dense_init(ks[4], (din, H), jnp.float32),
        "bi": jnp.zeros((H,), jnp.float32),
        "wf": _dense_init(ks[5], (din, H), jnp.float32),
        "bf": jnp.full((H,), 3.0, jnp.float32),   # open forget gates at init
        "gn": jnp.ones((din,), jnp.float32),
        "wd": _dense_init(ks[6], (din, d), dt),
    }


def mlstm_cache_init(cfg: ModelConfig, batch: int) -> Params:
    din = int(cfg.mlstm_proj_factor * cfg.d_model)
    H = cfg.n_heads
    dh = din // H
    return {"C": jnp.zeros((batch, H, dh, dh), jnp.float32),
            "n": jnp.zeros((batch, H, dh), jnp.float32),
            "m": jnp.full((batch, H), -1e30, jnp.float32)}


def mlstm_apply(p: Params, x: jax.Array, cfg: ModelConfig, *,
                cache: Cache = None, make_cache: bool = False,
                constrain=None) -> Tuple[jax.Array, Cache]:
    B, S, d = x.shape
    din = int(cfg.mlstm_proj_factor * d)
    H = cfg.n_heads
    dh = din // H
    up = x @ p["wup"]
    z, xi = jnp.split(up, 2, axis=-1)
    q = (xi @ p["wq"]).reshape(B, S, H, dh) * (dh ** -0.5)
    k = (xi @ p["wk"]).reshape(B, S, H, dh)
    v = (xi @ p["wv"]).reshape(B, S, H, dh)
    ig = xi.astype(jnp.float32) @ p["wi"] + p["bi"]            # (B,S,H)
    fg = xi.astype(jnp.float32) @ p["wf"] + p["bf"]

    init = None
    if cache is not None:
        init = (cache["C"], cache["n"],
                jnp.where(cache["m"] <= -1e29, -jnp.inf, cache["m"]))
    if cache is not None and S == 1:
        state, y = ops.mlstm_decode_step(init, q[:, 0], k[:, 0], v[:, 0],
                                         ig[:, 0], fg[:, 0])
        y = y[:, None].astype(x.dtype)
    else:
        chunk = min(cfg.ssm_chunk, S)
        y, state = ops.mlstm_chunked(q, k, v, ig, fg, chunk=chunk, init=init,
                                     impl=cfg.kernel_impl)
    y = y.reshape(B, S, din).astype(jnp.float32)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                          + cfg.norm_eps) * p["gn"]
    out = (y.astype(x.dtype) * jax.nn.silu(z)) @ p["wd"]

    new_cache: Cache = None
    if make_cache or cache is not None:
        C, n, m = state
        new_cache = {"C": C, "n": n,
                     "m": jnp.where(jnp.isfinite(m), m, -1e30)}
    return out, new_cache


# ---------------------------------------------------------------------------
# xLSTM: sLSTM block (scalar memory, sequential recurrence)
# ---------------------------------------------------------------------------
def slstm_init(key, cfg: ModelConfig) -> Params:
    d = cfg.d_model
    H = cfg.n_heads
    dh = d // H
    dt = cfg.activation_dtype
    ks = jax.random.split(key, 11)
    ffd = int(cfg.slstm_proj_factor * d)
    ffd = -(-ffd // 64) * 64
    p = {"gn": jnp.ones((d,), jnp.float32),
         "wu": _dense_init(ks[8], (d, ffd), dt),
         "wd2": _dense_init(ks[9], (ffd, d), dt)}
    for i, g in enumerate(("i", "f", "z", "o")):
        p[f"w{g}"] = _dense_init(ks[i], (d, d), jnp.float32)
        p[f"r{g}"] = (_dense_init(ks[4 + i], (H, dh, dh), jnp.float32, dh))
        p[f"b{g}"] = (jnp.full((d,), 1.0, jnp.float32) if g == "f"
                      else jnp.zeros((d,), jnp.float32))
    return p


def slstm_cache_init(cfg: ModelConfig, batch: int) -> Params:
    d = cfg.d_model
    return {"c": jnp.zeros((batch, d), jnp.float32),
            "n": jnp.ones((batch, d), jnp.float32),
            "h": jnp.zeros((batch, d), jnp.float32),
            "m": jnp.zeros((batch, d), jnp.float32)}


def _slstm_step(p, cfg, state, xt):
    """One sLSTM step.  xt: (B, d) fp32 pre-projected gate inputs."""
    c, n, h, m = state
    B = h.shape[0]
    H = cfg.n_heads
    dh = cfg.d_model // H
    hh = h.reshape(B, H, dh)

    def rec(g):
        return jnp.einsum("bhd,hde->bhe", hh, p[f"r{g}"]).reshape(B, -1)

    it = xt @ p["wi"] + p["bi"] + rec("i")
    ft = xt @ p["wf"] + p["bf"] + rec("f")
    zt = jnp.tanh(xt @ p["wz"] + p["bz"] + rec("z"))
    ot = jax.nn.sigmoid(xt @ p["wo"] + p["bo"] + rec("o"))
    logf = jax.nn.log_sigmoid(ft)
    m_new = jnp.maximum(logf + m, it)
    i_s = jnp.exp(it - m_new)
    f_s = jnp.exp(logf + m - m_new)
    c = f_s * c + i_s * zt
    n = f_s * n + i_s
    h = ot * (c / jnp.maximum(n, 1e-6))
    return (c, n, h, m_new)


def slstm_apply(p: Params, x: jax.Array, cfg: ModelConfig, *,
                cache: Cache = None, make_cache: bool = False,
                constrain=None) -> Tuple[jax.Array, Cache]:
    B, S, d = x.shape
    xf = x.astype(jnp.float32)
    if cache is not None:
        state = (cache["c"], cache["n"], cache["h"], cache["m"])
    else:
        state = (jnp.zeros((B, d), jnp.float32),
                 jnp.ones((B, d), jnp.float32),
                 jnp.zeros((B, d), jnp.float32),
                 jnp.zeros((B, d), jnp.float32))

    def step(carry, xt):
        new = _slstm_step(p, cfg, carry, xt)
        return new, new[2]  # h

    state, hs = jax.lax.scan(step, state, jnp.moveaxis(xf, 1, 0))
    y = jnp.moveaxis(hs, 0, 1)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                          + cfg.norm_eps) * p["gn"]
    y = y.astype(x.dtype)
    y = jax.nn.gelu(y @ p["wu"]) @ p["wd2"]
    new_cache: Cache = None
    if make_cache or cache is not None:
        new_cache = {"c": state[0], "n": state[1], "h": state[2],
                     "m": state[3]}
    return y, new_cache


# ---------------------------------------------------------------------------
# Zamba2 shared transformer block (two weight sets reused across depth)
# ---------------------------------------------------------------------------
def shared_block_init(key, cfg: ModelConfig) -> Params:
    """One shared block: attention over the 2d concatenation of the
    layer's input and the embeddings, then a gated MLP."""
    d, ff = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    dt = cfg.activation_dtype
    return {
        "norm": norm_init(cfg, 2 * d),
        "attn": attn_init(ks[0], cfg, d_in=2 * d),
        "norm2": norm_init(cfg),
        "mlp": {"gate_up": _dense_init(ks[1], (d, 2 * ff), dt),
                "down": _dense_init(ks[2], (ff, d), dt)},
    }


def shared_adapter_init(key, cfg: ModelConfig) -> Params:
    """What one invocation of a shared block owns: the rank-r LoRA on the
    MLP's gate/up projection and the linear that follows the block."""
    d, ff, r = cfg.d_model, cfg.d_ff, cfg.adapter_rank
    ks = jax.random.split(key, 3)
    dt = cfg.activation_dtype
    return {"lora_a": _dense_init(ks[0], (d, r), dt),
            "lora_b": _dense_init(ks[1], (r, 2 * ff), dt),
            "linear": _dense_init(ks[2], (d, d), dt)}


def shared_block_apply(p: Params, a: Params, x: jax.Array, x0: jax.Array,
                       cfg: ModelConfig, *, kv: Cache = None, slot=None,
                       cache_index=None, constrain=None
                       ) -> Tuple[jax.Array, Cache]:
    """One invocation of a Zamba2 shared block, then its linear.

    ``h = rmsnorm(concat(x, x0))``; causal attention from 2d to
    ``n_heads`` heads of ``head_dim``, scores scaled by
    ``(head_dim / 2)^-1/2``, no rotary embedding; ``h = rmsnorm(h W_o)``;
    ``[g, u] = h W_gate_up + h A B`` (the invocation's LoRA);
    ``gelu(g) * u`` (exact GELU) through ``W_down``; the invocation's
    linear last.  The block has no residual of its own: its output is
    added to the input of the Mamba2 layer that follows.

    ``kv`` is the KV cache of every invocation, (invocations, B, T, Hkv,
    dh), and ``slot`` this invocation's index in it: in decode
    (``cache_index`` given) the new k/v are written at ``cache_index`` and
    the block attends over its slot; in prefill the sequence's k/v fill
    the slot.  Returns the output and the updated ``kv``.
    """
    B, S, _ = x.shape
    dh, H, Hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    scale = (dh / 2) ** -0.5
    h = norm_apply(p["norm"], jnp.concatenate([x, x0], axis=-1), cfg)
    w = p["attn"]
    q = (h @ w["wq"]).reshape(B, S, H, dh)
    k = (h @ w["wk"]).reshape(B, S, Hkv, dh)
    v = (h @ w["wv"]).reshape(B, S, Hkv, dh)
    if constrain is not None:
        q, k, v = constrain.heads(q), constrain.heads(k), constrain.heads(v)
    if cache_index is not None:
        at = (slot, 0, cache_index, 0, 0)
        kv = {"k": jax.lax.dynamic_update_slice(kv["k"], k[None], at),
              "v": jax.lax.dynamic_update_slice(kv["v"], v[None], at)}
        kc, vc = (jax.lax.dynamic_index_in_dim(kv[n], slot, 0, False)
                  for n in ("k", "v"))
        o = ops.decode_attention(q, kc, vc, causal=True, scale=scale,
                                 kv_valid_len=jnp.full((B,), cache_index + S,
                                                       jnp.int32),
                                 q_offset=jnp.full((B,), cache_index,
                                                   jnp.int32))
    else:
        o = ops.flash_attention(q, k, v, causal=True, scale=scale,
                                impl=cfg.kernel_impl, constrain=constrain)
        if kv is not None:
            at = (slot, 0, 0, 0, 0)
            kv = {"k": jax.lax.dynamic_update_slice(kv["k"], k[None], at),
                  "v": jax.lax.dynamic_update_slice(kv["v"], v[None], at)}
    h = norm_apply(p["norm2"], o.reshape(B, S, H * dh) @ w["wo"], cfg)
    gu = h @ p["mlp"]["gate_up"] + (h @ a["lora_a"]) @ a["lora_b"]
    g, u = jnp.split(gu, 2, axis=-1)
    m = jax.nn.gelu(g, approximate=False) * u
    if constrain is not None:
        m = constrain.ffn(m)
    return (m @ p["mlp"]["down"]) @ a["linear"], kv
