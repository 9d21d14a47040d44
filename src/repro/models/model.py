"""Composable block-pattern language model (decoder & encoder).

A model is ``cfg.pattern`` (a repeating unit of layers, each a tuple of
blocks) scanned ``cfg.repeats`` times with stacked parameters
(``jax.lax.scan`` over the leading layer dimension keeps HLO size — and
hence multi-pod compile time — independent of depth).  Heterogeneous
patterns (xLSTM's mLSTM/sLSTM interleave) are expressed inside the unit.
Zamba2's schedule is not periodic (shared-block invocations at layers 6,
12, ..., 42, 47, 51): its Mamba2 layers are one scan, and a ``lax.cond``
in the scan body runs a shared block's invocation at the layers of
``cfg.hybrid_layers`` (:func:`_hybrid_stack`).  Every cache leaf keeps a
leading layer axis: the scanned layers', or the invocations' for the
shared blocks' KV caches.

Three entry modes:
* ``train``   — full sequence, logits for every position.
* ``prefill`` — full sequence, returns the serving cache.
* ``decode``  — one token against a fixed-size cache at ``cache_index``.

Decode never rewrites a cache inside the layer stack: the stack reads every
KV cache where it lies and returns each attention layer's new entries
(:func:`decode_entries`), and one write puts them in place
(:func:`write_entries`).  Recurrent state comes back whole.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import layers as L
from .config import (ModelConfig, ATTN, SWA, MLP, MOE, MAMBA2, SLSTM,
                     MLSTM)

# The cache entry of the shared blocks' KV, stacked over invocations.
SHARED_KV = "shared_attn"


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _block_init(key, kind: str, cfg: ModelConfig):
    if kind in (ATTN, SWA):
        return L.attn_init(key, cfg)
    if kind == MLP:
        return L.mlp_init(key, cfg)
    if kind == MOE:
        return L.moe_init(key, cfg)
    if kind == MAMBA2:
        return L.mamba2_init(key, cfg)
    if kind == SLSTM:
        return L.slstm_init(key, cfg)
    if kind == MLSTM:
        return L.mlstm_init(key, cfg)
    raise ValueError(kind)


def _unit_init(key, cfg: ModelConfig):
    p: Dict[str, Any] = {}
    i = 0
    for li, layer in enumerate(cfg.pattern):
        for bi, kind in enumerate(layer):
            k1, k2, key = jax.random.split(jax.random.fold_in(key, i), 3)
            name = f"L{li}_{bi}_{kind}"
            p[name] = _block_init(k1, kind, cfg)
            p[f"L{li}_{bi}_norm"] = L.norm_init(cfg)
            i += 1
    return p


def init(cfg: ModelConfig, key: jax.Array) -> Dict[str, Any]:
    ks = jax.random.split(key, 5)
    params: Dict[str, Any] = {}
    if cfg.frontend != "audio":
        params["embed"] = (jax.random.normal(ks[0], (cfg.vocab, cfg.d_model))
                           * 0.02).astype(cfg.activation_dtype)
    unit_keys = jax.random.split(ks[1], cfg.repeats)
    params["layers"] = jax.vmap(lambda k: _unit_init(k, cfg))(unit_keys)
    if cfg.hybrid_layers:
        kb, ka = jax.random.split(ks[2])
        params["shared"] = {
            "blocks": jax.vmap(lambda k: L.shared_block_init(k, cfg))(
                jax.random.split(kb, cfg.shared_blocks)),
            "adapters": jax.vmap(lambda k: L.shared_adapter_init(k, cfg))(
                jax.random.split(ka, len(cfg.hybrid_layers)))}
    params["final_norm"] = L.norm_init(cfg)
    if not cfg.tie_embeddings or cfg.frontend == "audio":
        params["lm_head"] = L._dense_init(ks[3], (cfg.d_model, cfg.vocab),
                                          cfg.activation_dtype)
    return params


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------
def _block_cache(kind: str, cfg: ModelConfig, batch: int, cache_len: int):
    if kind in (ATTN, SWA):
        return L.attn_cache_init(cfg, batch, cache_len)
    if kind == MAMBA2:
        return L.mamba2_cache_init(cfg, batch)
    if kind == SLSTM:
        return L.slstm_cache_init(cfg, batch)
    if kind == MLSTM:
        return L.mlstm_cache_init(cfg, batch)
    return None


def init_cache(cfg: ModelConfig, batch: int, cache_len: int):
    """Decode cache template, stacked over the unit-repeat dimension (and
    the shared blocks' KV over their invocations)."""
    unit = {}
    for li, layer in enumerate(cfg.pattern):
        for bi, kind in enumerate(layer):
            c = _block_cache(kind, cfg, batch, cache_len)
            if c is not None:
                unit[f"L{li}_{bi}_{kind}"] = c
    cache = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (cfg.repeats,) + x.shape), unit)
    if cfg.hybrid_layers:
        kv = L.attn_cache_init(cfg, batch, cache_len)
        cache[SHARED_KV] = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(
                x[None], (len(cfg.hybrid_layers),) + x.shape), kv)
    return cache


# ---------------------------------------------------------------------------
# apply
# ---------------------------------------------------------------------------
class _NoConstrain:
    """Default no-op sharding-constraint hooks (runtime installs real ones)."""

    def __getattr__(self, name):
        return lambda x: x


def _unit_apply(x, unit_p, unit_cache, *, cfg: ModelConfig,
                angles, mode: str, cache_index, constrain):
    make_cache = mode == "prefill"
    decoding = mode == "decode"
    new_cache: Dict[str, Any] = {}
    aux = jnp.zeros((), jnp.float32)
    for li, layer in enumerate(cfg.pattern):
        for bi, kind in enumerate(layer):
            name = f"L{li}_{bi}_{kind}"
            c = unit_cache.get(name) if unit_cache else None
            xin = L.norm_apply(unit_p[f"L{li}_{bi}_norm"], x, cfg)
            nc = None
            if kind in (ATTN, SWA):
                win = cfg.sliding_window if kind == SWA else None
                h, nc = L.attn_apply(unit_p[name], xin, cfg,
                                     angles=angles, window=win, cache=c,
                                     cache_index=cache_index,
                                     make_cache=make_cache,
                                     constrain=constrain)
            elif kind == MLP:
                h = L.mlp_apply(unit_p[name], xin, cfg,
                                constrain=constrain)
            elif kind == MOE:
                h, a = L.moe_apply(unit_p[name], xin, cfg,
                                   constrain=constrain)
                aux = aux + a
            elif kind == MAMBA2:
                h, nc = L.mamba2_apply(unit_p[name], xin, cfg, cache=c,
                                       make_cache=make_cache,
                                       constrain=constrain)
            elif kind == SLSTM:
                h, nc = L.slstm_apply(unit_p[name], xin, cfg, cache=c,
                                      make_cache=make_cache,
                                      constrain=constrain)
            elif kind == MLSTM:
                h, nc = L.mlstm_apply(unit_p[name], xin, cfg, cache=c,
                                      make_cache=make_cache,
                                      constrain=constrain)
            else:
                raise ValueError(kind)
            x = constrain.residual(x + h)
            if nc is not None and (make_cache or decoding):
                new_cache[name] = nc
    return x, new_cache, aux


def _remat(body, remat: Optional[str]):
    if remat == "full":
        return jax.checkpoint(body, prevent_cse=False)
    if remat == "dots":
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            prevent_cse=False)
    return body


def _hybrid_stack(params, x, cfg: ModelConfig, *, mode: str, cache,
                  cache_index, constrain, remat):
    """Zamba2's layer stack.  Returns (x, new_cache).

    One scan over the Mamba2 layers.  At layer ``cfg.hybrid_layers[i]`` a
    ``lax.cond`` first runs invocation ``i``: shared block
    ``i % cfg.shared_blocks`` with invocation ``i``'s own LoRA and linear,
    over the layer's input and the embeddings.  Its output is added to
    the Mamba2 layer's input, before the layer's norm, and not to the
    residual stream:

        t = shared(x, x0) if hybrid else 0
        x = x + mamba2(rmsnorm(x + t))

    The invocations' KV caches are one leaf stacked over invocations
    (``SHARED_KV``).  Prefill fills them in the scan's carry.  Decode
    reads them where they lie: every layer yields its invocation's new
    entries (zeros where it has none), and the invocations' layers are
    gathered after the scan.
    """
    (kind,), = cfg.pattern
    if kind != MAMBA2:
        raise ValueError(f"a hybrid schedule runs over Mamba2 layers, "
                         f"not {cfg.pattern}")
    name, norm = f"L0_0_{MAMBA2}", "L0_0_norm"
    invocation = np.full(cfg.n_layers, -1, np.int32)
    invocation[list(cfg.hybrid_layers)] = np.arange(len(cfg.hybrid_layers))
    shared, x0 = params["shared"], x
    decoding = mode == "decode"
    B, S = x.shape[:2]
    kv = None
    if decoding:
        kv = cache[SHARED_KV]
    elif mode == "prefill":
        kv = jax.tree_util.tree_map(
            lambda a: jnp.zeros((len(cfg.hybrid_layers),) + a.shape,
                                a.dtype),
            jax.eval_shape(lambda: L.attn_cache_init(cfg, B, S)))

    def invoke(i, xc, kv):
        with jax.named_scope("zamba2/shared_block"):
            block = jax.tree_util.tree_map(
                lambda w: w[i % cfg.shared_blocks], shared["blocks"])
            adapter = jax.tree_util.tree_map(lambda w: w[i],
                                             shared["adapters"])
            return L.shared_block_apply(
                block, adapter, xc, x0, cfg, kv=kv, slot=i,
                cache_index=cache_index, constrain=constrain)

    def skip(i, xc, kv):
        if decoding:  # no invocation here: no new entries
            kv = L.attn_cache_init(cfg, B, S)
        return jnp.zeros_like(xc), kv

    def body(carry, xs):
        xc, kv_carry = carry
        layer_p, layer_cache, i = xs
        t, kv_out = jax.lax.cond(i >= 0, invoke, skip, i, xc,
                                 kv if decoding else kv_carry)
        with jax.named_scope("zamba2/mamba2"):
            h, nc = L.mamba2_apply(
                layer_p[name], L.norm_apply(layer_p[norm], xc + t, cfg), cfg,
                cache=layer_cache.get(name), make_cache=mode == "prefill",
                constrain=constrain)
        out = {name: nc} if mode != "train" else {}
        if decoding:
            out[SHARED_KV], kv_out = kv_out, None
        return (constrain.residual(xc + h), kv_out), out

    layer_cache = {name: cache[name]} if decoding else {}
    (x, kv_carry), new_cache = jax.lax.scan(
        _remat(body, remat), (x, None if decoding else kv),
        (params["layers"], layer_cache, jnp.asarray(invocation)))
    if decoding:
        at = np.asarray(cfg.hybrid_layers)
        new_cache[SHARED_KV] = jax.tree_util.tree_map(
            lambda e: e[at], new_cache[SHARED_KV])
    elif mode == "prefill":
        new_cache[SHARED_KV] = kv_carry
    return x, new_cache


def apply(params: Dict[str, Any], cfg: ModelConfig, batch: Dict[str, Any], *,
          mode: str = "train", cache=None, cache_index=None,
          constrain=None, remat: Optional[str] = None
          ) -> Tuple[jax.Array, Any, jax.Array]:
    """Forward pass.  Returns (logits, new_cache, aux_loss).

    Decode is :func:`decode_entries`, then :func:`write_entries` at
    ``cache_index``.
    """
    logits, new_cache, aux = _forward(
        params, cfg, batch, mode=mode, cache=cache, cache_index=cache_index,
        constrain=constrain, remat=remat)
    if mode == "decode":
        new_cache = write_entries(cache, new_cache, cache_index)
    return logits, new_cache, aux


def decode_entries(params: Dict[str, Any], cfg: ModelConfig,
                   batch: Dict[str, Any], *, cache, cache_index,
                   constrain=None) -> Tuple[jax.Array, Any]:
    """One decode step that leaves ``cache`` as it is.  Returns (logits,
    entries): ``entries`` has the cache's tree; a KV leaf holds the step's
    new entries, (layers, B, S, Hkv, dh), for positions ``cache_index``
    onwards, and a recurrent leaf the whole new state."""
    logits, entries, _ = _forward(params, cfg, batch, mode="decode",
                                  cache=cache, cache_index=cache_index,
                                  constrain=constrain)
    return logits, entries


def write_entries(cache, entries, cache_index):
    """The cache after a decode step: each KV leaf with its new entries
    written in place at ``cache_index``, each recurrent leaf replaced."""
    def write(path, c, e):
        if is_kv(path):  # (layers, B, T, Hkv, dh)
            return jax.lax.dynamic_update_slice_in_dim(c, e, cache_index, 2)
        return e
    return jax.tree_util.tree_map_with_path(write, cache, entries)


def _forward(params, cfg: ModelConfig, batch, *, mode: str, cache,
             cache_index, constrain, remat: Optional[str] = None):
    """The layer stack and the head.  Returns (logits, new_cache, aux);
    in decode ``new_cache`` is the step's entries (:func:`decode_entries`).
    """
    constrain = constrain or _NoConstrain()

    if cfg.frontend == "audio":
        x = batch["embeds"].astype(cfg.activation_dtype)
        B, S = x.shape[:2]
    else:
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = jnp.take(params["embed"], tokens, axis=0)
        if cfg.frontend == "vlm" and mode != "decode" \
                and "patch_embeds" in batch:
            pe = batch["patch_embeds"].astype(x.dtype)
            x = jax.lax.dynamic_update_slice(x, pe, (0, 0, 0))
    x = constrain.residual(x)

    if mode == "decode":
        positions = jnp.full((B, S), 0, jnp.int32) + cache_index
    else:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                     (B, S))
    if cfg.hybrid_layers:
        x, new_cache = _hybrid_stack(params, x, cfg, mode=mode, cache=cache,
                                     cache_index=cache_index,
                                     constrain=constrain, remat=remat)
        aux = jnp.zeros((), jnp.float32)
    else:
        angles = L.rope_angles(positions, cfg.head_dim, cfg.rope_theta)
        unit_fn = functools.partial(
            _unit_apply, cfg=cfg, angles=angles, mode=mode,
            cache_index=cache_index, constrain=constrain)

        def scan_body(carry, xs):
            xc, aux_acc = carry
            unit_p, unit_cache = xs
            xc, new_cache, aux = unit_fn(xc, unit_p, unit_cache)
            return (xc, aux_acc + aux), new_cache

        (x, aux), new_cache = jax.lax.scan(
            _remat(scan_body, remat), (x, jnp.zeros((), jnp.float32)),
            (params["layers"], cache if cache is not None else {}))

    x = L.norm_apply(params["final_norm"], x, cfg)
    if "lm_head" in params:
        logits = x @ params["lm_head"]
    else:
        logits = x @ params["embed"].T
    if cfg.logit_softcap > 0:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    logits = constrain.logits(logits)
    return logits, (new_cache if (mode != "train") else None), aux


def is_kv(path) -> bool:
    """Whether the cache leaf at ``path`` is a KV cache, (layers, B, T,
    Hkv, dh), whose size grows with the cache length; every other leaf is
    recurrent state (SSM state, conv tails, xLSTM memory) of a fixed
    size."""
    keys = "/".join(str(p) for p in path)
    return "_attn" in keys or "_swa" in keys


def pad_cache(cfg: ModelConfig, cache, target_len: int):
    """Grow a prefill cache to a fixed decode size (KV time axis padding).

    SSM/xLSTM state caches are size-independent and pass through.
    """
    def pad(path, x):
        if is_kv(path):  # (layers, B, T, Hkv, dh)
            padn = target_len - x.shape[2]
            if padn > 0:
                x = jnp.pad(x, ((0, 0), (0, 0), (0, padn), (0, 0), (0, 0)))
        return x
    return jax.tree_util.tree_map_with_path(pad, cache)


# ---------------------------------------------------------------------------
# loss / utilities
# ---------------------------------------------------------------------------
def lm_loss(logits: jax.Array, labels: jax.Array,
            mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean cross-entropy in fp32.  logits: (B,S,V); labels: (B,S)."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def param_count(params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))


def active_param_count(params, cfg: ModelConfig) -> int:
    """Parameters touched per token (MoE experts scaled by top_k/E)."""
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        size = int(leaf.size)
        keys = "/".join(str(p) for p in path)
        if "_moe" in keys and "router" not in keys:
            size = size * cfg.top_k // max(cfg.n_experts, 1)
        total += size
    return total
