"""Sharding policy: parameter rules, activation constraints, batch specs.

The policy implements DP (+hierarchical pod-DP), FSDP (params/optimizer
sharded over the data axes), TP (heads / ffn / experts over the model axis),
SP (residual-stream sequence sharding over the model axis between blocks)
and EP (MoE experts over the model axis).

Every preferred PartitionSpec is validated against the actual dimension
sizes — axes that do not divide a dimension are dropped (never silently
padded), so e.g. 4 kv heads on a 16-way model axis fall back cleanly.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..launch.mesh import dp_axes, tp_axis, axis_size


@dataclasses.dataclass(frozen=True)
class ShardingPolicy:
    """Knobs for the distribution strategy (the §Perf hillclimb surface)."""
    fsdp: bool = True              # shard params over the data axes
    tp: bool = True                # tensor parallelism over 'model'
    sp: bool = True                # sequence-shard residuals over 'model'
    ep: bool = True                # experts over 'model'
    remat: Optional[str] = "dots"  # None | "full" | "dots"
    grad_sync: str = "auto"        # auto | fused | bucketed | sentinel
    shard_embed_vocab: bool = True
    microbatches: int = 1          # gradient accumulation (activation mem ÷M)
    # "data": classic FSDP over the data axes (model axis = TP).
    # "all":  pure-FSDP — params AND batch shard over every mesh axis; no
    #         tensor parallelism (beyond-paper sharding-scheme change for
    #         models whose layers fit one chip).
    fsdp_axes: str = "data"
    fsdp_experts: bool = True      # False: expert weights skip FSDP (keeps
    #         contractions unsharded on d/ff -> no activation all-reduce
    #         over the data axes at the cost of replicated expert storage)
    gather_expert_weights: bool = False  # reshard expert weights at use
    #         (storage stays FSDP; the matmul sees gathered weights)


# ---------------------------------------------------------------------------
# parameter rules
# ---------------------------------------------------------------------------
# (path regex, candidate specs) — first matching rule wins; within a rule the
# first candidate whose partitioned dims all divide is used, else the first
# candidate with non-dividing axes dropped.  `F` is the FSDP axes tuple (or
# None), `T` the tensor axis (or None).
def _param_rules(F, T, policy: ShardingPolicy):
    E = T if policy.ep else None   # expert axis
    V = T if policy.shard_embed_vocab else None
    FE = F if policy.fsdp_experts else None   # FSDP axes for expert weights
    return [
        (r"embed$",                [P(V, F)]),
        (r"lm_head$",              [P(F, T)]),
        # moe (leading expert dim) — must precede the generic mlp rules.
        # EP proper when E divides the model axis (olmoe: 64 experts);
        # otherwise tensor-parallel experts (mixtral: 8 experts on a 16-way
        # axis -> shard the ffn dim instead).
        (r"router$",               [P(F, None)]),
        (r"_moe/w[gu]$",           [P(E, FE, None), P(None, FE, T)]),
        (r"_moe/wd$",              [P(E, None, FE), P(None, T, FE)]),
        # attention
        (r"w[qkv]$",               [P(F, T)]),
        (r"wo$",                   [P(T, F)]),
        # mlp
        (r"w[gu]$",                [P(F, T)]),
        (r"wd$",                   [P(T, F)]),
        # mamba2
        (r"w[zx]$",                [P(F, T)]),
        (r"w[BC]$",                [P(F, None)]),
        (r"wdt$",                  [P(F, None)]),
        (r"out$",                  [P(T, F)]),
        (r"conv_[wb]$",            [P()]),
        # xlstm
        (r"wup$",                  [P(F, T)]),
        (r"r[ifzo]$",              [P(T, None, None)]),
        (r"w[ifzo]$",              [P(F, T)]),
        (r"wd2$",                  [P(T, F)]),
        (r"b[ifzo]$",              [P()]),
        # norms / scalars / everything small
        (r".*",                    [P()]),
    ]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _divides(spec: P, shape: Tuple[int, ...], mesh) -> bool:
    for i, entry in enumerate(spec):
        if entry is None:
            continue
        if i >= len(shape) or shape[i] <= 0 \
                or shape[i] % axis_size(mesh, entry) != 0:
            return False
    return True


def _fit_spec(spec: P, shape: Tuple[int, ...], mesh) -> P:
    """Drop partition axes that don't divide their dimension."""
    out = []
    for i, entry in enumerate(spec):
        if entry is None:
            out.append(None)
            continue
        size = axis_size(mesh, entry)
        if i < len(shape) and shape[i] % size == 0 and shape[i] > 0:
            out.append(entry)
        else:
            out.append(None)
    # trailing dims unspecified -> replicated
    return P(*out)


def _fit_candidates(specs, shape: Tuple[int, ...], mesh) -> P:
    """First candidate that divides cleanly; else first candidate fitted."""
    for spec in specs:
        full = P(*(tuple(spec) + (None,) * (len(shape) - len(spec))))
        if _divides(full, shape, mesh):
            return full
    spec = specs[0]
    full = P(*(tuple(spec) + (None,) * (len(shape) - len(spec))))
    return _fit_spec(full, shape, mesh)


def batch_axes(mesh, policy: Optional[ShardingPolicy] = None):
    """Axes carrying the batch dim (all axes under pure-FSDP)."""
    D = dp_axes(mesh)
    if policy is not None and policy.fsdp_axes == "all" and tp_axis(mesh):
        D = D + (tp_axis(mesh),)
    return D


def _policy_axes(mesh, policy: ShardingPolicy):
    F = dp_axes(mesh)
    T = tp_axis(mesh)
    if policy.fsdp_axes == "all" and T is not None:
        F = F + (T,)
        T = None            # pure-FSDP: the model axis carries data
    if not policy.fsdp:
        F = None
    if not policy.tp:
        T = None
    return F, T


def param_shardings(mesh, abstract_params, policy: ShardingPolicy):
    """NamedShardings for a (possibly stacked) parameter tree."""
    F, T = _policy_axes(mesh, policy)
    rules = _param_rules(F, T, policy)

    def assign(path, leaf):
        s = _path_str(path)
        # stacked over layers, or (Zamba2) over shared blocks/invocations
        stacked = "layers/" in s or s.startswith(("layers", "shared/"))
        for pat, candidates in rules:
            if re.search(pat, s):
                break
        shape = leaf.shape
        if stacked:
            candidates = [P(None, *c) for c in candidates]  # unit-repeat dim
        return NamedSharding(mesh, _fit_candidates(candidates, shape, mesh))

    return jax.tree_util.tree_map_with_path(assign, abstract_params)


# ---------------------------------------------------------------------------
# batch / activation / cache shardings
# ---------------------------------------------------------------------------
def batch_shardings(mesh, abstract_batch,
                    policy: Optional[ShardingPolicy] = None):
    """Tokens & friends: batch dim over the DP axes (all axes for
    pure-FSDP policies)."""
    D = batch_axes(mesh, policy)

    def assign(path, leaf):
        spec = P(D, *([None] * (leaf.ndim - 1)))
        return NamedSharding(mesh, _fit_spec(spec, leaf.shape, mesh))

    return jax.tree_util.tree_map_with_path(assign, abstract_batch)


def cache_shardings(mesh, abstract_cache, policy: ShardingPolicy):
    """Decode caches: batch over DP where divisible, heads over TP.

    Leaf layouts (leading dim = unit repeats):
      attn k/v      (R, B, T, Hkv, dh) -> P(None, D, None, T, None)
      mamba2 state  (R, B, H, P, N)    -> P(None, D, T, None, None)
      mamba2 conv   (R, B, K, C)       -> P(None, D, None, T)
      mlstm C       (R, B, H, d, d)    -> P(None, D, T, None, None)
      mlstm n       (R, B, H, d)       -> P(None, D, T, None)
      mlstm m       (R, B, H)          -> P(None, D, T)
      slstm c/n/h/m (R, B, d)          -> P(None, D, T)
    """
    D = dp_axes(mesh)
    T = tp_axis(mesh) if policy.tp else None

    def assign(path, leaf):
        nd = leaf.ndim
        if nd >= 3:
            spec = [None, D] + [None] * (nd - 2)
            if nd >= 4:
                s = _path_str(path)
                if "_attn" in s or "_swa" in s:
                    # KV cache (R, B, T_time, Hkv, dh): heads over the model
                    # axis when they divide; otherwise shard the TIME dim —
                    # decode attention over a time-sharded cache is the
                    # flash-decode pattern (partial max/sum + tiny
                    # all-reduces) and GSPMD lowers it directly.  A
                    # replicated 32k cache is 10s of GiB per device.
                    if leaf.shape[nd - 2] % axis_size(mesh, T or ()) == 0 \
                            and T is not None:
                        spec[nd - 2] = T
                    else:
                        spec[2] = T
                else:
                    spec[2] = T
            else:
                spec[2] = T
        else:
            spec = [None] * nd
        return NamedSharding(mesh, _fit_spec(P(*spec), leaf.shape, mesh))

    return jax.tree_util.tree_map_with_path(assign, abstract_cache)


# ---------------------------------------------------------------------------
# activation constraints (installed into the model's `constrain` hooks)
# ---------------------------------------------------------------------------
class Constrainer:
    """with_sharding_constraint hooks threaded through the model.

    ``residual`` implements sequence parallelism: between blocks the
    residual stream (B, S, d) is sharded (DP, TP, None) so stored
    activations never materialise replicated copies over the model axis.
    """

    def __init__(self, mesh, policy: ShardingPolicy, *,
                 decode: bool = False):
        self.mesh = mesh
        self.policy = policy
        self.D = batch_axes(mesh, policy)
        _, self.T = _policy_axes(mesh, policy)
        self.decode = decode

    def _c(self, x, *spec):
        fitted = _fit_spec(P(*spec), x.shape, self.mesh)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, fitted))

    def residual(self, x):
        if x.ndim != 3:
            return x
        if self.policy.sp and not self.decode:
            return self._c(x, self.D, self.T, None)
        return self._c(x, self.D, None, None)

    def heads(self, x):      # (B, S, H, dh)
        return self._c(x, self.D, None, self.T, None)

    def attn_acc(self, x):   # (B, H, S, dh) — flash scan carry
        return self._c(x, self.D, self.T, None, None)

    def attn_stats(self, x):  # (B, H, S) — flash m/l carries
        return self._c(x, self.D, self.T, None)

    def ffn(self, x):        # (B, S, ff)
        return self._c(x, self.D, None, self.T)

    def experts(self, x):    # (G, E, cap, d) — groups over DP, experts over
        # the model axis when they divide it (EP); otherwise experts stay
        # local and the ffn dim carries the model axis (TP-experts).
        if self.policy.ep and x.ndim == 4:
            fitted = _fit_spec(P(self.D, self.T, None, None), x.shape,
                               self.mesh)
            if fitted[1] is not None:
                return jax.lax.with_sharding_constraint(
                    x, NamedSharding(self.mesh, fitted))
        return self._c(x, self.D, *([None] * (x.ndim - 1)))

    def expert_weights(self, w):  # (E, d, ff) / (E, ff, d)
        if not self.policy.gather_expert_weights:
            return w
        spec = P(self.T, None, None) if w.shape[0] % \
            axis_size(self.mesh, self.T or ()) == 0 and self.T else \
            P(None, None, self.T) if w.shape[2] % \
            axis_size(self.mesh, self.T or ()) == 0 and self.T else P()
        return self._c(w, *spec)

    def ssm_heads(self, x):  # (B, S, H, P)
        return self._c(x, self.D, None, self.T, None)

    def logits(self, x):     # (B, S, V)
        if self.decode:
            # decode: S == 1 — shard the vocab if it divides, else batch only
            return self._c(x, self.D, None, self.T)
        # Sequence-sharded logits (SP-consistent): vocab sizes are often not
        # divisible by the model axis (e.g. 49155 on 16) but power-of-two
        # sequence lengths always are — this is what keeps the fp32 loss
        # intermediates from replicating.
        return self._c(x, self.D, self.T, None)
