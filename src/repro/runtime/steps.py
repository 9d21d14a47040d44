"""Train / serve step builders with explicit shardings.

Two distribution modes:

* ``auto`` (production default): one ``jax.jit`` with NamedShardings on
  params/optimizer/batch; GSPMD inserts TP/FSDP collectives; XLA's
  latency-hiding scheduler overlaps them.  The §Roofline baselines lower
  through this path.

* ``manual`` DP: ``jax.shard_map`` manual over the DP axes (model axis
  stays auto) with the gradient-synchronisation schedule chosen explicitly
  (fused / bucketed / sentinel — core/overlap.py).  This is the Level-B
  reproduction of the paper's communication-task scheduling and the surface
  the overlap benchmarks compare.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import optim
from ..models import model
from ..models.config import ModelConfig
from ..launch.mesh import dp_axes
from .sharding import (ShardingPolicy, Constrainer, param_shardings,
                       batch_shardings, cache_shardings)

AUX_COEF = 0.01


class TrainState(NamedTuple):
    params: Any
    opt: optim.OptState


def init_train_state(cfg: ModelConfig, opt_cfg: optim.OptimConfig,
                     key) -> TrainState:
    params = model.init(cfg, key)
    return TrainState(params=params, opt=optim.init(params))


def abstract_train_state(cfg: ModelConfig, opt_cfg: optim.OptimConfig):
    return jax.eval_shape(
        lambda k: init_train_state(cfg, opt_cfg, k), jax.random.PRNGKey(0))


def state_shardings(mesh, abstract_state: TrainState,
                    policy: ShardingPolicy) -> TrainState:
    return TrainState(
        params=param_shardings(mesh, abstract_state.params, policy),
        opt=optim.OptState(
            step=NamedSharding(mesh, P()),
            m=param_shardings(mesh, abstract_state.opt.m, policy),
            v=param_shardings(mesh, abstract_state.opt.v, policy),
            master=param_shardings(mesh, abstract_state.opt.master, policy),
        ))


def _loss_fn(params, batch, cfg: ModelConfig, constrain, remat):
    logits, _, aux = model.apply(params, cfg, batch, mode="train",
                                 constrain=constrain, remat=remat)
    loss = model.lm_loss(logits, batch["labels"])
    return loss + AUX_COEF * aux, loss


# ---------------------------------------------------------------------------
# auto mode
# ---------------------------------------------------------------------------
def build_train_step(cfg: ModelConfig, mesh, policy: ShardingPolicy,
                     opt_cfg: optim.OptimConfig, *, abstract_batch=None,
                     donate: bool = True):
    """jit'd (state, batch) -> (state, metrics) with NamedShardings."""
    constrain = Constrainer(mesh, policy)
    M = max(1, policy.microbatches)

    def grad_fn(params, batch):
        return jax.value_and_grad(_loss_fn, has_aux=True)(
            params, batch, cfg, constrain, policy.remat)

    def train_step(state: TrainState, batch):
        if M == 1:
            (total, loss), grads = grad_fn(state.params, batch)
        else:
            # Gradient accumulation: scan over microbatches; the live
            # activation set shrinks by M while tokens/step (and the
            # gradient reduction) are unchanged.
            micro = jax.tree_util.tree_map(
                lambda x: x.reshape((M, x.shape[0] // M) + x.shape[1:]),
                batch)

            def acc_body(carry, mb):
                acc, tot_a, loss_a = carry
                (total, loss), g = grad_fn(state.params, mb)
                acc = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), acc, g)
                return (acc, tot_a + total, loss_a + loss), None

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            (grads, total, loss), _ = jax.lax.scan(
                acc_body, (zeros, jnp.zeros((), jnp.float32),
                           jnp.zeros((), jnp.float32)), micro)
            grads = jax.tree_util.tree_map(lambda g: g / M, grads)
            total, loss = total / M, loss / M
        new_params, new_opt, metrics = optim.update(
            opt_cfg, grads, state.opt, state.params)
        metrics = dict(metrics, loss=loss, total_loss=total)
        return TrainState(new_params, new_opt), metrics

    abstract = abstract_train_state(cfg, opt_cfg)
    sshard = state_shardings(mesh, abstract, policy)
    metrics_shard = {k: NamedSharding(mesh, P())
                     for k in ("lr", "grad_norm", "loss", "total_loss")}
    bshard = batch_shardings(mesh, abstract_batch, policy) \
        if abstract_batch is not None else None

    jitted = jax.jit(
        train_step,
        in_shardings=(sshard, bshard),
        out_shardings=(sshard, metrics_shard),
        donate_argnums=(0,) if donate else ())
    return jitted, sshard


# ---------------------------------------------------------------------------
# manual-DP mode (explicit grad-sync schedule; the paper's Level-B surface)
# ---------------------------------------------------------------------------
def build_train_step_manual(cfg: ModelConfig, mesh, policy: ShardingPolicy,
                            opt_cfg: optim.OptimConfig, *,
                            grad_sync: Optional[str] = None,
                            bucket_bytes: int = 4 << 20,
                            compress: Optional[str] = None):
    """shard_map-manual over DP axes; grad sync schedule is explicit.

    Requires ``policy.fsdp == False`` (params replicated over DP; TP still
    applies through the auto model axis).
    """
    from ..core import overlap

    assert not policy.fsdp, "manual grad-sync mode implies fsdp=False"
    mode = grad_sync or policy.grad_sync
    assert mode in ("fused", "bucketed", "sentinel"), mode
    D = dp_axes(mesh)
    constrain = None  # inside manual DP, batch dims are local; TP via auto

    def step_local(state: TrainState, batch):
        (total, loss), grads = jax.value_and_grad(
            _loss_fn, has_aux=True)(state.params, batch, cfg, None,
                                    policy.remat)
        grads = overlap.sync_grads(grads, axes=D, mode=mode,
                                   bucket_bytes=bucket_bytes,
                                   compress=compress)
        new_params, new_opt, metrics = optim.update(
            opt_cfg, grads, state.opt, state.params)
        loss = jax.lax.pmean(loss, D)
        metrics = dict(metrics, loss=loss, total_loss=total)
        return TrainState(new_params, new_opt), metrics

    replicated = P()

    def specs_for_state(abstract_state):
        return jax.tree_util.tree_map(lambda _: replicated, abstract_state)

    def specs_for_batch(abstract_batch):
        return jax.tree_util.tree_map(
            lambda leaf: P(D, *([None] * (leaf.ndim - 1))), abstract_batch)

    def make(abstract_state, abstract_batch):
        in_specs = (specs_for_state(abstract_state),
                    specs_for_batch(abstract_batch))
        out_specs = (specs_for_state(abstract_state),
                     {k: replicated for k in
                      ("lr", "grad_norm", "loss", "total_loss")})
        f = jax.shard_map(step_local, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, axis_names=set(D),
                          check_vma=False)
        # NOTE: no donation here — donating replicated shard_map inputs
        # deadlocks the CPU backend's collective rendezvous (the donated
        # buffer lives on one device; the implicit broadcast and the psum
        # schedule cross).  On TPU, re-enable donation after placing the
        # state with device_put(state, shardings).
        return jax.jit(f)

    return make


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------
def abstract_params(cfg: ModelConfig):
    return jax.eval_shape(lambda k: model.init(cfg, k),
                          jax.random.PRNGKey(0))


def build_prefill_step(cfg: ModelConfig, mesh, policy: ShardingPolicy, *,
                       abstract_batch=None):
    constrain = Constrainer(mesh, policy)

    def prefill(params, batch):
        logits, cache, _ = model.apply(params, cfg, batch, mode="prefill",
                                       constrain=constrain)
        return logits[:, -1:], cache

    ps = param_shardings(mesh, abstract_params(cfg), policy)
    bs = batch_shardings(mesh, abstract_batch, policy) \
        if abstract_batch is not None else None
    return jax.jit(prefill, in_shardings=(ps, bs))


def build_decode_step(cfg: ModelConfig, mesh, policy: ShardingPolicy, *,
                      batch: int, cache_len: int, abstract_batch=None,
                      donate: bool = True):
    constrain = Constrainer(mesh, policy, decode=True)

    def decode(params, cache, batch_, cache_index):
        logits, new_cache, _ = model.apply(
            params, cfg, batch_, mode="decode", cache=cache,
            cache_index=cache_index, constrain=constrain)
        return logits, new_cache

    ps = param_shardings(mesh, abstract_params(cfg), policy)
    a_cache = jax.eval_shape(
        functools.partial(model.init_cache, cfg, batch, cache_len))
    cs = cache_shardings(mesh, a_cache, policy)
    bs = batch_shardings(mesh, abstract_batch, policy) \
        if abstract_batch is not None else None
    jitted = jax.jit(decode, in_shardings=(ps, cs, bs, None),
                     out_shardings=(None, cs),
                     donate_argnums=(1,) if donate else ())
    return jitted, a_cache


def build_round_step(cfg: ModelConfig, mesh, policy: ShardingPolicy, *,
                     slots: int, cache_len: int):
    """Greedy decode of ``slots`` rows in one program, weights read once.

    The batch-1 decode is mapped over the rows of one stacked cache with
    the parameters shared, so each row keeps exactly the batch-1
    arithmetic (per-row position, MoE capacity, recurrent state).  The
    rows stack on axis 1 of every cache leaf, right after the
    layer-repeat axis the model scans over: where the scan's batching
    keeps a mapped axis, so no axis of the whole cache is moved.  The
    mapped step reads the cache as it lies and returns each row's new KV
    entries (:func:`repro.models.model.decode_entries`); the round then
    writes them in place, one row at a time at scalar indices, outside the
    map (mapped, the per-row write would be a scatter that moves the whole
    cache).  Returns ``(decode, handoff, abstract_cache)``:

    * ``decode(params, cache, toks, positions) -> (toks, logits, cache)``
      feeds row r its token ``toks[r]`` at ``positions[r]`` and returns
      every row's greedy next token and logits; the cache is donated.
    * ``handoff(cache, toks, prefill_cache, logits, row)
      -> (cache, toks, first)`` pads a batch-1 prefill's cache to
      ``cache_len`` and writes it into ``row``, with the prefill's
      greedy token ``first`` (shape (1,)); the cache is donated.
    """
    constrain = Constrainer(mesh, policy, decode=True)

    def row_step(params, cache, tok, pos):
        logits, entries = model.decode_entries(
            params, cfg, {"tokens": tok.reshape(1, 1)}, cache=cache,
            cache_index=pos, constrain=constrain)
        return logits[0, -1], entries

    def decode(params, cache, toks, positions):
        logits, entries = jax.vmap(row_step, in_axes=(None, 1, 0, 0),
                                   out_axes=(0, 1))(params, cache, toks,
                                                    positions)

        def write(path, c, e):
            if not model.is_kv(path):
                return e            # recurrent state, whole from the round
            for r in range(slots):  # (layers, slots, 1, T, Hkv, dh)
                c = jax.lax.dynamic_update_slice(
                    c, e[:, r:r + 1], (0, r, 0, positions[r], 0, 0))
            return c

        cache = jax.tree_util.tree_map_with_path(write, cache, entries)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits, cache

    def handoff(cache, toks, new, logits, row):
        new = model.pad_cache(cfg, new, cache_len)
        cache = jax.tree_util.tree_map(
            lambda c, n: jax.lax.dynamic_update_index_in_dim(c, n, row, 1),
            cache, new)
        first = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return cache, toks.at[row].set(first[0]), first

    row_cache = jax.eval_shape(
        functools.partial(model.init_cache, cfg, 1, cache_len))
    a_cache = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape[:1] + (slots,) + a.shape[1:],
                                       a.dtype), row_cache)
    ps = param_shardings(mesh, abstract_params(cfg), policy)
    return (jax.jit(decode, in_shardings=(ps, None, None, None),
                    donate_argnums=(1,)),
            jax.jit(handoff, donate_argnums=(0,)),
            a_cache)
