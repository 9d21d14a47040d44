"""Pipeline parallelism: GPipe-style microbatch schedule over a stage axis.

The Level-B catalogue's remaining parallelism letter.  Stages are laid out
over a mesh axis; activations travel stage→stage via
``jax.lax.ppermute`` inside ``shard_map`` (manual over the stage axis).
The schedule is the classic GPipe ladder: with S stages and M microbatches
the loop runs M+S−1 ticks; stage s computes microbatch t−s at tick t.
Bubble fraction = (S−1)/(M+S−1) — reported by :func:`bubble_fraction` so
the trade-off is visible in benchmarks.

The production (pod, data, model) mesh does not carry a stage axis — PP is
exercised on custom meshes (``tests/test_pipeline.py`` uses (stage=4,)) and
composes with the other axes through ``shard_map``'s ``axis_names``.
Communication pattern: one ``collective-permute`` per tick — the paper's
Level-B story again: the permutes carry no false dependencies, so
consecutive ticks' sends overlap the next microbatch's compute under XLA's
scheduler.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)


def pipeline_apply(stage_fn: Callable[[Any, jax.Array], jax.Array],
                   stage_params: Any, x: jax.Array, *,
                   mesh, stage_axis: str = "stage") -> jax.Array:
    """Run ``stage_fn`` as a pipeline over ``stage_axis``.

    stage_params: pytree with a leading stage dimension (sharded over
    ``stage_axis``); ``x``: (M, ...) microbatched inputs (replicated).
    Returns (M, ...) outputs of the final stage (replicated).
    """
    S = mesh.shape[stage_axis]
    M = x.shape[0]

    def local(params_local, x_all):
        # params_local: this stage's slice (leading dim 1) — squeeze it.
        params_here = jax.tree_util.tree_map(lambda p: p[0], params_local)
        sid = jax.lax.axis_index(stage_axis)
        n_ticks = M + S - 1
        fwd_perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            inbuf, outbuf = carry
            m = t - sid                       # microbatch index at my stage
            valid = (m >= 0) & (m < M)
            mb = jnp.clip(m, 0, M - 1)
            # stage 0 reads the raw microbatch; others read the permuted buf
            x_in = jnp.where(sid == 0, x_all[mb], inbuf)
            y = stage_fn(params_here, x_in)
            y = jnp.where(valid, y, jnp.zeros_like(y))
            # last stage records its finished microbatch
            record = valid & (sid == S - 1)
            outbuf = jax.lax.dynamic_update_index_in_dim(
                outbuf, jnp.where(record, y, outbuf[mb]), mb, 0)
            # everyone ships their activation to the next stage
            nxt = jax.lax.ppermute(y, stage_axis, fwd_perm)
            return (nxt, outbuf), None

        inbuf0 = jnp.zeros_like(x_all[0])
        outbuf0 = jnp.zeros_like(x_all)
        (_, outbuf), _ = jax.lax.scan(
            tick, (inbuf0, outbuf0), jnp.arange(n_ticks))
        # replicate the last stage's collected outputs to every stage
        mask = (jax.lax.axis_index(stage_axis) == S - 1).astype(outbuf.dtype)
        return jax.lax.psum(outbuf * mask, stage_axis)

    f = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(stage_axis), P()), out_specs=P(),
        axis_names={stage_axis}, check_vma=False)
    return f(stage_params, x)
