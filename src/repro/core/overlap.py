"""In-graph communication schedules — the Level-B TAMPI adaptation.

On a TPU pod the performance-critical communication lives *inside* one XLA
program, where "task dependencies" are HLO dataflow edges and "the
scheduler" is XLA's latency-hiding scheduler.  The paper's insight maps to
schedule construction: present the gradient synchronisation as

* ``fused``    — ONE all-reduce over the whole flattened gradient at the end
                 of backward.  This is the Fork-Join/Pure-MPI pattern: a
                 barrier-style phase boundary; nothing can overlap.
* ``bucketed`` — one all-reduce per parameter bucket with NO artificial
                 dependencies between them, so each reduction is issued as
                 soon as its producers are done and overlaps the remaining
                 backward compute.  This is the Interop/TAMPI pattern —
                 dependencies alone order the collectives.
* ``sentinel`` — the bucketed collectives chained through explicit tokens
                 (``lax.optimization_barrier``), serialising them exactly
                 like the artificial sentinel dependency of paper §6.3/§7.1.

Since the schedule-IR refactor this module is a thin wrapper over
:mod:`repro.core.lowering`, the Level-B executor of the same
:mod:`repro.core.schedule` IR the host progress engine interprets: each
bucket's reduction is one schedule node — ``algorithm="native"`` (the
default) lowers it to a fused ``lax.psum`` (identical HLO to the pre-IR
code: one ``all-reduce`` per bucket, same order), while ``"ring"`` /
``"doubling"`` lower the explicit ppermute rounds of the corresponding
host schedule, including the segmented/pipelined ring
(``segments > 1``).  ``halo_exchange_rows`` likewise executes the
1-D neighbourhood schedule via :func:`repro.core.lowering.lower_neighbor`.

These run inside ``jax.shard_map`` manual over the DP axes (the model axis
stays auto/GSPMD).  Structural verification = collective count/order in the
lowered HLO; benchmarks/overlap_bench.py measures wall time on the local
mesh plus the α-β predicted times, and EXPERIMENTS.md §Perf reports the
roofline deltas.

``compress="bf16"`` halves the bytes on the wire (cast → reduce → cast), an
orthogonal distributed-optimization trick.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from . import lowering
from . import schedule as schedule_ir
from .options import CollectiveOptions, renamed_kwarg

DEFAULT_BUCKET_BYTES = 4 << 20


def _flatten_with_sizes(grads):
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    shapes = [l.shape for l in leaves]
    sizes = [int(l.size) for l in leaves]
    return leaves, treedef, shapes, sizes


def _make_buckets(nbytes: Sequence[int],
                  bucket_bytes: int) -> List[List[int]]:
    """Greedy byte-based bucketing of leaf indices (DDP-style).

    ``nbytes[i]`` is leaf i's byte count AS SENT (``size × wire-dtype
    itemsize`` — bf16 buckets pack twice the element count of fp32, so
    ``bucket_bytes`` bounds the actual message size; the old
    4-bytes-per-element assumption over-fragmented narrow dtypes).
    """
    buckets: List[List[int]] = []
    cur: List[int] = []
    acc = 0
    for i, b in enumerate(nbytes):
        cur.append(i)
        acc += b
        if acc >= bucket_bytes:
            buckets.append(cur)
            cur, acc = [], 0
    if cur:
        buckets.append(cur)
    return buckets


def _wire_dtype(leaf, compress: Optional[str], wire: str):
    """The dtype a leaf travels (and accumulates) in.

    Default ``reduce_dtype="fp32"``: everything upcasts to fp32 — the
    safe accumulation the pre-IR code always used (the repo's default
    model dtype is bf16, so silently summing DP gradients in bf16 would
    be a numerics regression).  ``reduce_dtype="leaf"`` opts floating
    leaves into their own dtype (a bf16 grad travels AND accumulates in
    bf16 — the same trade ``compress="bf16"`` makes globally); integer
    dtypes always upcast (a psum would overflow).  ``compress`` overrides
    both.
    """
    if compress == "bf16":
        return jnp.dtype(jnp.bfloat16)
    if compress is None and wire == "leaf" and \
            jnp.issubdtype(leaf.dtype, jnp.floating):
        return jnp.dtype(leaf.dtype)
    return jnp.dtype(jnp.float32)


def sync_grads(grads, *, axes, mode: str = "bucketed",
               bucket_bytes: int = DEFAULT_BUCKET_BYTES,
               compress: Optional[str] = None, mean: bool = True,
               algorithm: Optional[str] = None, segments: int = 1,
               reduce_dtype: Optional[str] = None,
               wire: Optional[str] = None,
               hierarchical: Optional[bool] = None,
               stage_impl: Optional[str] = None,
               stage_wire: Optional[str] = None,
               options: Optional[CollectiveOptions] = None):
    """Reduce gradients over the (manual) DP axes with a chosen schedule.

    Must be called inside ``shard_map`` manual over ``axes``.  ``mode``
    picks the bucketing/ordering pattern (fused/bucketed/sentinel);
    ``algorithm`` picks each bucket's wire schedule — ``"native"`` (one
    fused all-reduce node, the default and the production path),
    ``"ring"``/``"doubling"`` (explicit in-graph rounds lowered from the
    schedule IR; single DP axis only), with ``segments > 1`` pipelining
    the ring.  ``hierarchical=True`` requires exactly two DP axes in
    ``(inter, intra)`` order — e.g. ``("pod", "data")`` on the multi-pod
    production mesh — and reduces each bucket with the composed
    :func:`repro.core.schedule.build_hierarchical` schedule (intra-axis
    ring rounds, inter-axis butterfly or fused psum), the Level-B form of
    :class:`repro.core.collectives.HierarchicalCollectives`.

    Presentation dtype: by default (``reduce_dtype="fp32"``) every leaf
    travels and accumulates in fp32 — identical numerics to the pre-IR
    code in every mode; ``reduce_dtype="leaf"`` opts floating leaves
    into their own dtype — halving bf16 wire bytes at the cost of bf16
    accumulation, the same trade ``compress="bf16"`` makes globally.
    Buckets are dtype-grouped and sized by bytes AS SENT, so
    ``bucket_bytes`` bounds the real message size under either setting.
    The rule is shared by all three modes, so mode selection never
    changes numerics.  ``wire=`` is the deprecated spelling of
    ``reduce_dtype=`` (see
    :class:`repro.core.options.CollectiveOptions`, accepted here as
    ``options=``).

    ``stage_impl`` routes each bucket's between-round elementwise stages
    through the fused Pallas tier (see
    :func:`repro.core.lowering.allreduce`; explicit-round algorithms
    only).  ``stage_wire`` (``"bf16"``/``"int8"``) additionally narrows
    the ring transport dtype per round — distinct from ``reduce_dtype=``,
    which picks the dtype a leaf is PRESENTED to the collective in.
    """
    reduce_dtype = renamed_kwarg("wire", wire, "reduce_dtype",
                                 reduce_dtype)
    (algorithm, segments, hierarchical, stage_impl, stage_wire,
     reduce_dtype) = CollectiveOptions.merge(
        options, algorithm=algorithm, segments=segments,
        hierarchical=hierarchical, stage_impl=stage_impl,
        stage_wire=stage_wire, reduce_dtype=reduce_dtype)
    algorithm = algorithm or "native"
    reduce_dtype = reduce_dtype or "fp32"
    hierarchical = bool(hierarchical)
    if isinstance(axes, str):
        axes = (axes,)
    if compress == "int8" and (stage_impl is not None
                               or stage_wire is not None):
        raise ValueError("compress='int8' uses its own quantised "
                         "all_to_all path; drop stage_impl=/stage_wire=")
    if hierarchical:
        if len(tuple(axes)) != 2:
            raise ValueError(f"hierarchical grad sync needs exactly two "
                             f"DP axes (inter, intra), got {tuple(axes)}")
        if algorithm != "native" or segments != 1:
            raise ValueError("hierarchical=True picks the schedule; drop "
                             "algorithm=/segments=")
        algorithm = "hierarchical"
    leaves, treedef, shapes, sizes = _flatten_with_sizes(grads)
    # psum over multiple axes: pass the tuple directly.
    axis_arg = tuple(axes)

    def reduce_block(x):
        if compress == "int8":
            assert len(axis_arg) == 1, "int8 path: single reduction axis"
            return quantized_psum_mean(x.astype(jnp.float32),
                                       axis_arg[0]) * \
                jax.lax.axis_size(axis_arg[0])  # sync_grads divides later
        if compress == "bf16":
            x = x.astype(jnp.bfloat16)
        x = lowering.allreduce(x, axis_arg, algorithm=algorithm,
                               segments=segments, stage_impl=stage_impl,
                               stage_wire=stage_wire)
        return x.astype(jnp.float32)

    if reduce_dtype not in ("fp32", "leaf"):
        raise ValueError(f"unknown reduce_dtype policy {reduce_dtype!r}; "
                         f"one of ['fp32', 'leaf']")
    # Leaves group by their presentation dtype in EVERY mode, so the
    # per-leaf numerics are identical whichever mode is selected (under
    # the fp32 default that is one group with the exact pre-IR layout
    # and HLO).
    groups: Dict[Any, List[int]] = {}
    for i, l in enumerate(leaves):
        groups.setdefault(_wire_dtype(l, compress, reduce_dtype),
                          []).append(i)

    if mode == "fused":
        # one collective per wire dtype (one total for uniform models) —
        # the fork-join phase boundary.
        out = [None] * len(leaves)
        for wdt, idxs in groups.items():
            flat = jnp.concatenate([leaves[i].astype(wdt).reshape(-1)
                                    for i in idxs])
            flat = reduce_block(flat)
            off = 0
            for i in idxs:
                out[i] = flat[off:off + sizes[i]].reshape(shapes[i])
                off += sizes[i]
    elif mode in ("bucketed", "sentinel"):
        # dtype-homogeneous buckets (DDP-style): each group buckets
        # greedily by bytes AS SENT, so ``bucket_bytes`` bounds the real
        # message size — a bf16 bucket packs twice the elements of an
        # fp32 one.
        reduced: List[Any] = [None] * len(leaves)
        token = None
        for wdt, idxs in groups.items():
            itemsize = 1 if compress == "int8" else wdt.itemsize
            nbytes = [sizes[i] * itemsize for i in idxs]
            for b in _make_buckets(nbytes, bucket_bytes):
                sel = [idxs[j] for j in b]
                chunk = jnp.concatenate(
                    [leaves[i].astype(wdt).reshape(-1) for i in sel])
                if mode == "sentinel" and token is not None:
                    # Serialise on the previous collective — the artificial
                    # dependency the paper's technique removes.
                    chunk, _ = jax.lax.optimization_barrier((chunk, token))
                chunk = reduce_block(chunk)
                token = jnp.sum(chunk[:1])
                off = 0
                for i in sel:
                    reduced[i] = chunk[off:off + sizes[i]].reshape(
                        shapes[i])
                    off += sizes[i]
        out = reduced
    else:
        raise ValueError(f"unknown grad sync mode {mode!r}")

    if mean:
        # DP world size is static inside shard_map — no collective needed.
        ws = 1.0
        for a in axis_arg:
            ws *= jax.lax.axis_size(a)
        out = [o / ws for o in out]
    return treedef.unflatten([o.astype(l.dtype)
                              for o, l in zip(out, leaves)])


# ---------------------------------------------------------------------------
# int8 quantized reduction (gradient compression, 4x wire reduction)
# ---------------------------------------------------------------------------
def quantized_psum_mean(x: jax.Array, axis: str) -> jax.Array:
    """Mean-reduce a flat fp32 vector over ``axis`` with int8 on the wire.

    reduce-scatter leg: per-rank symmetric int8 quantisation (scales
    exchanged as scalars), shards moved with an int8 ``all_to_all``,
    dequantised and summed in fp32; all-gather leg: the reduced shard is
    re-quantised and gathered in int8.  Wire bytes ≈ 2·n·1B vs 2·n·4B for
    an fp32 ring all-reduce.  Quantisation error is bounded by
    max|g|/127 per element per leg (no error feedback — acceptable for
    gradients under Adam's normalisation; see EXPERIMENTS.md).
    Must run inside shard_map manual over ``axis``.
    """
    world = jax.lax.axis_size(axis)
    n = x.size
    pad = (-n) % world
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-20) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    qs = q.reshape(world, -1)
    # my shard of everyone's quantised gradient (int8 on the wire)
    recv = jax.lax.all_to_all(qs, axis, split_axis=0, concat_axis=0,
                              tiled=False)
    scales = jax.lax.all_gather(scale, axis)            # (world,) fp32
    partial = jnp.sum(recv.astype(jnp.float32)
                      * scales[:, None], axis=0) / world
    # gather the reduced shards back, again in int8
    s2 = jnp.maximum(jnp.max(jnp.abs(partial)), 1e-20) / 127.0
    q2 = jnp.clip(jnp.round(partial / s2), -127, 127).astype(jnp.int8)
    gathered = jax.lax.all_gather(q2, axis)             # (world, n/world) s8
    s2s = jax.lax.all_gather(s2, axis)
    out = (gathered.astype(jnp.float32) * s2s[:, None]).reshape(-1)
    return out[:n] if pad else out


# ---------------------------------------------------------------------------
# Halo exchange schedules (Gauss–Seidel, paper §7.1 at Level B)
# ---------------------------------------------------------------------------
def halo_exchange_rows(x, axis_name: str, *, width: int = 1
                       ) -> Tuple[jax.Array, jax.Array]:
    """Exchange boundary rows with both neighbours along a sharded axis.

    x: the local (rows, cols) block of a 1-D row decomposition.  Returns
    (top_halo, bottom_halo) received from the previous/next shard (zeros at
    the domain edges).  Inside shard_map manual over ``axis_name``.

    Executes the 1-D non-periodic neighbourhood schedule — the same
    :func:`repro.core.schedule.build_neighbor` IR the host-side
    :class:`repro.core.collectives.HaloExchange` interprets — lowered to
    one ppermute per direction; boundary ranks have no permutation pair,
    so their halos arrive as ppermute's zeros.
    """
    n = jax.lax.axis_size(axis_name)
    sched = schedule_ir.build_neighbor(lowering.chain_topology(n))
    got = lowering.lower_neighbor(
        sched, {(0, 1): x[-width:], (0, -1): x[:width]}, axis_name)
    return got[(0, -1)], got[(0, 1)]


def chained(x, token):
    """Serialise ``x`` on ``token`` (sentinel-style artificial dependency)."""
    if token is None:
        return x, jnp.zeros((), x.dtype)
    x, _ = jax.lax.optimization_barrier((x, token))
    return x, jnp.sum(jnp.ravel(x)[:1]).astype(x.dtype)
