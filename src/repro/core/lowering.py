"""Level-B executor: lower schedule IR to in-graph XLA collectives.

The second consumer of :mod:`repro.core.schedule` (the first is the host
interpreter in :mod:`repro.core.collectives`): the SAME schedule object
that the host progress engine interprets op-by-op is mapped here onto
JAX primitives inside ``shard_map``-manual regions, where "task
dependencies" are HLO dataflow edges and "the scheduler" is XLA's
latency-hiding scheduler.

Three lowering strategies, chosen by the schedule:

* **Explicit rounds** — ring (any rank count, any segment count) and
  recursive-doubling (power-of-two rank counts) allreduces become
  ``lax.ppermute`` rounds whose count and order mirror the schedule's
  transfer structure exactly (``2(n-1)·S`` ring rounds, ``log2 n``
  butterfly rounds; asserted against ``Schedule`` op counts in tests).
  Segmented schedules emit independent per-segment round chains with no
  artificial dependencies between them, so XLA overlaps the combine of
  segment *k* with the transport of segment *k+1* — the in-graph
  realisation of the pipelined schedule.

* **Fused node** — ``algorithm="native"`` lowers the whole allreduce to
  one ``lax.psum``; XLA's own combiner picks the wire schedule.  This is
  what :func:`repro.core.overlap.sync_grads` uses by default, which keeps
  the bucketed/sentinel HLO (one ``all-reduce`` per bucket, same order)
  byte-compatible with the pre-IR code.

* **Neighbourhood** — a :func:`repro.core.schedule.build_neighbor`
  schedule lowers to one ``ppermute`` per direction whose permutation
  pairs are read straight off the schedule's transfers; ranks missing a
  direction (non-periodic boundaries) simply have no pair and XLA
  delivers zeros — which is how
  :func:`repro.core.overlap.halo_exchange_rows` gets its zero boundary
  halos without explicit masking.

* **Hierarchical two-axis** — a
  :func:`repro.core.schedule.build_hierarchical` schedule lowers over TWO
  mesh axes ``(inter, intra)``: the intra-axis ring reduce-scatter and
  allgather stages become explicit ``ppermute`` rounds along the intra
  axis, and the inter stage becomes the recursive-doubling butterfly
  along the inter axis (power-of-two pod counts) or one fused
  ``lax.psum`` of the owned chunk (any pod count) — the same three-stage
  composition the host interpreter runs, reading its structure off the
  schedule's ``axes`` metadata.

In-graph lowering restrictions (by construction of the substrate): the
combining operator is addition (the gradient/residual case), payloads are
dense arrays, and flat explicit-round lowerings run over ONE mesh axis —
``native`` takes an axis tuple, and ``hierarchical`` takes exactly two
axes in ``(inter, intra)`` order.

**Pallas executor tier** (``stage_impl=``): the elementwise stages
between ppermute rounds — reduce-scatter combine, allgather install,
wire cast/dequant — are memory-bound work that unfused XLA round-trips
through HBM once per stage.  ``stage_impl="pallas"`` routes them through
the fused single-pass kernels in :mod:`repro.kernels.collective_stages`
(``"pallas_interpret"`` for CPU parity runs, ``"ref"`` for the jnp
oracle); ``stage_impl=None`` keeps the plain XLA elementwise path
byte-for-byte.  ``stage_wire="bf16"``/``"int8"`` (formerly spelled
``wire=``; see :class:`repro.core.options.CollectiveOptions`)
additionally narrows the ring
transport dtype (explicit-round ring only): reduce-scatter rounds
quantise the outgoing chunk and the fused combine dequantises while
accumulating; the allgather leg quantises each reduced chunk ONCE at its
owner, forwards the wire payload around the whole ring, and every rank
dequantises all chunks at the end — so all ranks compute bit-identical
results from the same wire bytes.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from ..kernels import ops as kernel_ops
from . import schedule as schedule_ir
from .options import CollectiveOptions, renamed_kwarg
from .schedule import Schedule, Send

Axes = Union[str, Sequence[str]]

_WIRE_DTYPES = {"bf16": jnp.bfloat16, "int8": jnp.int8}


def _check_stage_opts(algorithm: str, stage_impl: Optional[str],
                      wire: Optional[str]) -> None:
    if stage_impl not in (None, "pallas", "pallas_interpret", "ref"):
        raise ValueError(f"unknown stage_impl {stage_impl!r}")
    if wire is None:
        return
    if wire not in _WIRE_DTYPES:
        raise ValueError(f"unknown stage_wire dtype {wire!r}; choose from "
                         f"{sorted(_WIRE_DTYPES)}")
    if stage_impl is None:
        raise ValueError("stage_wire= needs a fused stage tier; pass "
                         "stage_impl=")
    if algorithm != "ring":
        raise ValueError(f"wire cast covers explicit ring rounds only, "
                         f"not algorithm={algorithm!r}")


def _single_axis(axis_name: Axes, what: str) -> str:
    if isinstance(axis_name, str):
        return axis_name
    axes = tuple(axis_name)
    if len(axes) != 1:
        raise ValueError(f"{what} lowers over a single mesh axis, got "
                         f"{axes}; use algorithm='native' for axis tuples")
    return axes[0]


def _two_axes(axis_name: Axes) -> Tuple[str, str]:
    """``(inter, intra)`` mesh axes of a hierarchical lowering."""
    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    if len(axes) != 2:
        raise ValueError(f"hierarchical allreduce lowers over exactly two "
                         f"mesh axes (inter, intra), got {axes}")
    return axes[0], axes[1]


def _check_world(sched: Schedule, axis_name: str) -> None:
    n = lax.axis_size(axis_name)
    if sched.n != n:
        raise ValueError(f"schedule is for {sched.n} ranks but axis "
                         f"{axis_name!r} has {n} shards")


def sends_per_rank(sched: Schedule) -> int:
    """Transfer rounds each rank issues — the lowered ppermute count per
    explicit-round leg (structural-equivalence hook for tests)."""
    return max(sum(isinstance(op, Send) for op in prog)
               for prog in sched.programs)


# ---------------------------------------------------------------------------
# Allreduce lowerings
# ---------------------------------------------------------------------------
def allreduce(x: jax.Array, axes: Axes, *,
              algorithm: Optional[str] = None, segments: int = 1,
              sched: Optional[Schedule] = None,
              stage_impl: Optional[str] = None,
              stage_wire: Optional[str] = None,
              wire: Optional[str] = None,
              options: Optional[CollectiveOptions] = None) -> jax.Array:
    """Sum-allreduce ``x`` over ``axes`` with a chosen schedule.

    ``algorithm="native"`` (the default) emits one fused ``lax.psum``
    node (XLA picks the rounds); ``"ring"``/``"doubling"`` build (or
    take) a schedule and emit its explicit ppermute rounds.  Must be
    called inside ``shard_map`` manual over ``axes``.

    ``stage_impl`` routes the between-round elementwise stages through
    the fused Pallas tier (``"pallas"``/``"pallas_interpret"``/``"ref"``;
    ``None`` keeps the plain XLA path).  ``stage_wire`` narrows the ring
    transport dtype (``"bf16"``/``"int8"``; needs ``stage_impl``, ring
    algorithm only).  ``wire=`` is the deprecated spelling of
    ``stage_wire=``; an explicit :class:`CollectiveOptions` spec is
    accepted as ``options=``.
    """
    stage_wire = renamed_kwarg("wire", wire, "stage_wire", stage_wire)
    algorithm, segments, stage_impl, stage_wire = CollectiveOptions.merge(
        options, algorithm=algorithm, segments=segments,
        stage_impl=stage_impl, stage_wire=stage_wire)
    if algorithm is None:
        algorithm = "native"
    if sched is None and algorithm == "native":
        if stage_impl is not None or stage_wire is not None:
            raise ValueError("native lowering is one fused psum node — "
                             "no stages to fuse; drop "
                             "stage_impl=/stage_wire=")
        return lax.psum(x, tuple(axes) if not isinstance(axes, str)
                        else (axes,))
    _check_stage_opts(algorithm if sched is None else sched.algorithm,
                      stage_impl, stage_wire)
    if sched is None and algorithm == "hierarchical":
        if segments != 1:
            # mirror Collectives._resolve: the composed schedule is fixed,
            # silently dropping segments would fake pipelining.
            raise ValueError("hierarchical allreduce fixes the composed "
                             "schedule; drop segments=")
        inter_axis, intra_axis = _two_axes(axes)
        sched = schedule_ir.build_hierarchical(lax.axis_size(intra_axis),
                                               lax.axis_size(inter_axis))
    if sched is None:
        axis = _single_axis(axes, f"allreduce[{algorithm}]")
        sched = schedule_ir.build("allreduce", algorithm,
                                  lax.axis_size(axis), segments=segments)
    return lower_allreduce(sched, x, axes, stage_impl=stage_impl,
                           stage_wire=stage_wire)


def lower_allreduce(sched: Schedule, x: jax.Array, axes: Axes, *,
                    stage_impl: Optional[str] = None,
                    stage_wire: Optional[str] = None,
                    wire: Optional[str] = None,
                    options: Optional[CollectiveOptions] = None
                    ) -> jax.Array:
    """Lower an allreduce schedule to explicit in-graph rounds.

    The schedule fixes algorithm and segmentation, so ``options=`` may
    only set the stage-tier knobs here.  ``wire=`` is the deprecated
    spelling of ``stage_wire=``.
    """
    stage_wire = renamed_kwarg("wire", wire, "stage_wire", stage_wire)
    stage_impl, stage_wire = CollectiveOptions.merge(
        options, stage_impl=stage_impl, stage_wire=stage_wire)
    if sched.name != "allreduce":
        raise ValueError(f"expected an allreduce schedule, got "
                         f"{sched.name!r}")
    _check_stage_opts(sched.algorithm, stage_impl, stage_wire)
    if sched.algorithm == "hierarchical":
        return _hierarchical_allreduce(sched, x, axes,
                                       stage_impl=stage_impl)
    axis = _single_axis(axes, f"allreduce[{sched.algorithm}]")
    _check_world(sched, axis)
    if sched.n == 1:
        return x
    if sched.algorithm == "ring":
        return _ring_allreduce(x, axis, sched.n, sched.segments,
                               stage_impl=stage_impl, wire=stage_wire)
    if sched.algorithm == "doubling":
        if sched.n & (sched.n - 1):
            # fold/unfold needs rank-asymmetric control flow, which SPMD
            # lowering cannot express — the fused node is the honest
            # equivalent (same dataflow position, XLA picks the rounds).
            return lax.psum(x, (axis,))
        return _butterfly_allreduce(x, axis, sched.n,
                                    stage_impl=stage_impl)
    raise ValueError(f"cannot lower algorithm {sched.algorithm!r}")


def _ring_allreduce(x: jax.Array, axis: str, n: int, segments: int,
                    stage_impl: Optional[str] = None,
                    wire: Optional[str] = None) -> jax.Array:
    """Ring allreduce as ``2(n-1)·S`` explicit ppermute rounds.

    Mirrors the host schedule chunk-for-chunk: reduce-scatter rounds send
    chunk ``(r-1-k) % n`` and combine into ``(r-2-k) % n``; allgather
    rounds forward chunk ``(r-k) % n``.  With ``segments=S > 1`` the
    per-segment chains carry no cross-segment dependencies, so XLA's
    scheduler overlaps segment ``k+1`` transport with segment ``k``
    combine — the pipelined schedule at Level B.

    With ``stage_impl`` the per-round combine runs as ONE fused kernel
    pass; with ``wire`` the transport additionally travels in the narrow
    dtype — int8 rounds ppermute the quantised chunk plus its scalar
    scale, and the allgather leg quantises each reduced chunk once at its
    owner and dequantises everywhere at the end (all ranks decode the
    same wire bytes, so results stay cross-rank bit-identical).
    """
    idx = lax.axis_index(axis)
    orig_shape, orig_dtype = x.shape, x.dtype
    flat = x.reshape(-1)
    m = flat.shape[0]
    pieces = n * segments
    pad = (-m) % pieces
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    chunks = flat.reshape(n, segments, -1)
    fwd = [(i, (i + 1) % n) for i in range(n)]
    for k in range(n - 1):              # reduce-scatter leg
        for s in range(segments):
            src_c = (idx - 1 - k) % n
            send = jnp.take(chunks[:, s], src_c, axis=0)
            tgt = (idx - 2 - k) % n
            if stage_impl is None:
                got = lax.ppermute(send, axis, fwd)
                chunks = chunks.at[tgt, s].add(got)
                continue
            gscale = None
            if wire == "int8":
                q, scale = kernel_ops.quantize_stage(send,
                                                     impl=stage_impl)
                send = q
                gscale = lax.ppermute(scale, axis, fwd)
            elif wire == "bf16":
                send = send.astype(jnp.bfloat16)
            got = lax.ppermute(send, axis, fwd)
            row = jnp.take(chunks[:, s], tgt, axis=0)
            new = kernel_ops.combine_stage(row, got, gscale,
                                           impl=stage_impl)
            chunks = chunks.at[tgt, s].set(new)
    if wire is None:
        for k in range(n - 1):          # allgather leg
            for s in range(segments):
                src_c = (idx - k) % n
                got = lax.ppermute(jnp.take(chunks[:, s], src_c, axis=0),
                                   axis, fwd)
                tgt = (idx - k - 1) % n
                chunks = chunks.at[tgt, s].set(got)
    else:
        # Allgather leg in wire dtype: each rank owns reduced chunk
        # ``idx`` after the RS leg — quantise it ONCE, forward the wire
        # payload (+ scale) around the ring, then dequantise every chunk
        # (own included) so all ranks decode identical wire bytes.
        wdt = _WIRE_DTYPES[wire]
        wchunks = jnp.zeros(chunks.shape, wdt)
        scales = jnp.zeros((n, segments), jnp.float32)
        for s in range(segments):
            own = jnp.take(chunks[:, s], idx, axis=0)
            if wire == "int8":
                q, scale = kernel_ops.quantize_stage(own, impl=stage_impl)
            else:
                q, scale = own.astype(wdt), jnp.float32(1.0)
            wchunks = wchunks.at[idx, s].set(q)
            scales = scales.at[idx, s].set(scale)
        for k in range(n - 1):
            for s in range(segments):
                src_c = (idx - k) % n
                got = lax.ppermute(jnp.take(wchunks[:, s], src_c, axis=0),
                                   axis, fwd)
                gscale = lax.ppermute(jnp.take(scales[:, s], src_c,
                                               axis=0), axis, fwd)
                tgt = (idx - k - 1) % n
                wchunks = wchunks.at[tgt, s].set(got)
                scales = scales.at[tgt, s].set(gscale)
        rows = []
        for i in range(n):
            segs = []
            for s in range(segments):
                segs.append(kernel_ops.combine_stage(
                    chunks[i, s], wchunks[i, s],
                    scales[i, s] if wire == "int8" else None,
                    accumulate=False, impl=stage_impl))
            rows.append(jnp.stack(segs))
        chunks = jnp.stack(rows)
    out = chunks.reshape(-1)
    if pad:
        out = out[:m]
    return out.reshape(orig_shape).astype(orig_dtype)


def _hierarchical_allreduce(sched: Schedule, x: jax.Array, axes: Axes,
                            stage_impl: Optional[str] = None
                            ) -> jax.Array:
    """Lower a :func:`repro.core.schedule.build_hierarchical` schedule
    over two mesh axes.

    Mirrors the schedule stage-for-stage: ``intra-1`` reduce-scatter
    ppermute rounds along the intra axis (send chunk ``(l-1-k) % n_i``,
    combine into ``(l-2-k) % n_i`` — identical indexing to the host
    programs), the inter allreduce of the owned chunk (butterfly rounds
    along the inter axis for power-of-two pod counts, else one fused
    ``lax.psum`` — the same trade the flat non-power-of-two doubling
    makes), and ``intra-1`` allgather rounds back.  Must run inside
    ``shard_map`` manual over both axes, passed in the schedule's
    major→minor ``(inter, intra)`` order.
    """
    inter_axis, intra_axis = _two_axes(axes)
    sizes = dict(sched.axes)
    n_e, n_i = sizes["inter"], sizes["intra"]
    if (lax.axis_size(inter_axis), lax.axis_size(intra_axis)) != (n_e, n_i):
        raise ValueError(
            f"schedule is for an (inter={n_e}) × (intra={n_i}) grid but "
            f"axes ({inter_axis!r}, {intra_axis!r}) have sizes "
            f"({lax.axis_size(inter_axis)}, {lax.axis_size(intra_axis)})")
    if n_e * n_i == 1:
        return x
    orig_shape, orig_dtype = x.shape, x.dtype
    flat = x.reshape(-1)
    m = flat.shape[0]
    pad = (-m) % n_i
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    chunks = flat.reshape(n_i, -1)
    li = lax.axis_index(intra_axis)
    fwd = [(i, (i + 1) % n_i) for i in range(n_i)]
    for k in range(n_i - 1):            # stage 1: intra reduce-scatter
        got = lax.ppermute(jnp.take(chunks, (li - 1 - k) % n_i, axis=0),
                           intra_axis, fwd)
        tgt = (li - 2 - k) % n_i
        if stage_impl is None:
            chunks = chunks.at[tgt].add(got)
        else:
            row = jnp.take(chunks, tgt, axis=0)
            chunks = chunks.at[tgt].set(
                kernel_ops.combine_stage(row, got, impl=stage_impl))
    own = jnp.take(chunks, li % n_i, axis=0)
    if n_e > 1:                         # stage 2: inter allreduce
        if n_e & (n_e - 1):
            own = lax.psum(own, (inter_axis,))
        else:
            own = _butterfly_allreduce(own, inter_axis, n_e,
                                       stage_impl=stage_impl)
    chunks = chunks.at[li % n_i].set(own)
    for k in range(n_i - 1):            # stage 3: intra allgather
        got = lax.ppermute(jnp.take(chunks, (li - k) % n_i, axis=0),
                           intra_axis, fwd)
        chunks = chunks.at[(li - k - 1) % n_i].set(got)
    out = chunks.reshape(-1)
    if pad:
        out = out[:m]
    return out.reshape(orig_shape).astype(orig_dtype)


def _butterfly_allreduce(x: jax.Array, axis: str, n: int,
                         stage_impl: Optional[str] = None) -> jax.Array:
    """Recursive doubling as ``log2 n`` bidirectional ppermute rounds
    (power-of-two rank counts)."""
    acc = x
    mask = 1
    while mask < n:
        perm = [(i, i ^ mask) for i in range(n)]
        got = lax.ppermute(acc, axis, perm)
        if stage_impl is None:
            acc = acc + got
        else:
            acc = kernel_ops.combine_stage(acc, got, impl=stage_impl)
        mask <<= 1
    return acc


# ---------------------------------------------------------------------------
# Neighbourhood lowering
# ---------------------------------------------------------------------------
def lower_neighbor(sched: Schedule, sends: Dict[Any, jax.Array],
                   axis_name: str) -> Dict[Any, jax.Array]:
    """Lower a neighbourhood schedule to one ppermute per direction.

    ``sends[d]`` is this shard's outgoing payload toward direction ``d``
    (every shard passes the same dict — SPMD); the result maps each
    direction to the payload received *from* the neighbour in that
    direction.  The permutation pairs are read off the schedule's
    transfers, so non-periodic boundary ranks — which have no pair —
    receive ``ppermute``'s zeros: the halo zero-fill falls out of the
    schedule structure instead of explicit masking.
    """
    if sched.output_kind != "dirs":
        raise ValueError("lower_neighbor needs a neighbourhood schedule "
                         "(build_neighbor)")
    _check_world(sched, axis_name)
    by_dir: Dict[Any, list] = {}
    for t in sched.transfers():
        _, d = t.src_buf            # ("s", direction)
        by_dir.setdefault(d, []).append((t.src, t.dst))
    out: Dict[Any, jax.Array] = {}
    for d, payload in sends.items():
        pairs = sorted(by_dir.get(d, []))
        opp = (d[0], -d[1])
        if not pairs:               # degenerate grid: no such edge at all
            out[opp] = jnp.zeros_like(payload)
            continue
        out[opp] = lax.ppermute(payload, axis_name, pairs)
    return out


def chain_topology(n: int) -> Tuple[Tuple[Tuple[Tuple[int, int], int],
                                          ...], ...]:
    """1-D non-periodic chain topology (row decomposition), the shape
    :meth:`repro.core.tac.CartGroup.topology` produces for
    ``cart_create((n,))``."""
    topo = []
    for r in range(n):
        dirs = []
        if r > 0:
            dirs.append(((0, -1), r - 1))
        if r < n - 1:
            dirs.append(((0, 1), r + 1))
        topo.append(tuple(dirs))
    return tuple(topo)
