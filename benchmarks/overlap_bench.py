"""Gradient-synchronisation schedule benchmark (Level-B TAMPI adaptation).

Compares the three in-graph communication schedules (core/overlap.py over
core/lowering.py): ``fused`` (fork-join analogue), ``bucketed`` (interop
analogue) and ``sentinel`` (artificial serialisation) on a real LM train
step, plus the **hierarchical two-axis allreduce** (one
``schedule.build_hierarchical`` IR object lowered over an
(inter × intra) mesh) against the flat ring and the fused native node:

* REAL execution wall time on the local mesh (the module forces 8 host
  devices so the (2 × 4) two-axis mesh is real; CPU backend restriction
  documented in tests/test_distributed.py);
* structural collective counts from the pre-optimisation StableHLO;
* **α-β predicted times** from the schedule IR
  (`repro.core.schedule.Schedule.cost`) under the NOMINAL constants
  below, plus the linear **cost features** — critical-path rounds ``R``,
  one-port wire bytes ``W``, one-port combine bytes ``V`` — next to every
  measurement, so ``tools/calibrate.py`` can least-squares fit
  CALIBRATED α/β/γ (+ per-call overhead) from the same file and the
  bench-smoke CI job can gate on measured-vs-calibrated-predicted drift
  against the committed ``BENCH_baseline.json``.  Reporting both
  predictions is what makes the gate compare like with like: the nominal
  constants under-predict wall time by 20–70× on this host (they model a
  production interconnect, and ``measured_s`` includes the whole step),
  while the calibrated fit absorbs machine speed and per-call overhead.

Plus the host-runtime **progress leg** (``bench_progress``): the
continuation engine (`repro.core.continuations.ContinuationEngine`)
swept over in-flight event-bound op counts — its per-completion cost
stays flat, asserted hard (a notification regression fails the job)
and recorded with cost features so the calibrated gate covers it.

CSV: name,us_per_call,derived
"""

from __future__ import annotations

import os
import re
import sys

# The two-axis hierarchical leg needs a real (inter × intra) device grid;
# force 8 host devices BEFORE jax initialises (same flag the lowering
# tests use in subprocesses).  Harmless for the 1-core legs.  A
# preexisting smaller count can't be overridden once set by the caller's
# environment — reject it up front instead of failing opaquely at mesh
# construction.
_FLAG = "--xla_force_host_platform_device_count"
_m = re.search(_FLAG + r"=(\d+)", os.environ.get("XLA_FLAGS", ""))
if _m is None:
    os.environ["XLA_FLAGS"] = \
        (os.environ.get("XLA_FLAGS", "") + f" {_FLAG}=8").strip()
elif int(_m.group(1)) < 8:
    raise SystemExit(
        f"overlap_bench needs >= 8 host devices for the two-axis mesh; "
        f"XLA_FLAGS already pins {_m.group(0)} — unset it or raise it")

import contextlib
import json
import pathlib
import time

import jax
import jax.numpy as jnp
from jax import lax

from repro import configs, optim
from repro.kernels import ops as kernel_ops
from repro.core import lowering
from repro.core import schedule as schedule_ir
from repro.core import tac
from repro.core.collectives import (Collectives, CollectiveHandle,
                                    ProgressEngine, _Machine)
from repro.core.continuations import ContinuationEngine
from repro.core.overlap import _make_buckets
from repro.models import inputs
from repro.runtime import steps
from repro.runtime.sharding import ShardingPolicy
from repro.launch.mesh import make_mesh

# Nominal host-interconnect model for the predicted times (per-message
# latency, seconds per byte on the wire, combine seconds per byte).
ALPHA, BETA, GAMMA = 5e-6, 1e-9, 2.5e-10
REF_RANKS = 8               # predicted times quoted for an 8-way DP mesh
INTER, INTRA = 2, 4         # the two-axis (pod × data) bench grid


def features(sched: schedule_ir.Schedule, size: float) -> dict:
    """Linear α-β(-γ) cost features of one schedule at one payload size.

    ``cost(α, β, size, γ) ≈ α·rounds + β·wire_bytes + γ·combine_bytes``
    with each term read off the DAG in isolation — the linearisation
    ``tools/calibrate.py`` fits measured times against.  (The exact DAG
    cost may be below the sum where transport overlaps combines; the
    bench and the calibrator use the SAME linear form, so the gate is
    self-consistent.)
    """
    return {"rounds": sched.cost(1.0, 0.0, 0.0),
            "wire_bytes": sched.cost(0.0, 1.0, size),
            "combine_bytes": sched.cost(0.0, 0.0, size, gamma=1.0)}


def _sum_features(fs) -> dict:
    out = {"rounds": 0.0, "wire_bytes": 0.0, "combine_bytes": 0.0}
    for f in fs:
        for k in out:
            out[k] += f[k]
    return out


def predict(mode: str, leaf_bytes: list, bucket_bytes: int,
            n: int = REF_RANKS) -> dict:
    """α-β predicted seconds for one grad-sync under a given schedule.

    Buckets come from the SAME greedy bucketing the real step uses
    (`repro.core.overlap._make_buckets` over the actual per-leaf wire
    bytes), each bucket's algorithm/segment count from the IR's own
    selection (`repro.core.schedule.best_schedule`); the mode decides how
    bucket costs compose: one fused node, overlapped buckets (max —
    dependencies alone order them), or sentinel-serialised buckets (sum).
    The composed linear ``features`` follow the same rule (argmax bucket
    for the overlapped modes, sum for sentinel).
    """
    total = sum(leaf_bytes)
    if mode == "fused":
        bucket_sizes = [total]
    else:
        buckets = _make_buckets(leaf_bytes, bucket_bytes)
        bucket_sizes = [sum(leaf_bytes[i] for i in b) for b in buckets]
    costs, feats, algs, segs = [], [], set(), set()
    for sz in bucket_sizes:
        sched = schedule_ir.best_schedule("allreduce", n, sz,
                                          alpha=ALPHA, beta=BETA,
                                          gamma=GAMMA)
        costs.append(sched.cost(ALPHA, BETA, sz, gamma=GAMMA))
        feats.append(features(sched, sz))
        algs.add(sched.algorithm)
        segs.add(sched.segments)
    if mode == "sentinel":
        cost, feat = sum(costs), _sum_features(feats)
    else:
        cost = max(costs)
        feat = feats[costs.index(cost)]
    return {"predicted_s": cost, "features": feat,
            "algorithms": sorted(algs),
            "segments": sorted(segs), "n_buckets": len(bucket_sizes),
            "bucket_bytes_max": max(bucket_sizes), "ref_ranks": n}


N_BATCHES = 5       # timing batches per leg; the median batch is reported


def _median(samples) -> float:
    return sorted(samples)[len(samples) // 2]


def _time_call(fn, arg, reps: int) -> float:
    """Median of ``N_BATCHES`` timed batches of ``reps`` calls each.

    A single timing batch on a shared runner can sit 2×+ off the steady
    state (neighbor noise, frequency ramps); the drift gate compares
    per-row ratios against a committed baseline, so noise containment
    here is what gives the ×tolerance its headroom.
    """
    out = fn(arg)                       # warmup / compile
    jax.block_until_ready(out)
    samples = []
    for _ in range(N_BATCHES):
        t0 = time.monotonic()
        for _ in range(reps):
            out = fn(arg)
        jax.block_until_ready(out)
        samples.append((time.monotonic() - t0) / reps)
    return _median(samples)


def bench_hierarchical(reps: int, elems: int) -> dict:
    """The two-axis leg: hierarchical vs flat ring vs fused native psum.

    One `repro.core.schedule.build_hierarchical` schedule drives the
    (INTER × INTRA) lowering; the flat ring runs the same payload over a
    single 8-way axis; ``native`` is one fused psum over both axes.
    Each entry carries the nominal predicted seconds and the linear cost
    features for the calibration fit.
    """
    from jax.sharding import PartitionSpec as P
    n = INTER * INTRA
    mesh2d = make_mesh((INTER, INTRA), ("pod", "data"))
    mesh1d = make_mesh((n,), ("data",))
    nbytes = elems * 4
    x = jax.random.normal(jax.random.PRNGKey(3), (n * elems,))

    def lowered(mesh, axes, **kw):
        def f(xl):
            return lowering.allreduce(xl, axes, **kw)
        spec = P(tuple(mesh.axis_names))
        return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=spec, out_specs=P(),
                                 axis_names=set(mesh.axis_names),
                                 check_vma=False))

    hier_sched = schedule_ir.build_hierarchical(INTRA, INTER)
    ring_sched = schedule_ir.build("allreduce", "ring", n)
    legs = {
        "hierarchical": (lowered(mesh2d, ("pod", "data"),
                                 algorithm="hierarchical"), hier_sched),
        "flat_ring": (lowered(mesh1d, ("data",), algorithm="ring"),
                      ring_sched),
        "native": (lowered(mesh2d, ("pod", "data")), None),
    }
    report = {"inter": INTER, "intra": INTRA, "payload_bytes": nbytes}
    for name, (fn, sched) in legs.items():
        txt = fn.lower(x).as_text()
        entry = {"measured_s": _time_call(fn, x, reps),
                 "collective_permutes": txt.count("collective_permute"),
                 "all_reduces": txt.count("all_reduce")}
        if sched is not None:
            entry["predicted_s"] = sched.cost(ALPHA, BETA, nbytes,
                                              gamma=GAMMA)
            entry["features"] = features(sched, nbytes)
        report[name] = entry
    return report


# ---------------------------------------------------------------------------
# Fused-stage microbench: the Pallas executor tier vs unfused elementwise
# ---------------------------------------------------------------------------
# Analytic HBM traffic of one reduce-scatter combine stage, bytes per
# element: the fused kernel reads the wire payload and the fp32
# accumulator and writes the result ONCE; the unfused path additionally
# materialises the fp32 copy of the cast/dequantised wire payload (one
# write + one read-back).  fp32 has no cast stage, so fused == unfused
# there — the ≤0.6× bytes claim is about the narrow-wire legs.
_ACC_B = 4
_WIRE_B = {"fp32": 4, "bf16": 2, "int8": 1}
STAGE_MAX_FUSED_RATIO = 0.6


def stage_hbm_bytes(wire: str, elems: int, fused: bool) -> float:
    per = _WIRE_B[wire] + 2 * _ACC_B          # read got + read acc + write
    if not fused and wire != "fp32":
        per += 2 * _ACC_B                     # fp32 temp: write + read back
    return float(per * elems)


def gs_stage_hbm_bytes(h: int, w: int, fused: bool) -> float:
    # fused: one read of the block + one write of the update (residual
    # and edges accumulate in-register; O(H+W) edge bytes are noise).
    # unfused: the residual pass re-reads BOTH the new and the old block.
    per = 2 * _ACC_B if fused else 4 * _ACC_B
    return float(per * h * w)


def bench_stages(smoke: bool = False) -> dict:
    """Fused vs unfused collective-stage legs (the tentpole's bench gate).

    Each wire dtype is timed both ways on the same payload: ``unfused``
    materialises the cast/dequant intermediate behind an
    ``optimization_barrier`` (the separate-pass shape XLA emits when the
    stages stay distinct HLO ops), ``fused`` is the single-pass
    :func:`repro.kernels.ops.combine_stage` (the jnp oracle off-TPU — one
    XLA fusion — and the Pallas kernel on TPU).  Rows carry the ANALYTIC
    per-stage HBM bytes as their ``combine_bytes`` feature under the
    ``stage:*`` overhead classes, so ``tools/calibrate.py`` fits a
    per-stage γ (seconds per HBM byte) with per-variant intercepts, and
    the drift gate tracks both variants.  HARD ASSERTS fused bytes ≤
    ``STAGE_MAX_FUSED_RATIO`` × unfused on every narrow-wire leg, and the
    same for the fused Gauss–Seidel stencil stage.
    """
    elems = 1 << 18 if smoke else 1 << 20
    reps = 3 if smoke else 10
    key = jax.random.PRNGKey(7)
    acc = jax.random.normal(key, (elems,), jnp.float32)
    got32 = jax.random.normal(jax.random.PRNGKey(8), (elems,), jnp.float32)
    report: dict = {"elems": elems, "max_fused_ratio": STAGE_MAX_FUSED_RATIO}

    def wire_payload(wire):
        if wire == "bf16":
            return got32.astype(jnp.bfloat16), None
        if wire == "int8":
            return kernel_ops.quantize_stage(got32, impl="ref")
        return got32, None

    def unfused_fn(wire):
        def f(args):
            a, g, s = args
            if wire == "int8":
                deq = g.astype(jnp.float32) * s
            elif wire == "bf16":
                deq = g.astype(jnp.float32)
            else:
                deq = g
            # keep the cast a SEPARATE materialised pass — the unfused
            # HLO shape (without this, XLA fuses and measures the fused
            # path twice).
            deq, a2 = lax.optimization_barrier((deq, a))
            return a2 + deq
        return jax.jit(f)

    def fused_fn(wire):
        def f(args):
            a, g, s = args
            return kernel_ops.combine_stage(a, g, s, impl="ref")
        return jax.jit(f)

    for wire in ("fp32", "bf16", "int8"):
        g, s = wire_payload(wire)
        arg = (acc, g, s)
        for variant, fn in (("unfused", unfused_fn(wire)),
                            ("fused", fused_fn(wire))):
            hbm = stage_hbm_bytes(wire, elems, variant == "fused")
            report[f"combine_{wire}_{variant}"] = {
                "measured_s": _time_call(fn, arg, reps),
                "hbm_bytes": hbm,
                "features": {"rounds": 0.0, "wire_bytes": 0.0,
                             "combine_bytes": hbm},
                "overhead_class": f"stage:{variant}",
            }
        ratio = (report[f"combine_{wire}_fused"]["hbm_bytes"]
                 / report[f"combine_{wire}_unfused"]["hbm_bytes"])
        report[f"combine_{wire}_fused"]["bytes_ratio"] = ratio
        if wire != "fp32" and ratio > STAGE_MAX_FUSED_RATIO:
            raise SystemExit(
                f"fused {wire} combine stage lost its bytes win: "
                f"{ratio:.2f}x unfused (max {STAGE_MAX_FUSED_RATIO})")

    # Gauss–Seidel stencil stage: update + residual + boundary-pack in
    # one pass vs the update-then-re-read shape.
    H = W = 256 if smoke else 512
    blk = jax.random.normal(key, (H, W), jnp.float32)
    edges = (jax.random.normal(key, (W,), jnp.float32),
             jax.random.normal(key, (H,), jnp.float32),
             jax.random.normal(key, (W,), jnp.float32),
             jax.random.normal(key, (H,), jnp.float32))

    def gs_unfused(args):
        b, (t, l, bt, r) = args
        up = jnp.concatenate([t[None, :], b[:-1]], axis=0)
        down = jnp.concatenate([b[1:], bt[None, :]], axis=0)
        left = jnp.concatenate([l[:, None], b[:, :-1]], axis=1)
        right = jnp.concatenate([b[:, 1:], r[:, None]], axis=1)
        new = 0.25 * (up + down + left + right)
        new2, b2 = lax.optimization_barrier((new, b))   # separate passes
        res = jnp.sum(jnp.abs(new2 - b2))
        return new, res, (new2[0], new2[-1], new2[:, 0], new2[:, -1])

    def gs_fused(args):
        b, (t, l, bt, r) = args
        return kernel_ops.gs_stencil(b, t, l, bt, r, impl="ref")

    for variant, fn in (("unfused", jax.jit(gs_unfused)),
                        ("fused", jax.jit(gs_fused))):
        hbm = gs_stage_hbm_bytes(H, W, variant == "fused")
        report[f"gs_stencil_{variant}"] = {
            "measured_s": _time_call(fn, (blk, (edges[0], edges[1],
                                                edges[2], edges[3])), reps),
            "hbm_bytes": hbm,
            "features": {"rounds": 0.0, "wire_bytes": 0.0,
                         "combine_bytes": hbm},
            "overhead_class": f"stage:{variant}",
        }
    gs_ratio = (report["gs_stencil_fused"]["hbm_bytes"]
                / report["gs_stencil_unfused"]["hbm_bytes"])
    report["gs_stencil_fused"]["bytes_ratio"] = gs_ratio
    if gs_ratio > STAGE_MAX_FUSED_RATIO:
        raise SystemExit(f"fused stencil stage lost its bytes win: "
                         f"{gs_ratio:.2f}x unfused")
    return report


def bench_lowered_stages(reps: int, elems: int) -> dict:
    """Level-B fused-vs-unfused legs: the SAME flat-ring allreduce
    lowered with and without the fused stage tier (plus the bf16-wire
    variant), measured on the real 8-device mesh.  The wire-bytes
    feature of the bf16 leg is halved — the narrow transport the fused
    dequant-combine makes free."""
    from jax.sharding import PartitionSpec as P
    n = REF_RANKS
    mesh = make_mesh((n,), ("data",))
    nbytes = elems * 4
    x = jax.random.normal(jax.random.PRNGKey(5), (n * elems,))
    sched = schedule_ir.build("allreduce", "ring", n)
    base_feat = features(sched, nbytes)

    def lowered(**kw):
        def f(xl):
            return lowering.allreduce(xl, ("data",), algorithm="ring",
                                      **kw)
        return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("data"),
                                 out_specs=P(), axis_names={"data"},
                                 check_vma=False))

    legs = {
        "unfused": (lowered(), dict(base_feat)),
        "fused": (lowered(stage_impl="ref"), dict(base_feat)),
        "fused_bf16": (lowered(stage_impl="ref", wire="bf16"),
                       dict(base_feat,
                            wire_bytes=base_feat["wire_bytes"] / 2)),
    }
    report = {"ranks": n, "payload_bytes": nbytes}
    for name, (fn, feat) in legs.items():
        txt = fn.lower(x).as_text()
        report[name] = {
            "measured_s": _time_call(fn, x, reps),
            "collective_permutes": txt.count("collective_permute"),
            "features": feat,
        }
    return report


def bench_inter(reps: int, elems: int) -> dict:
    """Inter-axis (pod-level) butterfly legs: measured rows under the
    ``inter:butterfly`` overhead class, so the calibration fit carries a
    separate ``inter`` α/β family — the two-tier constants
    ``best_schedule`` uses to cost ``build_hierarchical`` candidates
    (``algorithm="auto"`` over two-level topologies)."""
    from jax.sharding import PartitionSpec as P
    report: dict = {"payload_bytes": elems * 4}
    for n_pods, shape in ((2, (2, 4)), (4, (4, 2))):
        mesh = make_mesh(shape, ("pod", "data"))
        rounds = n_pods.bit_length() - 1

        def f(xl):
            return lowering._butterfly_allreduce(xl, "pod", n_pods)
        sf = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("pod"),
                               out_specs=P("pod"),
                               axis_names={"pod", "data"},
                               check_vma=False))
        x = jax.random.normal(jax.random.PRNGKey(9), (n_pods * elems,))
        nbytes = elems * 4
        report[f"butterfly_{n_pods}pods"] = {
            "measured_s": _time_call(sf, x, reps),
            "features": {"rounds": float(rounds),
                         "wire_bytes": float(rounds * nbytes),
                         "combine_bytes": float(rounds * nbytes)},
            "overhead_class": "inter:butterfly",
        }
    return report


# ---------------------------------------------------------------------------
# Level-A executor microbench: compiled vs interpreted schedule programs
# ---------------------------------------------------------------------------
LEVEL_A_RANKS = 8
LEVEL_A_ALGORITHMS = ("ring", "doubling")
LEVEL_A_ELEMS = (64, 1 << 14)       # float64 payloads: 512 B and 128 KiB
# raw small-payload regression guard: compiled may never be SLOWER than
# the interpreter it replaces (the calibrated ≤0.5× overhead bar lives
# in tools/calibrate.py, where shared per-transfer cost is factored
# out — raw wall time is transport-dominated, so the raw ratio only
# needs to catch a fast path that stopped being fast).
LEVEL_A_MAX_SMALL_RATIO = 0.98


def serial_features(sched: schedule_ir.Schedule, size: float) -> dict:
    """Linear cost features of one schedule under a SERIAL driver.

    ``Collectives.run_group`` drives every rank's program round-robin on
    one thread, so wall time tracks the schedule's TOTAL work — transfers
    executed, bytes moved, bytes combined, summed over all ranks — not
    the one-port critical path :func:`features` reads off the DAG for the
    overlapped XLA legs.  α then fits the per-transfer host transport
    cost (shared by both executors on the same wire) and each executor
    class's ``overhead`` intercept absorbs its per-call fixed cost.
    """
    rounds = wire = combine = 0.0
    for prog in sched.programs:
        for op in prog:
            if isinstance(op, schedule_ir.Send):
                rounds += 1.0
                wire += op.frac * size
            elif isinstance(op, schedule_ir.Combine):
                combine += op.frac * size
    return {"rounds": rounds, "wire_bytes": wire, "combine_bytes": combine}


def bench_level_a(smoke: bool = False) -> dict:
    """The executor leg: compiled per-rank programs vs the interpreter.

    The SAME host collectives (8-rank allreduce over a ``CommWorld``,
    both wire-compatible executors) timed under the serial group driver
    at two payload sizes × two algorithms, rows tagged with
    ``overhead_class`` so ``tools/calibrate.py`` fits a separate
    per-call overhead constant per executor (α/β/γ shared).  HARD
    ASSERTS the raw small-payload win — a compiled-path regression fails
    the bench-smoke job before the calibrated gate even runs.
    """
    import numpy as np
    n = LEVEL_A_RANKS
    reps = 10 if smoke else 30
    report: dict = {"ranks": n, "reps": reps,
                    "compiled": {}, "interpreted": {}}
    small = {"compiled": 0.0, "interpreted": 0.0}

    def runner(executor, algorithm, elems):
        world = tac.CommWorld(n)
        coll = Collectives(world, executor=executor)
        kw = [{"value": np.arange(elems, dtype=np.float64) + r}
              for r in range(n)]
        return lambda _: coll.run_group("allreduce", kw,
                                        algorithm=algorithm)

    # Executors INTERLEAVED per configuration (and one untimed warm pass
    # first): allocator/code warmup and neighbor noise hit both classes
    # alike, so the compiled/interpreted ratio stays honest even when
    # absolute times wander.
    for algorithm in LEVEL_A_ALGORITHMS:
        for elems in LEVEL_A_ELEMS:
            for executor in ("compiled", "interpreted"):
                runner(executor, algorithm, elems)(None)
    for algorithm in LEVEL_A_ALGORITHMS:
        for elems in LEVEL_A_ELEMS:
            sched = schedule_ir.build("allreduce", algorithm, n)
            nbytes = elems * 8
            for executor in ("compiled", "interpreted"):
                # _time_call's warmup call also compiles + caches the
                # per-rank programs: steady-state timing for both.
                dt = _time_call(runner(executor, algorithm, elems),
                                None, reps)
                report[executor][f"{algorithm}_{elems}"] = {
                    "algorithm": algorithm, "payload_bytes": nbytes,
                    "measured_s": dt,
                    "features": serial_features(sched, nbytes),
                    "overhead_class": f"level_a:{executor}",
                }
                if elems == LEVEL_A_ELEMS[0]:
                    small[executor] += dt
    ratio = small["compiled"] / small["interpreted"]
    report["small_payload_ratio"] = ratio
    if ratio > LEVEL_A_MAX_SMALL_RATIO:
        raise SystemExit(
            f"compiled executor lost its small-payload win: "
            f"compiled/interpreted = {ratio:.2f} "
            f"(max {LEVEL_A_MAX_SMALL_RATIO}); the per-call fast path "
            f"regressed")
    return report


# ---------------------------------------------------------------------------
# Progress-path microbench: continuation notification over an in-flight sweep
# ---------------------------------------------------------------------------
IN_FLIGHT_SWEEP = (8, 16, 32, 64)
PROGRESS_OPS_BUDGET = 20_000    # ~progress ops per timed batch (noise floor)
DISPATCH_S = 1e-6               # nominal per-dispatch model constant


def _progress_setup(n: int):
    """Arm ``n`` in-flight event-bound machines on a fresh engine.

    Returns ``(handles, drain, counters)``: ``drain()`` completes one
    handle per tick and services the engine once per completion (the
    steady-state shape), ``counters()`` reads the progress-op totals
    ``(tests, dispatches)`` — one queue dispatch per completion, flat in
    the in-flight count, and zero tests.
    """
    handles = [tac.EventHandle() for _ in range(n)]

    def gen(h):
        res = yield h
        return res

    engine = ContinuationEngine()
    eng = ProgressEngine(engine)
    for h in handles:
        eng.submit(_Machine(gen(h), CollectiveHandle()))

    def drain():
        for i, h in enumerate(handles):
            h.complete(i)
            engine.service(None)
        if eng.pending:
            raise SystemExit(f"progress bench: {eng.pending} machines "
                             f"stuck")

    def counters():
        return engine.stats["tests"], engine.stats["dispatches"]
    return handles, drain, counters


def bench_progress(smoke: bool = False) -> dict:
    """The continuation leg: progress cost over an in-flight sweep.

    Every row carries linear cost features (``rounds`` = progress ops —
    handle tests + callback dispatches) beside ``measured_s`` so
    ``tools/calibrate.py`` fits the per-op cost and the CI gate covers
    it, plus the nominal prediction ``DISPATCH_S × completions``.  The
    function HARD ASSERTS the scaling claim — progress ops per
    completion flat (sub-linear in the in-flight count) — so a
    notification regression fails the bench-smoke job outright.
    """
    budget = 4_000 if smoke else PROGRESS_OPS_BUDGET
    report: dict = {"sweep": list(IN_FLIGHT_SWEEP), "dispatch_s": DISPATCH_S}
    rows = {}
    for n in IN_FLIGHT_SWEEP:
        _, drain, counters = _progress_setup(n)
        drain()
        tests, dispatches = counters()          # deterministic totals
        ops = tests + dispatches
        reps = max(1, budget // max(ops, 1))
        samples = []
        for _ in range(N_BATCHES):
            # arm outside the clock: measured_s is the PROGRESS cost
            # (completion + notification), not machine setup.
            drains = [_progress_setup(n)[1] for _ in range(reps)]
            t0 = time.monotonic()
            for d in drains:
                d()
            samples.append((time.monotonic() - t0) / reps)
        rows[f"inflight_{n}"] = {
            "in_flight": n, "completions": n, "tests": tests,
            "dispatches": dispatches,
            "ops_per_completion": ops / n,
            "measured_s": _median(samples),
            "predicted_s": DISPATCH_S * n,
            "features": {"rounds": float(ops), "wire_bytes": 0.0,
                         "combine_bytes": 0.0},
        }
    report["continuation"] = rows
    lo, hi = min(IN_FLIGHT_SWEEP), max(IN_FLIGHT_SWEEP)
    per = {n: rows[f"inflight_{n}"]["ops_per_completion"]
           for n in IN_FLIGHT_SWEEP}
    if max(per.values()) > 2.0 or per[hi] > 1.5 * per[lo]:
        raise SystemExit(
            f"continuation progress cost is NOT flat in in-flight ops: "
            f"ops/completion {per} (expected O(1) dispatches per "
            f"completion)")
    return report


# ---------------------------------------------------------------------------
# Observability sentinel: the disabled tracer must be (near-)free
# ---------------------------------------------------------------------------
OBS_MAX_OVERHEAD = 0.02     # NullTracer guard cost budget: ≤ 2% of hot path
OBS_GUARD_LOOPS = 1_000_000


def bench_obs(smoke: bool = False) -> dict:
    """The ``repro.obs`` overhead sentinel: tracing off must cost ≤ 2%.

    Every instrumentation site in the runtime is guarded by one module
    attribute read (``if trace.TRACING: ...``).  This leg bounds that
    cost on the hottest instrumented path (the continuation-engine
    drain of ``bench_progress``): it counts the guarded emissions one
    drain performs under a real tracer, measures the per-check guard
    cost with tracing disabled, and HARD ASSERTS
    ``emissions × guard_cost ≤ OBS_MAX_OVERHEAD × drain_time`` — the
    NullTracer overhead an untraced run pays for carrying the
    instrumentation.  Rows ``obs.null`` / ``obs.active`` (measured drain
    time without/with an active tracer, event counts as the ``rounds``
    feature) feed the calibrated drift gate under the ``obs`` scope.
    """
    from repro import obs
    from repro.obs import trace as _tr

    n = max(IN_FLIGHT_SWEEP)
    reps = 20 if smoke else 50

    # (1) guarded emissions per drain, counted under a real tracer.
    with obs.tracing() as tr:
        _, drain, counters = _progress_setup(n)
        drain()
        n_events = len(tr.events())
        tests, dispatches = counters()
    if n_events == 0:
        raise SystemExit("obs sentinel: a traced drain emitted no events "
                         "— the instrumentation went dead")

    # (2) per-check guard cost on the disabled path (tracing is off
    # here, so the loop body is exactly what every untraced site pays).
    # The empty-loop baseline is subtracted so the number is the
    # attribute read itself, not the timing loop around it; the 0.5 ns
    # floor keeps the bound honest when the subtraction lands in noise.
    assert not _tr.TRACING
    hits = 0
    t0 = time.monotonic()
    for _ in range(OBS_GUARD_LOOPS):
        if _tr.TRACING:
            hits += 1      # pragma: no cover - tracing is off
    t_guarded = time.monotonic() - t0
    t0 = time.monotonic()
    for _ in range(OBS_GUARD_LOOPS):
        pass
    t_empty = time.monotonic() - t0
    guard_s = max((t_guarded - t_empty) / OBS_GUARD_LOOPS, 0.5e-9)
    assert hits == 0

    # (3) the drain itself, untraced (NullTracer) and traced.
    def timed(active: bool) -> float:
        samples = []
        for _ in range(N_BATCHES):
            with contextlib.ExitStack() as stack:
                if active:
                    stack.enter_context(obs.tracing())
                drains = [_progress_setup(n)[1] for _ in range(reps)]
                t0 = time.monotonic()
                for d in drains:
                    d()
                samples.append((time.monotonic() - t0) / reps)
        return _median(samples)

    t_null = timed(False)
    t_active = timed(True)
    overhead = n_events * guard_s / max(t_null, 1e-12)
    report = {
        "in_flight": n,
        "events_per_drain": n_events,
        "guard_ns": guard_s * 1e9,
        "overhead_fraction": overhead,
        "max_overhead": OBS_MAX_OVERHEAD,
        "null": {
            "measured_s": t_null,
            "features": {"rounds": float(tests + dispatches),
                         "wire_bytes": 0.0, "combine_bytes": 0.0},
            "overhead_class": "obs:null",
        },
        "active": {
            "measured_s": t_active,
            "events": n_events,
            "features": {"rounds": float(n_events), "wire_bytes": 0.0,
                         "combine_bytes": 0.0},
            "overhead_class": "obs:active",
        },
    }
    if overhead > OBS_MAX_OVERHEAD:
        raise SystemExit(
            f"obs sentinel: NullTracer overhead {overhead * 100:.2f}% of "
            f"the continuation drain exceeds the "
            f"{OBS_MAX_OVERHEAD * 100:.0f}% budget "
            f"({n_events} guarded sites × {guard_s * 1e9:.1f} ns vs "
            f"{t_null * 1e6:.1f} µs hot path) — an instrumentation site "
            f"stopped being guard-only")
    return report


def bench(print_fn=print, smoke: bool = False,
          json_path: str = "BENCH_overlap.json"):
    rows = []
    n_layers = 2 if smoke else 8
    reps = 2 if smoke else 5
    cfg = configs.smoke("granite_3_2b").scaled(dtype="float32",
                                               n_layers=n_layers)
    opt_cfg = optim.OptimConfig()
    key = jax.random.PRNGKey(0)
    state = steps.init_train_state(cfg, opt_cfg, key)
    batch = inputs.make_batch(cfg, batch=8, seq=64, key=key)
    abatch = jax.eval_shape(lambda: batch)
    # A REAL 8-way DP mesh (the module forces 8 host devices): the
    # measured all-reduces are genuine 8-rank collectives, so the
    # REF_RANKS=8 cost features describe the schedule that actually
    # executes and a bucketed/sentinel serialisation regression moves
    # measured_s.  (Pre-calibration this bench ran on a (1, 1) mesh,
    # where every mode's collective was a 1-rank no-op and the gate
    # would have tracked pure compute.)
    mesh = make_mesh((8, 1), ("data", "model"))
    bucket_bytes = 1 << 16
    # fp32 training: grads travel in their own (fp32) dtype, so the wire
    # bytes ARE size × itemsize — the same list sync_grads buckets by.
    leaf_bytes = [int(l.size) * l.dtype.itemsize
                  for l in jax.tree_util.tree_leaves(state.params)]
    grad_bytes = sum(leaf_bytes)

    report = {"alpha": ALPHA, "beta": BETA, "gamma": GAMMA,
              "grad_bytes": grad_bytes, "bucket_bytes": bucket_bytes,
              "modes": {}}
    for mode in ("fused", "bucketed", "sentinel"):
        policy = ShardingPolicy(fsdp=False, tp=False, sp=False, remat=None,
                                grad_sync=mode)
        with mesh:
            make = steps.build_train_step_manual(
                cfg, mesh, policy, opt_cfg, bucket_bytes=bucket_bytes)
            f = make(jax.eval_shape(lambda: state), abatch)
            lowered = f.lower(state, batch)
            txt = lowered.as_text()
            n_ar = txt.count("all_reduce")
            n_barrier = txt.count("optimization_barrier")
            compiled = lowered.compile()
            s, m = compiled(state, batch)          # warmup
            jax.block_until_ready(m["loss"])
            samples = []
            for _ in range(N_BATCHES):             # median batch (see
                t0 = time.monotonic()              # _time_call)
                for _ in range(reps):
                    s, m = compiled(s, batch)
                jax.block_until_ready(m["loss"])
                samples.append((time.monotonic() - t0) / reps)
            dt = _median(samples)
        rows.append((f"gradsync_{mode}", dt * 1e6,
                     f"all_reduces={n_ar};barriers={n_barrier}"))
        report["modes"][mode] = dict(
            predict(mode, leaf_bytes, bucket_bytes),
            measured_s=dt, all_reduces=n_ar, barriers=n_barrier)

    # hierarchical two-axis leg on the real (2 × 4) device grid; the
    # per-call cost is microseconds, so many reps cost nothing and keep
    # the gated ratios out of timer-resolution noise.
    hier = bench_hierarchical(max(reps * 5, 10),
                              elems=1 << 14 if smoke else 1 << 16)
    report["hierarchical"] = hier
    for name in ("hierarchical", "flat_ring", "native"):
        e = hier[name]
        rows.append((f"allreduce_{name}", e["measured_s"] * 1e6,
                     f"ppermutes={e['collective_permutes']};"
                     f"all_reduces={e['all_reduces']}"))

    # fused-stage legs (Pallas executor tier): per-stage HBM-bytes rows
    # for the stage:* γ fit, the hard ≤0.6× bytes assert, and the
    # Level-B fused-vs-unfused ring lowering on the real mesh.
    stages = bench_stages(smoke)
    report["stages"] = stages
    for name, e in stages.items():
        if isinstance(e, dict) and "measured_s" in e:
            rows.append((f"stage_{name}", e["measured_s"] * 1e6,
                         f"hbm_bytes={e['hbm_bytes']:.0f};"
                         f"class={e['overhead_class']}"))
    lowered_stages = bench_lowered_stages(max(reps * 5, 10),
                                          elems=1 << 14 if smoke
                                          else 1 << 16)
    report["lowered_stages"] = lowered_stages
    for name in ("unfused", "fused", "fused_bf16"):
        e = lowered_stages[name]
        rows.append((f"lowered_ring_{name}", e["measured_s"] * 1e6,
                     f"ppermutes={e['collective_permutes']}"))

    # inter-axis butterfly rows: the two-tier (inter family) constants
    # for hierarchical candidates under algorithm="auto".
    inter = bench_inter(max(reps * 5, 10), elems=1 << 14 if smoke
                        else 1 << 16)
    report["inter"] = inter
    for name, e in inter.items():
        if isinstance(e, dict) and "measured_s" in e:
            rows.append((f"inter_{name}", e["measured_s"] * 1e6,
                         f"rounds={e['features']['rounds']:.0f}"))

    # compiled vs interpreted schedule executors (Level-A host path):
    # per-executor overhead_class rows for the per-class calibration fit
    # (small-payload win hard-asserted)
    level_a = bench_level_a(smoke)
    report["level_a"] = level_a
    for executor in ("compiled", "interpreted"):
        for name, e in level_a[executor].items():
            rows.append((f"level_a_{executor}_{name}",
                         e["measured_s"] * 1e6,
                         f"payload_bytes={e['payload_bytes']};"
                         f"class={e['overhead_class']}"))

    # continuation notification: progress cost over an in-flight sweep
    # (flat per completion; hard-asserted)
    progress = bench_progress(smoke)
    report["progress"] = progress
    for n in IN_FLIGHT_SWEEP:
        e = progress["continuation"][f"inflight_{n}"]
        rows.append((f"progress_continuation_{n}", e["measured_s"] * 1e6,
                     f"tests={e['tests']};dispatches={e['dispatches']};"
                     f"ops_per_completion={e['ops_per_completion']:.2f}"))

    # observability sentinel: NullTracer guard cost bounded (hard assert)
    # + untraced/traced drain rows for the calibrated gate.
    obs_report = bench_obs(smoke)
    report["obs"] = obs_report
    for leg in ("null", "active"):
        e = obs_report[leg]
        rows.append((f"obs_{leg}", e["measured_s"] * 1e6,
                     f"rounds={e['features']['rounds']:.0f};"
                     f"class={e['overhead_class']}"))
    rows.append(("obs_overhead",
                 obs_report["overhead_fraction"] * 1e6,
                 f"fraction-ppm;max={OBS_MAX_OVERHEAD}"))

    # segmented vs unsegmented ring under the same model: the pipelining
    # claim the simulator verifies (tests/test_schedule.py) quoted here
    # for the bench report.
    un = schedule_ir.build("allreduce", "ring", REF_RANKS)
    seg = schedule_ir.build("allreduce", "ring", REF_RANKS, segments=4)
    report["segmented_ring"] = {
        "payload_bytes": grad_bytes,
        "unsegmented_s": un.cost(ALPHA, BETA, grad_bytes, gamma=GAMMA),
        "segments4_s": seg.cost(ALPHA, BETA, grad_bytes, gamma=GAMMA),
    }
    # the row namespaces this bench owns: tools/calibrate.py --gate
    # limits the missing-baseline-row check to these, so rows other
    # benches contribute to the shared baseline (serve.*) don't fail
    # an overlap-only run.
    report["gate_scope"] = ["modes", "hierarchical", "stages",
                           "lowered_stages", "inter", "level_a",
                           "progress", "obs"]
    pathlib.Path(json_path).write_text(json.dumps(report, indent=2))
    rows.append(("gradsync_predict_json", 0.0, json_path))
    for r in rows:
        print_fn(f"{r[0]},{r[1]:.1f},{r[2]}")
    return rows


if __name__ == "__main__":
    if "--obs" in sys.argv[1:]:
        # CI obs-smoke job: run ONLY the observability sentinel (the
        # NullTracer ≤ 2% hard assert) without the jax-heavy legs.
        out = bench_obs(smoke="--smoke" in sys.argv[1:])
        print(f"obs_null,{out['null']['measured_s'] * 1e6:.1f},"
              f"events={out['events_per_drain']}")
        print(f"obs_active,{out['active']['measured_s'] * 1e6:.1f},"
              f"events={out['events_per_drain']}")
        print(f"obs_overhead,{out['overhead_fraction'] * 1e6:.1f},"
              f"fraction-ppm;guard_ns={out['guard_ns']:.1f}")
    else:
        bench(smoke="--smoke" in sys.argv[1:])
