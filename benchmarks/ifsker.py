"""IFSKer benchmark — paper §7.2 (Fig. 14).

Mock-up of the IFS spectral-transform weather code: timestep cycles of
grid-point physics and Fourier transforms with a data transposition
(all-to-all) between phases.  Grid space distributes *points* across ranks;
spectral space distributes *fields*; the transitions redistribute.

Versions (as in the paper — Fork-Join/Sentinel are equivalent to Pure here
because there is one rank per core):

* ``pure``           — sequential phases with a full exchange between them.
* ``interop-blk``    — the transposition is a per-rank ``alltoall`` from
                       the task-aware collectives API in *blocking* mode
                       (pause/resume per round): the exchange overlaps
                       physics/FFTs of other ranks' tasks.
* ``interop-nonblk`` — the same ``alltoall`` in *event-bound* mode: the
                       exchange task finishes immediately, its dependency
                       release waits on the collective — the paper's
                       preferred mode for many small messages.

The data transposition (grid space ↔ spectral space) is exactly MPI's
all-to-all, so this benchmark is the collectives subsystem's end-to-end
exercise (core/collectives.py); the ``pure`` version drives the same
schedule sequentially through ``Collectives.run_group``.

Real executions validate numerics across versions; the simulator replays
the task DAGs for the scaling curve.  CSV: name,us_per_call,derived
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from repro.core import Collectives, TaskRuntime, tac
from repro.core import schedule as schedule_ir
from repro.core.simulate import Simulator, SimTask, COMPUTE, COMM_PAUSED, \
    COMM_EVENTS, COMM_HELD

VERSIONS = ("pure", "interop-blk", "interop-nonblk")


def physics(x: np.ndarray) -> np.ndarray:
    return x + 0.1 * np.tanh(x) - 0.01 * x ** 3


def spectral_step(f: np.ndarray) -> np.ndarray:
    F = np.fft.rfft(f)
    F *= np.exp(-0.01 * np.arange(F.size))   # diffusion in spectral space
    return np.fft.irfft(F, n=f.size)


def run_real(version: str, *, n_ranks: int = 2, workers: int = 2,
             n_fields: int = 8, n_grid: int = 64, steps: int = 3,
             seed: int = 0):
    """Returns (final fields array, runtime stats)."""
    assert n_fields % n_ranks == 0 and n_grid % n_ranks == 0
    rng = np.random.default_rng(seed)
    pts = n_grid // n_ranks
    # grid space: fields[f] split by points across ranks
    grid: Dict = {(f, r): rng.standard_normal(pts)
                  for f in range(n_fields) for r in range(n_ranks)}
    spec: Dict = {}
    world = tac.CommWorld(n_ranks)
    coll = Collectives(world)
    exch: Dict = {}   # alltoall results (or event-bound handles)
    tac.init(tac.TASK_MULTIPLE if version.startswith("interop")
             else tac.THREAD_MULTIPLE)
    rt = TaskRuntime(num_workers=workers)
    rt.start()

    def owner(f: int) -> int:
        return f % n_ranks

    fields_of = {o: [f for f in range(n_fields) if owner(f) == o]
                 for o in range(n_ranks)}
    a2a_mode = "event" if version == "interop-nonblk" else "blocking"

    def phys_task(f, r, it):
        grid[(f, r)] = physics(grid[(f, r)])

    def pack_g2s(r):
        """Block for owner o = my point-slices of o's fields, field order."""
        return [np.concatenate([grid[(f, r)] for f in fields_of[o]])
                for o in range(n_ranks)]

    def pack_s2g(o):
        """Block for rank r = r's point-slices of my fields, field order."""
        return [np.concatenate([spec[f][r * pts:(r + 1) * pts]
                                for f in fields_of[o]])
                for r in range(n_ranks)]

    # exch is keyed by rank only: iteration it+1's exchange task cannot
    # start before iteration it's readers finished (it is gated through
    # unpack → phys), so each slot is safely overwritten and peak memory
    # stays O(n_grid × n_fields) instead of growing with step count.
    def a2a_g2s(r, it):
        exch[("g2s", r)] = coll.alltoall(
            pack_g2s(r), rank=r, mode=a2a_mode, key=("g2s", it))

    def a2a_s2g(o, it):
        exch[("s2g", o)] = coll.alltoall(
            pack_s2g(o), rank=o, mode=a2a_mode, key=("s2g", it))

    def fft_field(f, it):
        o = owner(f)
        parts = exch[("g2s", o)]
        if isinstance(parts, tac.AsyncHandle):
            parts = parts.result
        j = fields_of[o].index(f)
        full = np.concatenate([parts[s][j * pts:(j + 1) * pts]
                               for s in range(n_ranks)])
        spec[f] = spectral_step(full)

    def unpack(r, it):
        parts = exch.pop(("s2g", r))
        if isinstance(parts, tac.AsyncHandle):
            parts = parts.result
        for o in range(n_ranks):
            for j, f in enumerate(fields_of[o]):
                grid[(f, r)] = parts[o][j * pts:(j + 1) * pts]

    for it in range(steps):
        if version == "pure":
            for f in range(n_fields):
                for r in range(n_ranks):
                    phys_task(f, r, it)
            g2s = coll.run_group(
                "alltoall", [{"blocks": pack_g2s(r)}
                             for r in range(n_ranks)], key=("g2s", it))
            for r in range(n_ranks):
                exch[("g2s", r)] = g2s[r]
            for f in range(n_fields):
                fft_field(f, it)
            s2g = coll.run_group(
                "alltoall", [{"blocks": pack_s2g(o)}
                             for o in range(n_ranks)], key=("s2g", it))
            for o in range(n_ranks):
                exch[("s2g", o)] = s2g[o]
            for r in range(n_ranks):
                unpack(r, it)
            continue

        for r in range(n_ranks):
            for f in range(n_fields):
                rt.submit(phys_task, f, r, it, inout=[("g", f, r)],
                          name=f"phys[{f},{r}]@{it}", label="compute",
                          rank=r)
        for r in range(n_ranks):
            rt.submit(a2a_g2s, r, it,
                      in_=[("g", f, r) for f in range(n_fields)],
                      out=[("xg", r, it)], label="comm",
                      name=f"a2a_g2s[{r}]@{it}", rank=r)
        for f in range(n_fields):
            rt.submit(fft_field, f, it, in_=[("xg", owner(f), it)],
                      out=[("s", f)], label="compute",
                      name=f"fft[{f}]@{it}", rank=owner(f))
        for o in range(n_ranks):
            rt.submit(a2a_s2g, o, it,
                      in_=[("s", f) for f in fields_of[o]],
                      out=[("xs", o, it)], label="comm",
                      name=f"a2a_s2g[{o}]@{it}", rank=o)
        for r in range(n_ranks):
            rt.submit(unpack, r, it, in_=[("xs", r, it)],
                      inout=[("g", f, r) for f in range(n_fields)],
                      label="compute", name=f"unp[{r}]@{it}", rank=r)

    rt.taskwait()
    stats = dict(rt.stats)
    rt.close()
    out = np.stack([np.concatenate([grid[(f, r)] for r in range(n_ranks)])
                    for f in range(n_fields)])
    return out, stats


# ---------------------------------------------------------------------------
# elastic execution: checkpoint / injected rank death / shrink / resume
# ---------------------------------------------------------------------------
def _elastic_step(comm, coll, fields: np.ndarray, *, mode, rt, it):
    """One IFSKer timestep of the global ``fields`` array over ``comm``.

    Works for ANY communicator size that divides both axes; physics and
    the spectral step are decomposition-independent, so the result is
    bitwise identical at every rank count — which is what lets a
    shrunken world resume a dead one's checkpoint exactly.
    """
    n_ranks = comm.size
    n_fields, n_grid = fields.shape
    pts = n_grid // n_ranks
    grid = {(f, r): fields[f, r * pts:(r + 1) * pts].copy()
            for f in range(n_fields) for r in range(n_ranks)}
    spec: Dict = {}
    exch: Dict = {}
    fields_of = {o: [f for f in range(n_fields) if f % n_ranks == o]
                 for o in range(n_ranks)}

    def phys_task(f, r):
        grid[(f, r)] = physics(grid[(f, r)])

    def a2a_g2s(r):
        blocks = [np.concatenate([grid[(f, r)] for f in fields_of[o]])
                  for o in range(n_ranks)]
        exch[("g2s", r)] = coll.alltoall(blocks, rank=r, mode=mode,
                                         key=("eg2s", it))

    def fft_field(f):
        o = f % n_ranks
        parts = exch[("g2s", o)]
        if isinstance(parts, tac.AsyncHandle):
            parts = parts.result
        j = fields_of[o].index(f)
        full = np.concatenate([parts[s][j * pts:(j + 1) * pts]
                               for s in range(n_ranks)])
        spec[f] = spectral_step(full)

    def a2a_s2g(o):
        blocks = [np.concatenate([spec[f][r * pts:(r + 1) * pts]
                                  for f in fields_of[o]])
                  for r in range(n_ranks)]
        exch[("s2g", o)] = coll.alltoall(blocks, rank=o, mode=mode,
                                         key=("es2g", it))

    def unpack(r):
        parts = exch[("s2g", r)]
        if isinstance(parts, tac.AsyncHandle):
            parts = parts.result
        for o in range(n_ranks):
            for j, f in enumerate(fields_of[o]):
                grid[(f, r)] = parts[o][j * pts:(j + 1) * pts]

    for r in range(n_ranks):
        for f in range(n_fields):
            rt.submit(phys_task, f, r, inout=[("g", f, r)],
                      name=f"ephys[{f},{r}]@{it}", label="compute",
                      rank=r)
    for r in range(n_ranks):
        rt.submit(a2a_g2s, r, in_=[("g", f, r) for f in range(n_fields)],
                  out=[("xg", r, it)], label="comm",
                  name=f"ea2a_g2s[{r}]@{it}", rank=r)
    for f in range(n_fields):
        rt.submit(fft_field, f, in_=[("xg", f % n_ranks, it)],
                  out=[("s", f)], label="compute", name=f"efft[{f}]@{it}",
                  rank=f % n_ranks)
    for o in range(n_ranks):
        rt.submit(a2a_s2g, o, in_=[("s", f) for f in fields_of[o]],
                  out=[("xs", o, it)], label="comm",
                  name=f"ea2a_s2g[{o}]@{it}", rank=o)
    for r in range(n_ranks):
        rt.submit(unpack, r, in_=[("xs", r, it)],
                  inout=[("g", f, r) for f in range(n_fields)],
                  label="compute", name=f"eunp[{r}]@{it}", rank=r)
    rt.taskwait()
    return np.stack([np.concatenate([grid[(f, r)]
                                     for r in range(n_ranks)])
                     for f in range(n_fields)])


def run_elastic(ckpt_dir: str, *, n_ranks: int = 4, workers: int = 2,
                n_fields: int = 12, n_grid: int = 24, steps: int = 4,
                kill_step: int = None, kill_rank: int = 0,
                kill_after_ops: int = 1, mode: str = "event",
                seed: int = 0):
    """Fault-tolerant IFSKer: checkpoint each step, survive an injected
    rank death mid-transposition, shrink, resume (see
    ``gauss_seidel.run_elastic`` for the recovery protocol).  The axes
    must divide every rank count the run may shrink to (defaults: 12
    fields / 24 points over 4 ranks survive the loss of one).

    Returns ``(final fields, info)``.
    """
    from repro import checkpoint as checkpoint_lib
    from repro.core import resilience
    from repro.core.executor import TaskError

    world = tac.CommWorld(n_ranks)
    injector = resilience.FaultInjector(world)
    tac.init(tac.TASK_MULTIPLE)

    step = checkpoint_lib.latest_step(ckpt_dir)
    if step is None:
        rng = np.random.default_rng(seed)
        fields = rng.standard_normal((n_fields, n_grid))
        checkpoint_lib.save_checkpoint(ckpt_dir, {"fields": fields}, 0)
        step = 0
    else:
        state, step = checkpoint_lib.restore_checkpoint(
            ckpt_dir, {"fields": np.empty((n_fields, n_grid))})
        fields = state["fields"]

    comm = world
    coll = Collectives(world)
    rt = TaskRuntime(num_workers=workers)
    rt.start()
    info = {"recoveries": []}

    try:
        while step < steps:
            it = step + 1
            if kill_step is not None and it == kill_step \
                    and not injector.killed:
                injector.arm(kill_rank, after_ops=kill_after_ops)
            try:
                fields = _elastic_step(comm, coll, fields, mode=mode,
                                       rt=rt, it=it)
            except TaskError:
                injector.disarm()
                rt.close()
                shrunk = resilience.recover(world)
                if n_fields % shrunk.size or n_grid % shrunk.size:
                    raise ValueError(
                        f"{n_fields} fields / {n_grid} points do not "
                        f"divide over {shrunk.size} survivors")
                comm, coll = shrunk, Collectives(shrunk)
                rt = TaskRuntime(num_workers=workers)
                rt.start()
                state, step = checkpoint_lib.restore_checkpoint(
                    ckpt_dir, {"fields": np.empty((n_fields, n_grid))})
                fields = state["fields"]
                info["recoveries"].append(
                    {"at_step": it, "killed": list(world.failed),
                     "survivors": comm.size, "resumed_step": step})
                continue
            step = it
            checkpoint_lib.save_checkpoint(ckpt_dir, {"fields": fields},
                                           step)
    finally:
        rt.close()
    info["size"] = comm.size
    return fields, info


# ---------------------------------------------------------------------------
# simulated scaling (Fig. 14)
# ---------------------------------------------------------------------------
def build_sim(version, *, n_ranks, n_fields=64, steps=6, t_phys=1.0,
              t_fft=1.0, t_comm=0.02, latency=0.05):
    """Replays the DAG the real versions now execute: per-rank ``alltoall``
    collective nodes for each transposition (g2s / s2g), with the waiting
    discipline of the version (held / paused / event-bound)."""
    tasks: List[SimTask] = []
    index: Dict[str, int] = {}

    def add(rank, cost, kind=COMPUTE, start=(), name="", group=None,
            group_latency=0.0):
        t = SimTask(len(tasks), rank, cost, kind=kind,
                    start_deps=[(index[s], 0.0) for s in start
                                if s and s in index],
                    name=name, group=group, group_latency=group_latency)
        tasks.append(t)
        index[name] = t.id

    kind = {"interop-blk": COMM_PAUSED,
            "interop-nonblk": COMM_EVENTS}.get(version, COMM_HELD)
    fl = n_fields // n_ranks  # fields per rank in spectral space
    tp = t_phys / fl          # physics cost per (field, rank) slice
    # pairwise all-to-all latency from the IR cost model (α = per-message
    # latency, wires free — equals the old rounds × latency count)
    a2a_lat = schedule_ir.build("alltoall", "ring", n_ranks).cost(
        latency, 0.0, 0)

    for it in range(steps):
        for r in range(n_ranks):
            for f in range(n_fields):
                add(r, tp, start=[f"unp[{r}]@{it - 1}"] if it else [],
                    name=f"phys[{f},{r}]@{it}")
        for r in range(n_ranks):
            add(r, t_comm, kind=kind,
                start=[f"phys[{f},{r}]@{it}" for f in range(n_fields)],
                group=f"g2s@{it}", group_latency=a2a_lat,
                name=f"a2a_g2s[{r}]@{it}")
        for f in range(n_fields):
            o = f % n_ranks
            add(o, t_fft / fl, start=[f"a2a_g2s[{o}]@{it}"],
                name=f"fft[{f}]@{it}")
        for o in range(n_ranks):
            add(o, t_comm, kind=kind,
                start=[f"fft[{f}]@{it}" for f in range(n_fields)
                       if f % n_ranks == o],
                group=f"s2g@{it}", group_latency=a2a_lat,
                name=f"a2a_s2g[{o}]@{it}")
        for r in range(n_ranks):
            add(r, t_comm, start=[f"a2a_s2g[{r}]@{it}"],
                name=f"unp[{r}]@{it}")
    return tasks


def simulate_version(version, *, n_ranks, workers=4, **kw):
    tasks = build_sim(version, n_ranks=n_ranks, **kw)
    sim = Simulator(n_ranks, 1 if version == "pure" else workers,
                    task_overhead=0.001, resume_overhead=0.005)
    return sim.run(tasks).makespan


def bench(print_fn=print):
    rows = []
    ref, _ = run_real("pure")
    for v in VERSIONS[1:]:
        out, stats = run_real(v)
        err = float(np.abs(out - ref).max())
        assert err < 1e-10, (v, err)

    # end-to-end legs: each interop version, same numerics as pure.
    for v in VERSIONS[1:]:
        t0 = time.monotonic()
        out, _ = run_real(v)
        dt = (time.monotonic() - t0) / 3
        assert float(np.abs(out - ref).max()) < 1e-10, v
        rows.append((f"ifsker_e2e_{v}", dt * 1e6, "e2e"))

    for v in VERSIONS:
        t0 = time.monotonic()
        _, stats = run_real(v)
        dt = (time.monotonic() - t0) / 3
        rows.append((f"ifsker_real_{v}", dt * 1e6,
                     f"blocks={stats.get('task_blocks', 0)}"))

    base = simulate_version("pure", n_ranks=1)
    for v in VERSIONS:
        for n in (1, 2, 4, 8, 16):
            mk = simulate_version(v, n_ranks=n)
            rows.append((f"ifsker_sim_{v}_r{n}", mk * 1e6,
                         f"speedup={base / mk:.2f}"))
    for r in rows:
        print_fn(f"{r[0]},{r[1]:.1f},{r[2]}")
    return rows


# ---------------------------------------------------------------------------
# traced leg: Perfetto timeline for the transposition pipeline
# ---------------------------------------------------------------------------
def run_traced(trace_path: str, *, print_fn=print):
    """``--trace`` leg: one interop-nonblk run under the tracer.

    Exports the task/handle/collective timeline of the event-bound
    transposition pipeline as Perfetto JSON with the per-rank overlap
    fractions and straggler scores in ``otherData``; exits non-zero if
    the document violates ``repro.obs.SPAN_SCHEMA``.
    """
    from repro import obs

    with obs.tracing(capacity=1 << 18) as tr:
        run_real("interop-nonblk", n_ranks=2, workers=2,
                 n_fields=8, n_grid=128, steps=3)
        events = tr.events()
    overlap = obs.overlap_fraction(events)
    doc = obs.export_trace(trace_path, events=events, extra={
        "benchmark": "ifsker",
        "overlap_fraction": overlap,
        "per_rank_overlap": {str(r): f for r, f in
                             obs.per_rank_overlap(events).items()},
        "straggler_scores": {str(r): s for r, s in
                             obs.straggler_scores(events).items()},
    })
    obs.assert_valid_trace(doc)
    print_fn(f"ifsker_trace_overlap,{overlap * 1e6:.1f},"
             f"overlap-fraction-ppm")
    print_fn(f"ifsker_trace_events,{len(events)},file={trace_path}")
    return overlap


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(description="IFSKer benchmark (paper §7.2)")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="run the traced interop-nonblk leg and write "
                         "Perfetto JSON here (skips the plain bench)")
    ns = ap.parse_args()
    if ns.trace:
        run_traced(ns.trace)
    else:
        bench()
