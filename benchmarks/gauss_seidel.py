"""Gauss–Seidel / heat-equation benchmark — paper §7.1 (Figs. 9–13).

Five versions of the blocked Gauss–Seidel iteration, mirroring the paper:

* ``pure``            — sequential compute per rank, sequential halo
                        exchange between iterations (Pure MPI).
* ``forkjoin``        — parallel compute tasks; sequential communication
                        phase in the main thread; a taskwait barrier per
                        iteration.
* ``sentinel``        — taskified communication serialised by an artificial
                        sentinel dependency (what you must write WITHOUT
                        TASK_MULTIPLE, §6.3): one chained task drives each
                        whole halo round and the residual collective.
* ``interop-blk``     — TAMPI blocking mode: per-rank halo tasks use
                        task-aware waits (pause/resume); no artificial
                        dependencies.
* ``interop-nonblk``  — TAMPI non-blocking mode: per-rank halo tasks bind
                        the exchange to their event counter and finish
                        immediately; boundary compute declares dependencies.

Communication structure (since the sub-communicator PR): ranks form a 2-D
Cartesian grid (``CommWorld.cart_create``) and each rank owns a tile of
``nby × nbx`` blocks.  The per-block point-to-point wiring of the previous
revision is replaced by ONE :class:`~repro.core.collectives.HaloExchange`
round per rank per iteration — boundary rows/columns travel as single
per-neighbour messages.  Cross-rank coupling therefore uses iteration
``t-1`` data in every version (the classic halo-exchange hybrid:
Gauss–Seidel wavefront *inside* a rank, Jacobi coupling *across* ranks),
which is what makes the whole exchange postable at once.  The per-iteration
global residual runs through the hierarchical allreduce
(:class:`~repro.core.collectives.HierarchicalCollectives` — intra-row
chain + inter-leader doubling over two nested groups built by
``CommWorld.split``).

Measurements: (a) REAL execution on the host task runtime at small scale
(all versions must agree numerically); (b) deterministic makespans of the
same task DAGs under the paper's machine model (core/simulate.py) — the
scaling curves.  Halo rounds appear in the simulated graphs as
neighbourhood nodes (``SimTask(neighbors=...)``).  CSV schema:
name,us_per_call,derived
"""

from __future__ import annotations

import math
import time
from typing import Dict, List, Tuple

import numpy as np

from repro.core import (Collectives, HaloExchange, HierarchicalCollectives,
                        TaskRuntime, tac)
from repro.core.simulate import (Simulator, SimTask, COMPUTE, COMM_HELD,
                                 COMM_PAUSED, COMM_EVENTS)

VERSIONS = ("pure", "forkjoin", "sentinel", "interop-blk", "interop-nonblk")


def grid_dims(n_ranks: int) -> Tuple[int, int]:
    """Most-square 2-D factorization of ``n_ranks`` (py >= px)."""
    for d in range(int(math.isqrt(n_ranks)), 0, -1):
        if n_ranks % d == 0:
            return (n_ranks // d, d)
    return (n_ranks, 1)


def edge_blocks(cart, nby, nbx, r, d):
    """Block coordinates of rank ``r``'s tile edge facing direction ``d``.

    The single source of boundary geometry shared by the real execution
    (halo payloads, task deps) and the simulated graph — an edit here
    changes both sides together.
    """
    ry, rx = cart.coords(r)
    dim, disp = d
    if dim == 0:
        gy = ry * nby if disp < 0 else (ry + 1) * nby - 1
        return [(gy, rx * nbx + j) for j in range(nbx)]
    gx = rx * nbx if disp < 0 else (rx + 1) * nbx - 1
    return [(ry * nby + i, gx) for i in range(nby)]


def gs_block(block, top, left, bottom, right):
    padded = np.pad(block, 1)
    padded[0, 1:-1] = top
    padded[-1, 1:-1] = bottom
    padded[1:-1, 0] = left
    padded[1:-1, -1] = right
    return 0.25 * (padded[:-2, 1:-1] + padded[2:, 1:-1]
                   + padded[1:-1, :-2] + padded[1:-1, 2:])


# ---------------------------------------------------------------------------
# real execution on the host runtime
# ---------------------------------------------------------------------------
def run_real(version: str, *, n_ranks: int = 4, workers: int = 2,
             nby: int = 2, nbx: int = 2, bs: int = 16, iters: int = 3,
             seed: int = 0, block_impl: str = None):
    """Returns (final grid, stats).

    ``block_impl`` routes the per-block stage through the fused stencil
    kernel (:func:`repro.kernels.ops.gs_stencil`;
    "ref"/"pallas_interpret"/"pallas"): ONE pass over the block produces
    the interior update, the rank-local residual contribution AND the
    four packed boundary edges — the halo payloads and residual sums are
    then read from the per-block caches instead of re-slicing and
    re-reading the grids (the unfused path's extra passes).  The kernel
    computes in fp32; ``None`` keeps the float64 numpy path bit-exact.

    Dataflow: grids[it][gy][gx]; block (gy,gx) at iteration it reads
    up/left from iteration it when the neighbour block is on the SAME
    rank (spatial wavefront) and self/down/right from it-1; every
    cross-rank side reads the neighbour rank's it-1 boundary, delivered
    by that iteration's halo exchange.
    """
    if block_impl is not None:
        import jax.numpy as jnp
        from repro.kernels import ops as kernel_ops
    py, px = grid_dims(n_ranks)
    NYb, NXb = py * nby, px * nbx
    rng = np.random.default_rng(seed)
    grids: Dict[int, list] = {
        0: [[rng.standard_normal((bs, bs)) for _ in range(NXb)]
            for _ in range(NYb)]}
    for it in range(1, iters + 1):
        grids[it] = [[None] * NXb for _ in range(NYb)]
    zeros = np.zeros(bs)

    world = tac.CommWorld(n_ranks)
    cart = world.cart_create((py, px))
    hx = HaloExchange(cart)
    hier = HierarchicalCollectives(world, px)   # intra-row + leader column
    # persistent residual allreduce (MPI_Allreduce_init analogue): the
    # three-stage hierarchical schedule is resolved once and re-posted
    # every iteration with key=("res", it).
    residual_coll = hier.persistent(op="sum")
    halos: Dict = {}       # (rank, it) -> {direction: edge} | handle
    residuals: Dict = {}   # (rank, it) -> float | CollectiveHandle
    res_cache: Dict = {}   # (gy, gx, it) -> fused per-block residual
    edge_cache: Dict = {}  # (gy, gx, it) -> (top, bottom, left, right)
    tac.init(tac.TASK_MULTIPLE if version.startswith("interop")
             else tac.THREAD_MULTIPLE)
    rt = TaskRuntime(num_workers=workers)
    rt.start()

    def rank_of(gy, gx):
        return cart.rank_at((gy // nby, gx // nbx))

    def packed_edge(gy, gx, it, d):
        """A boundary edge from the fused kernel's boundary-pack output."""
        te, be, le, re = edge_cache[(gy, gx, it)]
        dim, disp = d
        if dim == 0:
            return te if disp < 0 else be
        return le if disp < 0 else re

    def halo_sends(r, it):
        """Outgoing it-1 boundary edges, one concatenated array per
        neighbour direction.  On the fused path the edges come packed
        from the stencil kernel's boundary outputs (no grid re-slice);
        iteration 0 has no kernel pass, so its edges slice the initial
        grid as usual."""
        out = {}
        for d, _ in hx.neighbors(r):
            cells = edge_blocks(cart, nby, nbx, r, d)
            if block_impl is not None and \
                    (cells[0] + (it - 1,)) in edge_cache:
                out[d] = np.concatenate(
                    [packed_edge(gy, gx, it - 1, d) for gy, gx in cells])
                continue
            dim, disp = d
            edge = 0 if disp < 0 else -1
            out[d] = np.concatenate(
                [grids[it - 1][gy][gx][edge, :].copy() if dim == 0
                 else grids[it - 1][gy][gx][:, edge].copy()
                 for gy, gx in cells])
        return out

    def boundary_blocks(r):
        """Block coordinates whose it-1 data feeds r's outgoing halos."""
        keys = set()
        for d, _ in hx.neighbors(r):
            keys.update(edge_blocks(cart, nby, nbx, r, d))
        return sorted(keys)

    def halo_edge(r, it, d, offset):
        h = halos[(r, it)]
        if isinstance(h, tac.AsyncHandle):
            h = h.result
        return h[d][offset * bs:(offset + 1) * bs]

    def compute_block(gy, gx, it):
        r = rank_of(gy, gx)
        ry, rx = gy // nby, gx // nbx
        g_cur, g_prev = grids[it], grids[it - 1]
        if gy == 0:
            top = zeros
        elif gy % nby == 0:
            top = halo_edge(r, it, (0, -1), gx - rx * nbx)
        else:
            top = g_cur[gy - 1][gx][-1, :]
        if gx == 0:
            left = zeros
        elif gx % nbx == 0:
            left = halo_edge(r, it, (1, -1), gy - ry * nby)
        else:
            left = g_cur[gy][gx - 1][:, -1]
        if gy == NYb - 1:
            bottom = zeros
        elif (gy + 1) % nby == 0:
            bottom = halo_edge(r, it, (0, 1), gx - rx * nbx)
        else:
            bottom = g_prev[gy + 1][gx][0, :]
        if gx == NXb - 1:
            right = zeros
        elif (gx + 1) % nbx == 0:
            right = halo_edge(r, it, (1, 1), gy - ry * nby)
        else:
            right = g_prev[gy][gx + 1][:, 0]
        if block_impl is None:
            grids[it][gy][gx] = gs_block(g_prev[gy][gx], top, left,
                                         bottom, right)
            return
        new, res, edges = kernel_ops.gs_stencil(
            jnp.asarray(g_prev[gy][gx], jnp.float32),
            jnp.asarray(top, jnp.float32), jnp.asarray(left, jnp.float32),
            jnp.asarray(bottom, jnp.float32),
            jnp.asarray(right, jnp.float32), impl=block_impl)
        grids[it][gy][gx] = np.asarray(new, np.float64)
        res_cache[(gy, gx, it)] = float(res)
        edge_cache[(gy, gx, it)] = tuple(np.asarray(e, np.float64)
                                         for e in edges)

    def block_deps(gy, gx, it):
        """Region deps for the compute task (task versions only)."""
        r = rank_of(gy, gx)
        deps = [("blk", gy, gx, it - 1)]
        crosses = False
        if gy > 0:
            if gy % nby:
                deps.append(("blk", gy - 1, gx, it))
            else:
                crosses = True
        if gx > 0:
            if gx % nbx:
                deps.append(("blk", gy, gx - 1, it))
            else:
                crosses = True
        if gy < NYb - 1:
            if (gy + 1) % nby:
                deps.append(("blk", gy + 1, gx, it - 1))
            else:
                crosses = True
        if gx < NXb - 1:
            if (gx + 1) % nbx:
                deps.append(("blk", gy, gx + 1, it - 1))
            else:
                crosses = True
        if crosses:
            deps.append(("halo", r, it))
        return deps

    def local_residual(r, it):
        ry, rx = cart.coords(r)
        tot = 0.0
        for gy in range(ry * nby, (ry + 1) * nby):
            for gx in range(rx * nbx, (rx + 1) * nbx):
                if block_impl is not None:
                    # fused path: the kernel already produced the
                    # per-block |new - old| sum — no grid re-read.
                    tot += res_cache[(gy, gx, it)]
                else:
                    tot += float(np.abs(grids[it][gy][gx]
                                        - grids[it - 1][gy][gx]).sum())
        return np.float64(tot)

    for it in range(1, iters + 1):
        # ---- halo phase --------------------------------------------------
        if version in ("pure", "forkjoin"):
            if version == "forkjoin":
                rt.taskwait()   # barrier: previous iteration fully done
            got = hx.run_group([halo_sends(r, it) for r in range(n_ranks)],
                               key=("h", it))
            for r in range(n_ranks):
                halos[(r, it)] = got[r]
        elif version == "sentinel":
            # Without TASK_MULTIPLE a blocking halo round inside per-rank
            # tasks would deadlock (§5) — the whole neighbourhood
            # collective is serialised into the sentinel chain instead.
            def halo_group(it2=it):
                got = hx.run_group(
                    [halo_sends(r, it2) for r in range(n_ranks)],
                    key=("h", it2))
                for r in range(n_ranks):
                    halos[(r, it2)] = got[r]
            rt.submit(halo_group,
                      in_=[("blk", gy, gx, it - 1)
                           for r in range(n_ranks)
                           for gy, gx in boundary_blocks(r)],
                      out=[("halo", r, it) for r in range(n_ranks)],
                      inout=[("comm-sentinel",)], label="comm",
                      name=f"halo@{it}")
        else:
            mode = "event" if version == "interop-nonblk" else "blocking"

            def halo_task(r, it2=it, mode=mode):
                def body():
                    halos[(r, it2)] = hx.start(halo_sends(r, it2), rank=r,
                                               mode=mode, key=("h", it2))
                return body
            for r in range(n_ranks):
                rt.submit(halo_task(r),
                          in_=[("blk", gy, gx, it - 1)
                               for gy, gx in boundary_blocks(r)],
                          out=[("halo", r, it)], label="comm",
                          name=f"halo[{r}]@{it}", rank=r)

        # ---- compute phase (intra-rank wavefront) ------------------------
        for gy in range(NYb):
            for gx in range(NXb):
                if version == "pure":
                    compute_block(gy, gx, it)
                else:
                    rt.submit(compute_block, gy, gx, it,
                              out=[("blk", gy, gx, it)],
                              in_=block_deps(gy, gx, it),
                              label="compute", name=f"c[{gy},{gx}]@{it}",
                              rank=rank_of(gy, gx))

        # ---- global residual: hierarchical allreduce ---------------------
        if version in ("pure", "forkjoin"):
            if version == "forkjoin":
                rt.taskwait()       # fork-join: iteration fully done
            vals = residual_coll.run_group(
                [local_residual(r, it) for r in range(n_ranks)],
                key=("res", it))
            for r in range(n_ranks):
                residuals[(r, it)] = float(vals[r])
        elif version == "sentinel":
            def res_group(it2=it):
                vals = residual_coll.run_group(
                    [local_residual(r, it2) for r in range(n_ranks)],
                    key=("res", it2))
                for r in range(n_ranks):
                    residuals[(r, it2)] = float(vals[r])
            rt.submit(res_group,
                      in_=[("blk", gy, gx, it) for gy in range(NYb)
                           for gx in range(NXb)],
                      inout=[("comm-sentinel",)], label="comm",
                      name=f"res@{it}")
        else:
            for r in range(n_ranks):
                def res_task(r=r, it2=it):
                    v = local_residual(r, it2)
                    if version == "interop-nonblk":
                        residuals[(r, it2)] = residual_coll.start(
                            v, rank=r, mode="event", key=("res", it2))
                    else:
                        residuals[(r, it2)] = float(residual_coll.start(
                            v, rank=r, mode="blocking", key=("res", it2)))
                ry, rx = cart.coords(r)
                rt.submit(res_task,
                          in_=[("blk", gy, gx, it)
                               for gy in range(ry * nby, (ry + 1) * nby)
                               for gx in range(rx * nbx, (rx + 1) * nbx)],
                          label="comm", name=f"res[{r}]@{it}", rank=r)

    rt.taskwait()
    stats = dict(rt.stats)
    # Resolve event-bound handles and check every rank saw the same value.
    res_by_it: Dict[int, float] = {}
    for (r, it), v in sorted(residuals.items()):
        if isinstance(v, tac.AsyncHandle):
            v = float(v.result)
        prev = res_by_it.setdefault(it, v)
        assert abs(prev - v) < 1e-9, ("residual disagreement", it, prev, v)
    stats["residuals"] = res_by_it
    rt.close()
    return np.block(grids[iters]), stats


# ---------------------------------------------------------------------------
# elastic execution: checkpoint / injected rank death / shrink / resume
# ---------------------------------------------------------------------------
def _blocks_of(grid: np.ndarray, NYb: int, NXb: int, bs: int):
    """Re-block a global grid into the benchmark's NYb x NXb tile list."""
    return [[grid[gy * bs:(gy + 1) * bs, gx * bs:(gx + 1) * bs].copy()
             for gx in range(NXb)] for gy in range(NYb)]


def _elastic_iteration(cart, hx, coll, prev, *, nby, nbx, bs, mode, rt, it):
    """One halo-coupled Gauss–Seidel iteration over ANY decomposition.

    ``prev`` is the previous iteration's global block list; returns
    ``(next block list, global residual)``.  Per-rank halo tasks post the
    neighbourhood exchange in TAMPI mode ``mode``; per-rank residual
    tasks run the allreduce over the (possibly shrunken) communicator.
    An injected rank death surfaces here as
    :class:`~repro.core.executor.TaskError` out of the taskwait — the
    machine that observes the dead peer revokes the communicator, so
    every surviving task fails promptly instead of parking.
    """
    n_ranks = cart.size
    NYb, NXb = len(prev), len(prev[0])
    cur = [[None] * NXb for _ in range(NYb)]
    zeros = np.zeros(bs)
    halos: Dict[int, object] = {}
    res: Dict[int, object] = {}

    def rank_of(gy, gx):
        return cart.rank_at((gy // nby, gx // nbx))

    def halo_sends(r):
        out = {}
        for d, _ in hx.neighbors(r):
            dim, disp = d
            edge = 0 if disp < 0 else -1
            out[d] = np.concatenate(
                [prev[gy][gx][edge, :].copy() if dim == 0
                 else prev[gy][gx][:, edge].copy()
                 for gy, gx in edge_blocks(cart, nby, nbx, r, d)])
        return out

    def halo_edge(r, d, offset):
        h = halos[r]
        if isinstance(h, tac.AsyncHandle):
            h = h.result
        return h[d][offset * bs:(offset + 1) * bs]

    def compute_block(gy, gx):
        r = rank_of(gy, gx)
        ry, rx = gy // nby, gx // nbx
        if gy == 0:
            top = zeros
        elif gy % nby == 0:
            top = halo_edge(r, (0, -1), gx - rx * nbx)
        else:
            top = cur[gy - 1][gx][-1, :]
        if gx == 0:
            left = zeros
        elif gx % nbx == 0:
            left = halo_edge(r, (1, -1), gy - ry * nby)
        else:
            left = cur[gy][gx - 1][:, -1]
        if gy == NYb - 1:
            bottom = zeros
        elif (gy + 1) % nby == 0:
            bottom = halo_edge(r, (0, 1), gx - rx * nbx)
        else:
            bottom = prev[gy + 1][gx][0, :]
        if gx == NXb - 1:
            right = zeros
        elif (gx + 1) % nbx == 0:
            right = halo_edge(r, (1, 1), gy - ry * nby)
        else:
            right = prev[gy][gx + 1][:, 0]
        cur[gy][gx] = gs_block(prev[gy][gx], top, left, bottom, right)

    def halo_task(r):
        def body():
            halos[r] = hx.start(halo_sends(r), rank=r, mode=mode,
                                key=("eh", it))
        return body

    for r in range(n_ranks):
        rt.submit(halo_task(r), out=[("halo", r, it)], label="comm",
                  name=f"ehalo[{r}]@{it}", rank=r)
    for gy in range(NYb):
        for gx in range(NXb):
            r = rank_of(gy, gx)
            deps = [("halo", r, it)]
            if gy % nby:
                deps.append(("blk", gy - 1, gx, it))
            if gx % nbx:
                deps.append(("blk", gy, gx - 1, it))
            rt.submit(compute_block, gy, gx, in_=deps,
                      out=[("blk", gy, gx, it)], label="compute",
                      name=f"ec[{gy},{gx}]@{it}", rank=r)
    for r in range(n_ranks):
        def res_task(r=r):
            ry, rx = cart.coords(r)
            tot = np.float64(sum(
                float(np.abs(cur[gy][gx] - prev[gy][gx]).sum())
                for gy in range(ry * nby, (ry + 1) * nby)
                for gx in range(rx * nbx, (rx + 1) * nbx)))
            res[r] = coll.allreduce(tot, rank=r, mode=mode, key=("er", it))
        ry, rx = cart.coords(r)
        rt.submit(res_task,
                  in_=[("blk", gy, gx, it)
                       for gy in range(ry * nby, (ry + 1) * nby)
                       for gx in range(rx * nbx, (rx + 1) * nbx)],
                  label="comm", name=f"eres[{r}]@{it}", rank=r)
    rt.taskwait()
    vals = {r: float(v.result if isinstance(v, tac.AsyncHandle) else v)
            for r, v in res.items()}
    first = next(iter(vals.values()))
    assert all(abs(v - first) < 1e-9 for v in vals.values()), vals
    return cur, first


def run_elastic(ckpt_dir: str, *, n_ranks: int = 4, workers: int = 2,
                nby: int = 3, nbx: int = 3, bs: int = 8, iters: int = 4,
                kill_iter: int = None, kill_rank: int = 0,
                kill_after_ops: int = 1, mode: str = "event",
                seed: int = 0):
    """Fault-tolerant Gauss–Seidel: the ULFM recovery loop end to end.

    Every completed iteration checkpoints the global grid to
    ``ckpt_dir`` (mesh-agnostic — the restore side may re-decompose).
    With ``kill_iter`` set, a :class:`~repro.core.resilience.FaultInjector`
    arms rank ``kill_rank`` to die at its ``kill_after_ops``-th posted
    operation of that iteration (mid-halo / mid-collective); the failure
    surfaces out of the taskwait, the survivors revoke + shrink
    (:func:`repro.core.resilience.recover`), re-shape as a fresh
    Cartesian grid over whatever decomposition divides the global blocks,
    and resume from the last completed checkpoint step.  If a run starts
    with checkpoints already in ``ckpt_dir`` it resumes from the latest
    (which is how the parity test builds its clean reference).

    Returns ``(final grid, info)`` where ``info`` records the residual
    per completed step, the surviving decomposition, and each recovery.
    """
    from repro import checkpoint as checkpoint_lib
    from repro.core import resilience
    from repro.core.executor import TaskError

    py, px = grid_dims(n_ranks)
    NYb, NXb = py * nby, px * nbx
    world = tac.CommWorld(n_ranks)
    injector = resilience.FaultInjector(world)
    tac.init(tac.TASK_MULTIPLE)

    step = checkpoint_lib.latest_step(ckpt_dir)
    if step is None:
        rng = np.random.default_rng(seed)
        grid = np.block([[rng.standard_normal((bs, bs))
                          for _ in range(NXb)] for _ in range(NYb)])
        checkpoint_lib.save_checkpoint(ckpt_dir, {"grid": grid}, 0)
        step = 0
    else:
        state, step = checkpoint_lib.restore_checkpoint(
            ckpt_dir, {"grid": np.empty((NYb * bs, NXb * bs))})
        grid = state["grid"]

    def shape_over(group_or_world, n):
        spy, spx = grid_dims(n)
        if NYb % spy or NXb % spx:
            raise ValueError(f"global {NYb}x{NXb} blocks do not divide "
                             f"over {n} survivors ({spy}x{spx})")
        cart = (group_or_world.cart((spy, spx))
                if hasattr(group_or_world, "cart")
                else group_or_world.cart_create((spy, spx)))
        return cart, NYb // spy, NXb // spx

    cart, cur_nby, cur_nbx = shape_over(world, n_ranks)
    hx, coll = HaloExchange(cart), Collectives(cart)
    rt = TaskRuntime(num_workers=workers)
    rt.start()
    info = {"residuals": {}, "recoveries": []}

    try:
        while step < iters:
            it = step + 1
            if kill_iter is not None and it == kill_iter \
                    and not injector.killed:
                injector.arm(kill_rank, after_ops=kill_after_ops)
            try:
                blocks, resid = _elastic_iteration(
                    cart, hx, coll, _blocks_of(grid, NYb, NXb, bs),
                    nby=cur_nby, nbx=cur_nbx, bs=bs, mode=mode, rt=rt,
                    it=it)
            except TaskError:
                # ULFM recovery: revoke (unstick peers), shrink
                # (agreement on the survivors), re-decompose, restore.
                injector.disarm()
                rt.close()
                shrunk = resilience.recover(world)
                cart, cur_nby, cur_nbx = shape_over(shrunk, shrunk.size)
                hx, coll = HaloExchange(cart), Collectives(cart)
                rt = TaskRuntime(num_workers=workers)
                rt.start()
                state, step = checkpoint_lib.restore_checkpoint(
                    ckpt_dir, {"grid": np.empty((NYb * bs, NXb * bs))})
                grid = state["grid"]
                info["recoveries"].append(
                    {"at_iter": it, "killed": list(world.failed),
                     "survivors": cart.size, "resumed_step": step})
                continue
            grid = np.block(blocks)
            step = it
            info["residuals"][step] = resid
            checkpoint_lib.save_checkpoint(ckpt_dir, {"grid": grid}, step)
    finally:
        rt.close()
    info["decomposition"] = (cart.size, cur_nby, cur_nbx)
    return grid, info


# ---------------------------------------------------------------------------
# simulated scaling (paper Figs. 9/11/12/13)
# ---------------------------------------------------------------------------
def build_sim_graph(version, *, n_ranks, nby, nbx, iters,
                    t_block=1.0, t_comm=0.05, latency=0.1):
    py, px = grid_dims(n_ranks)
    world = tac.CommWorld(n_ranks)
    cart = world.cart_create((py, px))
    NYb, NXb = py * nby, px * nbx
    tasks: List[SimTask] = []
    index: Dict[str, int] = {}

    def add(rank, compute, kind=COMPUTE, start=(), events=(), name="",
            group=None, group_latency=0.0):
        t = SimTask(len(tasks), rank, compute, kind=kind,
                    start_deps=[(index[s], 0.0) for s in start
                                if s and s in index],
                    event_deps=[(index[e], latency) for e in events
                                if e and e in index], name=name,
                    group=group, group_latency=group_latency)
        tasks.append(t)
        index[name] = t.id

    def rank_of(gy, gx):
        return cart.rank_at((gy // nby, gx // nbx))

    comm_kind = {"sentinel": COMM_HELD, "interop-blk": COMM_PAUSED,
                 "interop-nonblk": COMM_EVENTS}.get(version, COMM_HELD)
    # hierarchical residual latency from the schedule IR's α-β cost model
    # (α = per-message latency, wires/combines free — the pure-latency
    # point of the model, identical to the old rounds × latency count)
    res_lat = HierarchicalCollectives(world, px).cost(latency, 0.0, 0)
    last_comm = [None] * n_ranks

    def boundary_names(r, it):
        keys = set()
        for d, _ in cart.neighbor_dirs(r):
            keys.update(edge_blocks(cart, nby, nbx, r, d))
        return [f"c[{gy},{gx}]@{it}" for gy, gx in sorted(keys)]

    for it in range(iters):
        # one neighbourhood-collective node per rank per iteration; the
        # version decides how tightly it is gated:
        #   pure      — after the rank's ENTIRE previous iteration (comm
        #               phase follows compute phase, one flow per rank)
        #   forkjoin  — after the global barrier (main-thread comm phase)
        #   sentinel  — chained on the rank's previous comm task
        #   interop-* — only after the boundary blocks it actually ships
        for r in range(n_ranks):
            if not it:
                start = []
            elif version == "pure":
                ry, rx = cart.coords(r)
                start = [f"c[{gy},{gx}]@{it - 1}"
                         for gy in range(ry * nby, (ry + 1) * nby)
                         for gx in range(rx * nbx, (rx + 1) * nbx)]
            elif version == "forkjoin":
                start = [f"barrier@{it - 1}"]
            else:
                start = boundary_names(r, it - 1)
            if version == "sentinel":
                start = start + [last_comm[r] or ""]
            add(r, t_comm, kind=comm_kind, start=start,
                name=f"h[{r}]@{it}")
            if version == "sentinel":
                last_comm[r] = f"h[{r}]@{it}"
        for r in range(n_ranks):
            tasks[index[f"h[{r}]@{it}"]].neighbors = [
                (index[f"h[{nbr}]@{it}"], latency)
                for nbr in cart.neighbors(r)]

        for gy in range(NYb):
            for gx in range(NXb):
                r = rank_of(gy, gx)
                deps = []
                crosses = False
                if it:
                    deps.append(f"c[{gy},{gx}]@{it - 1}")
                    if version == "forkjoin":
                        deps.append(f"barrier@{it - 1}")
                if gy > 0:
                    if gy % nby:
                        deps.append(f"c[{gy - 1},{gx}]@{it}")
                    else:
                        crosses = True
                if gx > 0:
                    if gx % nbx:
                        deps.append(f"c[{gy},{gx - 1}]@{it}")
                    else:
                        crosses = True
                if gy < NYb - 1:
                    if (gy + 1) % nby:
                        deps.append(f"c[{gy + 1},{gx}]@{it - 1}")
                    else:
                        crosses = True
                if gx < NXb - 1:
                    if (gx + 1) % nbx:
                        deps.append(f"c[{gy},{gx + 1}]@{it - 1}")
                    else:
                        crosses = True
                if crosses:
                    # it == 0 still crosses: the first round ships the
                    # initial boundary data (it reads nothing, so the
                    # filtered @-1 deps leave it immediately ready)
                    deps.append(f"h[{r}]@{it}")
                add(r, t_block, start=deps, name=f"c[{gy},{gx}]@{it}")

        if version == "forkjoin":
            for r in range(n_ranks):
                ry, rx = cart.coords(r)
                add(r, 0.0,
                    start=[f"c[{gy},{gx}]@{it}"
                           for gy in range(ry * nby, (ry + 1) * nby)
                           for gx in range(rx * nbx, (rx + 1) * nbx)],
                    name=f"b[{r}]@{it}")
            add(0, 0.0, start=[f"b[{r}]@{it}" for r in range(n_ranks)],
                name=f"barrier@{it}")

        # residual allreduce: one collective node per rank per iteration
        res_kind = {"interop-blk": COMM_PAUSED,
                    "interop-nonblk": COMM_EVENTS}.get(version, COMM_HELD)
        for r in range(n_ranks):
            ry, rx = cart.coords(r)
            deps = [f"c[{gy},{gx}]@{it}"
                    for gy in range(ry * nby, (ry + 1) * nby)
                    for gx in range(rx * nbx, (rx + 1) * nbx)]
            if version == "forkjoin":
                deps.append(f"barrier@{it}")
            if version == "sentinel":
                deps.append(last_comm[r] or "")
            add(r, t_comm, kind=res_kind, start=deps,
                group=f"res@{it}", group_latency=res_lat,
                name=f"res[{r}]@{it}")
            if version == "sentinel":
                last_comm[r] = f"res[{r}]@{it}"
    return tasks


def simulate_version(version, *, n_ranks, workers=48, nby=4, nbx=16,
                     iters=10, t_block=1.0):
    if version == "pure":
        workers = 1   # Pure MPI: one sequential flow per rank
    tasks = build_sim_graph(version, n_ranks=n_ranks, nby=nby, nbx=nbx,
                            iters=iters, t_block=t_block)
    sim = Simulator(n_ranks, workers, task_overhead=0.002,
                    resume_overhead=0.01)
    return sim.run(tasks).makespan


# ---------------------------------------------------------------------------
def bench(print_fn=print, smoke: bool = False):
    rows = []
    ref, ref_stats = run_real("pure")
    for v in VERSIONS[1:]:
        out, st = run_real(v)
        err = float(np.abs(out - ref).max())
        assert err < 1e-10, (v, err)
        for it, val in ref_stats["residuals"].items():
            assert abs(st["residuals"][it] - val) < 1e-9, (v, it)

    # end-to-end interop legs: each must agree with the pure reference
    # bit for bit.
    for v in ("interop-blk", "interop-nonblk"):
        t0 = time.monotonic()
        out, _ = run_real(v)
        dt = (time.monotonic() - t0) / 3
        assert float(np.abs(out - ref).max()) < 1e-10, v
        rows.append((f"gs_e2e_{v}", dt * 1e6, "e2e"))

    # fused-stencil leg (Pallas executor tier): interior update, residual
    # and boundary-pack in ONE kernel pass per block — halo payloads and
    # residual sums come from the kernel outputs, not grid re-reads.  The
    # kernel computes in fp32, so the bound is fp32 epsilon (~1e-5 after
    # 3 iterations), not the float64 paths' 1e-10.
    from repro.kernels import ops as kernel_ops
    t0 = time.monotonic()
    out_f, st_f = run_real("interop-nonblk",
                           block_impl=kernel_ops.pallas_impl())
    dt = (time.monotonic() - t0) / 3
    err_f = float(np.abs(out_f - ref).max())
    assert err_f < 1e-4, err_f
    for it, val in ref_stats["residuals"].items():
        assert abs(st_f["residuals"][it] - val) <= 1e-4 * max(1.0, val), it
    rows.append(("gs_fused_stencil_interop", dt * 1e6,
                 f"maxerr={err_f:.1e}"))

    if smoke:
        # CI bench-smoke job: all five versions numerically agree (above)
        # and the schedule acceptance ordering holds on one simulated
        # point — event-bound strictly beats the sentinel serialisation.
        mks = {v: simulate_version(v, n_ranks=4, nby=4, nbx=4, iters=4)
               for v in VERSIONS}
        assert mks["interop-nonblk"] < mks["sentinel"], mks
        for v in VERSIONS:
            rows.append((f"gs_smoke_{v}", mks[v] * 1e6, "smoke"))
        for r in rows:
            print_fn(f"{r[0]},{r[1]:.1f},{r[2]}")
        return rows

    for v in VERSIONS:
        t0 = time.monotonic()
        _, stats = run_real(v)
        dt = (time.monotonic() - t0) / 3
        rows.append((f"gs_real_{v}", dt * 1e6,
                     f"blocks={stats.get('task_blocks', 0)}"
                     f";threads={stats.get('threads_spawned', 0)}"))

    # strong scaling (Fig. 9): fixed 8x8 global blocks, split over ranks
    base_s = simulate_version("pure", n_ranks=1, nby=8, nbx=8)
    for v in VERSIONS:
        for n in (1, 2, 4, 8, 16):
            py, px = grid_dims(n)
            mk = simulate_version(v, n_ranks=n, nby=8 // py, nbx=8 // px)
            rows.append((f"gs_strong_{v}_r{n}", mk * 1e6,
                         f"speedup={base_s / mk:.2f}"))

    # weak scaling (Fig. 11): 4x4 blocks per rank
    base_w = simulate_version("pure", n_ranks=1, nby=4, nbx=4)
    for v in VERSIONS:
        for n in (1, 2, 4, 8, 16):
            mk = simulate_version(v, n_ranks=n, nby=4, nbx=4)
            rows.append((f"gs_weak_{v}_r{n}", mk * 1e6,
                         f"efficiency={base_w / mk:.2f}"))

    base6 = simulate_version("pure", n_ranks=1, nby=4, nbx=4, iters=6)
    for v in ("interop-blk", "interop-nonblk"):
        for scale, label in ((1, "1024bs"), (2, "512bs"), (4, "256bs")):
            mk = simulate_version(v, n_ranks=8, nby=4 * scale,
                                  nbx=4 * scale, iters=6,
                                  t_block=1.0 / (scale * scale))
            rows.append((f"gs_gran_{v}_{label}", mk * 1e6,
                         f"speedup={base6 / mk:.2f}"))
    for r in rows:
        print_fn(f"{r[0]},{r[1]:.1f},{r[2]}")
    return rows


# ---------------------------------------------------------------------------
# traced leg: Perfetto timeline + overlap accounting (repro.obs)
# ---------------------------------------------------------------------------
def run_traced(trace_path: str, *, smoke: bool = False,
               print_fn=print) -> Dict[str, float]:
    """``--trace`` leg: real runs under the tracer, one Perfetto artifact.

    Runs the sentinel, interop-blk, and interop-nonblk versions under ONE
    :class:`repro.obs.Tracer` (so the exported timeline shows the three
    legs back to back), slices the event stream per leg, and derives the
    paper's headline number per leg — the overlap fraction (share of
    handle in-flight time covered by concurrent compute spans,
    :func:`repro.obs.analysis.overlap_fraction`).  Writes the trace-event
    JSON to ``trace_path`` with the derived metrics in ``otherData``.

    Hard acceptance checks (SystemExit on violation):

    * the document validates against ``repro.obs.SPAN_SCHEMA``;
    * the interop-blk leg recorded task pause spans (§4.1 pause/resume
      made visible);
    * the event-bound leg's overlap fraction is STRICTLY greater than
      the sentinel leg's (taskified-serialised comm cannot overlap).
    """
    from repro import obs

    params = dict(n_ranks=4, workers=2, nby=2, nbx=2,
                  bs=24 if smoke else 48, iters=3)
    legs = ("sentinel", "interop-blk", "interop-nonblk")
    windows: Dict[str, Tuple[float, float]] = {}
    with obs.tracing(capacity=1 << 18) as tr:
        for v in legs:
            t0 = (time.monotonic() - tr.epoch) * 1e6
            run_real(v, **params)
            windows[v] = (t0, (time.monotonic() - tr.epoch) * 1e6)
        events = tr.events()

    def leg_events(v):
        lo, hi = windows[v]
        return [e for e in events if lo <= e["ts"] < hi]

    overlaps = {v: obs.overlap_fraction(leg_events(v)) for v in legs}
    nonblk = leg_events("interop-nonblk")
    per_rank = obs.per_rank_overlap(nonblk)
    stragglers = obs.straggler_scores(nonblk)
    doc = obs.export_trace(trace_path, events=events, extra={
        "benchmark": "gauss_seidel",
        "legs": {v: {"window_us": list(windows[v]),
                     "overlap_fraction": overlaps[v]} for v in legs},
        "per_rank_overlap": {str(r): f for r, f in per_rank.items()},
        "straggler_scores": {str(r): s for r, s in stragglers.items()},
    })
    obs.assert_valid_trace(doc)
    pauses = sum(1 for e in leg_events("interop-blk")
                 if e["ph"] == "X" and e["cat"] == "task"
                 and e["name"] == "pause")
    if pauses == 0:
        raise SystemExit("traced leg: interop-blk recorded no task pause "
                         "spans — §4.1 pause/resume not visible")
    if not overlaps["interop-nonblk"] > overlaps["sentinel"]:
        raise SystemExit(
            f"overlap ordering violated: event-bound "
            f"{overlaps['interop-nonblk']:.3f} <= sentinel "
            f"{overlaps['sentinel']:.3f}")
    for v in legs:
        print_fn(f"gs_trace_{v},{overlaps[v] * 1e6:.1f},"
                 f"overlap-fraction-ppm")
    print_fn(f"gs_trace_events,{len(events)},file={trace_path}"
             f";pauses={pauses}")
    return overlaps


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(
        description="Gauss-Seidel benchmark (paper §7.1)")
    ap.add_argument("--smoke", action="store_true",
                    help="small CI leg: parity checks + one simulated point")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="run the traced legs and write Perfetto JSON here "
                         "(skips the plain bench)")
    ns = ap.parse_args()
    if ns.trace:
        run_traced(ns.trace, smoke=ns.smoke)
    else:
        bench(smoke=ns.smoke)
