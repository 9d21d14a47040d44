"""ULFM-style fault tolerance: injection, propagation, shrink, rebuild.

Layers under test (PR tentpole):

1. **Transport failure model** — ``CommWorld.fail_rank`` fails every
   pending handle naming the dead rank with ``RankFailedError`` (pushed
   through the completion callbacks — no new polling), and new posts
   toward it fail at post time.  ``revoke`` / ``revoke_group`` propagate
   a failure to handles that touch only live ranks.
2. **Shrink agreement** — ``CommWorld.shrink`` completes once every
   survivor voted (generation-counted like ``split``), yields one shared
   group, clears the revocation, and tolerates voters dying mid-vote.
3. **Epoch-keyed rebuild** — compiled plans go stale when the epoch
   bumps (``StaleProgramError``); persistent collectives and halo
   exchanges recompile themselves on first post after recovery.
4. **FaultInjector + harness** — deterministic mid-operation death; the
   hypothesis sweep drives failure point × algorithm × mode through
   tests/fault_harness.py and asserts hang-free surfacing, leak-free
   teardown, and survivor convergence (or, for a death point the
   victim never reaches, the clean result).
5. **Simulator rank death** — ``Simulator.run(fail=...)`` reports the
   failure cone instead of deadlocking.

The whole module carries the ``faults`` marker: the CI soak job runs
``-m faults`` with ``REPRO_FAULTS_SOAK`` scaling the hypothesis example
count.
"""

import os

import numpy as np
import pytest

from repro.core import (Collectives, FaultInjector, HaloExchange,
                        RankFailedError, CommRevokedError, TaskRuntime, tac)
from repro.core import program as program_ir
from repro.core import schedule as schedule_ir
from repro.core.executor import TaskError
from repro.core.resilience import recover, shrink_world
from repro.core.simulate import (Simulator, SimTask, COMPUTE, COMM_EVENTS)

from fault_harness import ALGORITHMS, run_with_failure

pytestmark = pytest.mark.faults


@pytest.fixture(autouse=True)
def _task_multiple():
    tac.init(tac.TASK_MULTIPLE)
    yield
    tac.init(tac.TASK_MULTIPLE)


# ---------------------------------------------------------------------------
# 1. transport failure model
# ---------------------------------------------------------------------------
def test_fail_rank_fails_pending_handles_and_new_posts():
    w = tac.CommWorld(4)
    pending = w.irecv(src=3, dst=0, tag="t")
    unrelated = w.irecv(src=1, dst=2, tag="u")
    assert w.epoch == 0 and w.alive == (0, 1, 2, 3)
    w.fail_rank(3)
    assert w.failed == frozenset({3}) and w.alive == (0, 1, 2)
    assert w.epoch == 1
    assert pending.test()              # completed erroneously, not hung
    with pytest.raises(RankFailedError) as ei:
        pending.result
    assert ei.value.rank == 3
    assert not unrelated.test()        # live-pair traffic untouched
    with pytest.raises(RankFailedError):
        w.isend(1.0, src=0, dst=3).result
    with pytest.raises(RankFailedError):
        w.irecv(src=3, dst=1).result
    w.fail_rank(3)                     # idempotent
    assert w.epoch == 1


def test_failure_pushes_through_callbacks_not_polls():
    """The error arrives via the same push path as a success: a callback
    registered before the failure fires exactly once, with the handle in
    its failed state."""
    w = tac.CommWorld(2)
    h = w.irecv(src=1, dst=0, tag=0)
    seen = []
    h.on_complete(lambda hh: seen.append(hh.error))
    w.fail_rank(1)
    assert len(seen) == 1 and isinstance(seen[0], RankFailedError)
    late = []
    h.on_complete(lambda hh: late.append(hh.error))   # post-failure reg
    assert len(late) == 1


def test_revoke_fails_live_pair_traffic():
    w = tac.CommWorld(4)
    h = w.irecv(src=1, dst=2, tag=9)   # both endpoints alive
    w.revoke()
    assert w.revoked
    with pytest.raises(CommRevokedError):
        h.result
    with pytest.raises(CommRevokedError):
        w.isend(1, src=0, dst=1).result
    # CommRevokedError is a RankFailedError: one except clause catches both
    assert issubclass(CommRevokedError, RankFailedError)


def test_revoke_group_is_scoped_to_the_group():
    w = tac.CommWorld(4)
    g = w.group([0, 1, 2])
    sibling = w.group([1, 2, 3])
    hg = g.irecv(src=0, dst=1, tag="a")
    hs = sibling.irecv(src=0, dst=1, tag="a")
    hw = w.irecv(src=0, dst=1, tag="a")
    g.revoke()
    with pytest.raises(CommRevokedError):
        hg.result
    assert not hs.test() and not hw.test()   # sibling + world untouched
    assert not w.revoked


# ---------------------------------------------------------------------------
# 2. shrink agreement
# ---------------------------------------------------------------------------
def test_shrink_agreement_completes_when_all_survivors_vote():
    w = tac.CommWorld(4)
    w.fail_rank(1)
    handles = {r: w.shrink(rank=r) for r in (0, 2)}
    assert not handles[0].test()       # rank 3 has not voted yet
    h3 = w.shrink(rank=3)
    groups = [handles[0].result, handles[2].result, h3.result]
    assert all(g is groups[0] for g in groups)   # ONE shared group
    assert groups[0].ranks == (0, 2, 3)
    # the shrunken group works: group-local p2p round trip
    groups[0].isend(7.5, src=0, dst=2, tag="x")
    assert groups[0].irecv(src=0, dst=2, tag="x").result == 7.5


def test_shrink_clears_revocation():
    w = tac.CommWorld(3)
    w.fail_rank(0)
    w.revoke()
    assert w.revoked
    g = shrink_world(w)
    assert not w.revoked
    assert g.ranks == (1, 2)
    # world traffic between survivors flows again
    w.isend(1, src=1, dst=2, tag="post")
    assert w.irecv(src=1, dst=2, tag="post").result == 1


def test_shrink_dead_caller_and_mid_vote_death():
    w = tac.CommWorld(4)
    w.fail_rank(0)
    with pytest.raises(RankFailedError):
        w.shrink(rank=0).result        # the dead cannot vote
    h1 = w.shrink(rank=1)
    h2 = w.shrink(rank=2)
    assert not h1.test()
    w.fail_rank(3)                     # a yet-to-vote survivor dies...
    g = h1.result                      # ...which completes the agreement
    assert g.ranks == (1, 2) and h2.result is g


def test_shrink_generations_are_independent():
    w = tac.CommWorld(3)
    w.fail_rank(2)
    first = [w.shrink(rank=r) for r in (0, 1)]
    second = [w.shrink(rank=r) for r in (0, 1)]
    ga = first[0].result
    gb = second[0].result
    assert ga is not gb and ga.ranks == gb.ranks == (0, 1)


def test_recover_helper_end_to_end():
    w = tac.CommWorld(5)
    w.fail_rank(2)
    parked = w.irecv(src=0, dst=4, tag="parked")   # live pair, pending
    g = recover(w)
    assert g.ranks == (0, 1, 3, 4)
    with pytest.raises(CommRevokedError):
        parked.result                  # revoke unstuck it
    out = Collectives(g).run_group(
        "allreduce", [{"value": np.float64(r)} for r in range(4)])
    assert all(float(v) == 6.0 for v in out)


# ---------------------------------------------------------------------------
# 3. epoch-keyed rebuild
# ---------------------------------------------------------------------------
def test_stale_program_raises_and_recompiles():
    w = tac.CommWorld(4)
    sched = schedule_ir.build("allreduce", "ring", 4)
    prog = program_ir.compile_schedule(sched, w, head=("t",))
    assert prog.epoch == 0
    w.fail_rank(3)
    with pytest.raises(program_ir.StaleProgramError):
        next(prog.gen(0, "k", value=np.float64(1)))
    fresh = program_ir.compile_schedule(sched, w, head=("t",))
    assert fresh is not prog and fresh.epoch == w.epoch


def test_persistent_collective_rebuilds_after_epoch_bump():
    w = tac.CommWorld(4)
    coll = Collectives(w)
    pers = coll.persistent("allreduce", algorithm="ring")
    vals = [np.float64(r) for r in range(4)]
    out = pers.run_group(vals, key="a")
    assert all(float(v) == 6.0 for v in out)
    before = pers._plan()
    w.epoch += 1                       # any failure/revoke does this
    out = pers.run_group(vals, key="b")    # no StaleProgramError: rebuilt
    assert all(float(v) == 6.0 for v in out)
    assert pers._plan() is not before


def test_halo_exchange_rebuilds_on_shrunken_cart():
    """The full rebuild path: kill, recover, re-shape the survivors as a
    fresh Cartesian grid, run a persistent halo exchange on it."""
    w = tac.CommWorld(5)
    w.fail_rank(4)
    g = recover(w)
    cart = g.cart((2, 2))
    hx = HaloExchange(cart)
    sends = [{d: np.array([float(r)]) for d, _ in hx.neighbors(r)}
             for r in range(4)]
    out = hx.run_group(sends)
    for r in range(4):
        for d, q in hx.neighbors(r):
            np.testing.assert_array_equal(out[r][d], [float(q)])
    with pytest.raises(ValueError, match="needs"):
        g.cart((2, 3))                 # wrong survivor count


# ---------------------------------------------------------------------------
# 4. FaultInjector + harness
# ---------------------------------------------------------------------------
def test_fault_injector_immediate_and_armed():
    w = tac.CommWorld(4)
    inj = FaultInjector(w)
    inj.kill(1)
    assert w.failed == frozenset({1}) and inj.killed == [1]
    inj.arm(2, after_ops=2)
    assert inj.armed
    w.isend(1, src=2, dst=0, tag=0)    # 1st post: still alive
    assert 2 not in w.failed
    w.irecv(src=0, dst=2, tag=1)       # 2nd post: trap fires
    assert 2 in w.failed and not inj.armed
    with pytest.raises(ValueError, match="out of range"):
        inj.arm(9)
    with pytest.raises(ValueError, match="after_ops"):
        inj.arm(0, after_ops=0)


def test_armed_injection_counts_only_the_victim():
    w = tac.CommWorld(3)
    inj = FaultInjector(w)
    inj.arm(1, after_ops=1)
    w.isend(1, src=0, dst=2, tag=0)    # other ranks' posts don't count
    w.irecv(src=0, dst=2, tag=0)
    assert not w.failed
    inj.disarm()
    assert not inj.armed
    w.isend(1, src=1, dst=2, tag=1)    # disarmed: victim survives
    assert not w.failed


@pytest.mark.parametrize("mode", ["blocking", "event"])
@pytest.mark.parametrize("algorithm", ["ring", "doubling"])
def test_injected_death_surfaces_and_survivors_recover(mode, algorithm):
    out = run_with_failure(n_ranks=4, victim=2, after_ops=1, mode=mode,
                           algorithm=algorithm)
    assert out.fired
    assert out.survivors.ranks == (0, 1, 3)
    assert 2 not in out.ok_ranks


@pytest.mark.parametrize("after_ops,fired", [(2, True), (3, False)])
def test_two_rank_doubling_death_point(after_ops, fired):
    """A 2-rank doubling victim posts exactly two operations (one send,
    one recv): a trap at its second op kills it; a trap at a third never
    fires, and the run must then be clean — no failed rank, every
    allreduce equal to numpy (checked inside the harness)."""
    out = run_with_failure(n_ranks=2, victim=0, after_ops=after_ops,
                           algorithm="doubling", mode="blocking")
    assert out.fired is fired
    if fired:
        assert 0 not in out.ok_ranks
        assert out.survivors.ranks == (1,)
    else:
        assert out.ok_ranks == [0, 1] and out.survivors is None


def test_late_injection_lets_finished_ranks_through():
    """Doubling allreduce, death at the victim's round-2 post: the pair
    that no longer needs the victim completes; the victim's round-2
    partner fails.  The failure cone is minimal, not all-or-nothing."""
    out = run_with_failure(n_ranks=4, victim=0, after_ops=3,
                           algorithm="doubling", mode="event")
    assert 0 not in out.ok_ranks
    assert out.ok_ranks or out.failed_ranks   # shape asserted in harness


def test_runtime_close_leak_free_after_failure():
    """Ten injected failures back to back: every runtime closes with
    zero registered polling services (asserted inside the harness)."""
    for seed in range(5):
        run_with_failure(n_ranks=4, victim=seed % 4, after_ops=1 + seed,
                         mode=("event", "blocking")[seed % 2],
                         recover_after=False, seed=seed)


def test_taskwait_raises_instead_of_hanging_blocking_mode():
    """A blocking-mode collective whose peer dies must surface out of
    taskwait as TaskError (the machine revokes; paused tasks resume with
    the error), never hang."""
    tac.init(tac.TASK_MULTIPLE)
    w = tac.CommWorld(3)
    coll = Collectives(w)
    inj = FaultInjector(w)
    inj.arm(1, after_ops=1)
    with TaskRuntime(num_workers=2) as rt:
        for r in range(3):
            def body(r=r):
                coll.allreduce(np.float64(r), rank=r, mode="blocking",
                               key="tw")
            rt.submit(body, name=f"b[{r}]")
        with pytest.raises(TaskError):
            rt.taskwait()


# The hypothesis sweep over failure point × algorithm × mode × backend
# lives in tests/test_resilience_properties.py (module-level importorskip
# must not take these unit tests down with it when hypothesis is absent).


# -- deterministic mini-sweep: runs even without hypothesis ------------------
@pytest.mark.parametrize("algorithm", ALGORITHMS)
@pytest.mark.parametrize("after_ops", [1, 2, 4])
def test_failure_point_sweep_deterministic(algorithm, after_ops):
    """A fixed grid over the same space the hypothesis suite samples:
    any death point in any algorithm recovers (harness asserts the
    hang-free / leak-free / convergence contract)."""
    out = run_with_failure(n_ranks=4, victim=(after_ops + 1) % 4,
                           after_ops=after_ops, algorithm=algorithm,
                           mode=("event", "blocking")[after_ops % 2],
                           seed=after_ops)
    assert out.survivors.size == 3


# ---------------------------------------------------------------------------
# 5. simulator rank death
# ---------------------------------------------------------------------------
def _chain(rank, ids, dep_lat=0.0):
    return [SimTask(i, rank, 1.0,
                    start_deps=[(i - 1, dep_lat)] if j else [])
            for j, i in enumerate(ids)]


def test_sim_rank_death_reports_failure_cone():
    # rank 0: 0 -> 1 -> 2 (chain); rank 1's task 3 event-depends on 1
    tasks = _chain(0, [0, 1, 2])
    tasks.append(SimTask(3, 1, 1.0, kind=COMM_EVENTS,
                         event_deps=[(1, 0.1)]))
    clean = Simulator(2, 1).run(tasks)
    assert not clean.failed
    res = Simulator(2, 1).run(tasks, fail=(0, 1.5))
    # task 0 finished before the death; 1, 2 die with the rank; 3 never
    # sees task 1's event -> the cone is {1, 2, 3}
    assert res.failed == {1, 2, 3}
    assert 0 in res.done_times and 1 not in res.done_times
    assert res.makespan <= clean.makespan


def test_sim_death_after_delivery_spares_consumers():
    tasks = _chain(0, [0])
    tasks.append(SimTask(1, 1, 1.0, kind=COMM_EVENTS,
                         event_deps=[(0, 0.5)]))
    res = Simulator(2, 1).run(tasks, fail=(0, 1.2))
    # rank 0 died AFTER its body completed at t=1: the in-flight message
    # still arrives and rank 1 finishes
    assert res.failed == set()
    assert 1 in res.done_times


def test_sim_fail_validation_and_determinism():
    tasks = _chain(0, [0, 1])
    with pytest.raises(ValueError):
        Simulator(1, 1).run(tasks, fail=(5, 1.0))
    a = Simulator(1, 1).run(tasks, fail=(0, 1.5)).failed
    b = Simulator(1, 1).run(tasks, fail=(0, 1.5)).failed
    assert a == b == {1}
