"""Continuation-based completion notification (core/continuations.py).

Covers the engine itself (attach, all-of sets, chaining, bounded queues,
poll fallback, error capture), the collectives ProgressEngine riding it
(O(completions) dispatches and zero tests — the acceptance-criterion
counters), the runtime wiring (TaskRuntime.continuations, wait/iwait
routing for push and push-less handles, scheduling-point drains,
deterministic service teardown), the §5 single-worker deadlock
regression under nested blocking, and the simulator's callback-dispatch
cost model.
"""

import threading

import numpy as np
import pytest

from repro.core import Collectives, TaskRuntime, tac
from repro.core.collectives import (CollectiveHandle, HaloExchange,
                                    ProgressEngine, _Machine)
from repro.core.continuations import Continuation, ContinuationEngine
from repro.core.simulate import COMM_EVENTS, COMPUTE, SimTask, Simulator


@pytest.fixture(autouse=True)
def _task_multiple():
    tac.init(tac.TASK_MULTIPLE)
    yield
    tac.init(tac.TASK_MULTIPLE)


def _world(n, **kw):
    w = tac.CommWorld(n)
    return w, Collectives(w, **kw)


# ---------------------------------------------------------------------------
# the engine standalone
# ---------------------------------------------------------------------------
def test_attach_single_handle_dispatches_once():
    eng = ContinuationEngine()
    h = tac.EventHandle()
    ran = []
    cont = eng.attach(h, lambda: ran.append(1))
    assert not cont.test() and eng.queued == 0
    h.complete("payload")
    assert eng.queued == 1 and not ran       # queued, not run inline
    assert eng.dispatch() == 1
    assert ran == [1] and cont.test() and cont.result == "payload"
    assert eng.stats["completions"] == 1 and eng.stats["dispatches"] == 1
    assert eng.stats["tests"] == 0           # push handle: never tested


def test_attach_already_complete_handle():
    eng = ContinuationEngine()
    h = tac.EventHandle()
    h.complete(7)
    cont = eng.attach(h, lambda: None)
    assert eng.queued == 1                   # ready at attach time
    eng.dispatch()
    assert cont.result == 7


def test_attach_set_fires_after_all():
    eng = ContinuationEngine()
    hs = [tac.EventHandle() for _ in range(3)]
    ran = []
    cont = eng.attach(hs, lambda: ran.append("all"))
    for i, h in enumerate(hs):
        assert eng.queued == 0 and not ran
        h.complete(i)
    assert eng.queued == 1
    eng.dispatch()
    assert ran == ["all"] and cont.result == [0, 1, 2]
    assert eng.stats["completions"] == 1     # the SET completed once


def test_continuations_chain():
    """attach() returns a testable/waitable handle, so continuations
    chain — the Continuations-paper property."""
    eng = ContinuationEngine()
    h = tac.EventHandle()
    order = []
    c1 = eng.attach(h, lambda: order.append("first"))
    c2 = eng.attach(c1, lambda: order.append("second"))
    c3 = eng.attach(c2, lambda: order.append("third"))
    h.complete("x")
    # one dispatch() drains the whole cascade, in dependency order
    eng.dispatch()
    assert order == ["first", "second", "third"]
    assert c3.test() and c2.test()
    assert eng.stats["dispatches"] == 3


def test_poll_fallback_for_pushless_handles():
    """Handles without on_complete (e.g. jax ArrayHandle) are polled from
    the engine's fallback list — the only tests it ever performs."""
    class Plain:
        def __init__(self):
            self.done = False
            self.result = None

        def test(self):
            return self.done

    eng = ContinuationEngine()
    h = Plain()
    ran = []
    eng.attach(h, lambda: ran.append(1))
    assert eng.polled == 1
    eng.service(None)
    assert not ran and eng.stats["tests"] == 1
    h.done = True
    h.result = 5
    eng.service(None)                        # test + arrival + dispatch
    assert ran == [1] and eng.polled == 0
    assert eng.stats["tests"] == 2


def test_bounded_queue_overflow_dispatches_inline():
    eng = ContinuationEngine(queue_capacity=2)
    hs = [tac.EventHandle() for _ in range(5)]
    ran = []
    for i, h in enumerate(hs):
        eng.attach(h, lambda i=i: ran.append(i))
    for h in hs:
        h.complete(None)
    # capacity 2 queued; 3 overflowed and ran on the completing thread
    assert eng.stats["inline_dispatches"] == 3 and len(ran) == 3
    eng.dispatch()
    assert sorted(ran) == [0, 1, 2, 3, 4]
    assert eng.stats["dispatches"] == 5


def test_callback_error_captured_not_raised():
    eng = ContinuationEngine()
    h = tac.EventHandle()
    cont = eng.attach(h, lambda: 1 / 0)
    h.complete(None)
    eng.dispatch()                           # must not raise here
    assert eng.stats["callback_errors"] == 1
    assert cont.test() and cont.error is not None
    with pytest.raises(ZeroDivisionError):
        _ = cont.result
    # the engine survives: later attachments still dispatch
    h2 = tac.EventHandle()
    ok = eng.attach(h2, lambda: None)
    h2.complete(3)
    eng.dispatch()
    assert ok.result == 3


def test_failed_handle_result_does_not_kill_dispatcher():
    """A handle whose `result` re-raises (a failed CollectiveHandle) must
    not escape dispatch: the error lands on the continuation and the
    dispatching thread survives."""
    eng = ContinuationEngine()
    h = CollectiveHandle()
    ran = []
    cont = eng.attach(h, lambda: ran.append(1))
    h.fail(ValueError("boom"))
    eng.dispatch()                           # must not raise
    assert ran == [1]                        # callback itself still ran
    assert eng.stats["callback_errors"] == 1
    with pytest.raises(ValueError, match="boom"):
        _ = cont.result


def test_attach_validates():
    eng = ContinuationEngine()
    with pytest.raises(ValueError):
        eng.attach([], lambda: None)
    with pytest.raises(ValueError):
        ContinuationEngine(queue_capacity=0)


def test_continuation_is_a_waitable_handle():
    """tac.wait accepts a Continuation anywhere it accepts an operation
    handle (the PMPI path here: no task)."""
    eng = ContinuationEngine()
    h = tac.EventHandle()
    cont = eng.attach(h, lambda: None)
    t = threading.Thread(target=lambda: (h.complete(42), eng.dispatch()))
    t.start()
    assert tac.wait(cont) == 42
    t.join()


# ---------------------------------------------------------------------------
# the acceptance-criterion counters: O(completions), zero tests
# ---------------------------------------------------------------------------
def _event_machines(engine, n):
    """n in-flight machines, each waiting on one EventHandle."""
    handles = [tac.EventHandle() for _ in range(n)]

    def gen(h):
        res = yield h
        return res

    for h in handles:
        engine.submit(_Machine(gen(h), CollectiveHandle()))
    return handles


def test_progress_counters_flat_vs_linear():
    """64 in-flight event-bound operations, one completion per tick: the
    progress engine performs O(completions) dispatches and ZERO tests —
    flat per completion, where re-testing every parked machine per tick
    would grow linearly with the in-flight count."""
    n = 64
    engine = ContinuationEngine()
    eng = ProgressEngine(engine)
    handles = _event_machines(eng, n)
    assert eng.pending == n
    for i, h in enumerate(handles):
        h.complete(i)
        engine.service(None)
    assert eng.pending == 0
    assert engine.stats["completions"] == n
    assert engine.stats["dispatches"] == n               # one per completion
    assert engine.stats["tests"] == 0                    # pure push
    assert eng.stats["rearms"] == n


def test_continuation_machine_rearms_across_rounds():
    """A multi-wait machine re-arms a continuation per awaited handle —
    dispatches stay O(completions), not O(machines × ticks)."""
    engine = ContinuationEngine()
    eng = ProgressEngine(engine)
    h1, h2, h3 = (tac.EventHandle() for _ in range(3))

    def gen():
        a = yield h1
        b = yield [h2, h3]
        return a + sum(b)

    done = CollectiveHandle()
    eng.submit(_Machine(gen(), done))
    h1.complete(1)
    engine.service(None)
    assert not done.test() and eng.pending == 1
    h2.complete(2)
    engine.service(None)                      # set incomplete: no fire
    assert not done.test()
    h3.complete(3)
    engine.service(None)
    assert done.result == 6 and eng.pending == 0
    assert eng.stats["rearms"] == 2           # one per parked wait


# ---------------------------------------------------------------------------
# runtime wiring: the continuation engine drives the full collective stack
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("algorithm", ["ring", "doubling"])
def test_many_in_flight_event_collectives_stress(algorithm):
    """≥64 concurrent event-bound collectives (8 ranks × 8 keyed
    allreduces) in each schedule family, all riding the continuation
    engine."""
    n, per_rank = 8, 8
    _, coll = _world(n)
    vals = {(r, k): np.full(4, float(r + 1) * (k + 1))
            for r in range(n) for k in range(per_rank)}
    refs = {k: np.sum(np.stack([vals[(r, k)] for r in range(n)]), axis=0)
            for k in range(per_rank)}
    handles = {}

    def comm(r):
        def body():
            for k in range(per_rank):
                handles[(r, k)] = coll.allreduce(
                    vals[(r, k)], rank=r, op="sum", algorithm=algorithm,
                    mode="event", key=("stress", k))
        return body

    with TaskRuntime(num_workers=4) as rt:
        for r in range(n):
            rt.submit(comm(r))
        rt.taskwait()
    assert len(handles) == n * per_rank      # 64 in-flight operations
    for (r, k), h in handles.items():
        np.testing.assert_allclose(h.result, refs[k])
    assert rt.stats.get("task_blocks", 0) == 0
    # machines rode the runtime's continuation engine, not a polled list
    assert rt._coll_engine._continuations is rt.continuations
    assert rt._coll_engine.stats["rearms"] > 0
    assert rt._coll_engine.pending == 0
    assert rt.continuations.stats["dispatches"] > 0
    rt.close()
    assert rt.polling.num_services == 0      # deterministic teardown


@pytest.mark.parametrize("block_mode", ["spare-thread", "nested"])
def test_blocking_collectives(block_mode):
    n = 5
    _, coll = _world(n)
    vals = [np.arange(6.0) * (r + 1) for r in range(n)]
    ref = np.sum(np.stack(vals), axis=0)
    results = {}

    def make(r):
        def body():
            results[r] = coll.allreduce(vals[r], rank=r, op="sum",
                                        mode="blocking", key="b")
        return body

    with TaskRuntime(num_workers=2, block_mode=block_mode) as rt:
        for r in range(n):
            rt.submit(make(r))
        rt.taskwait()
    for r in range(n):
        np.testing.assert_allclose(results[r], ref)
    assert rt.stats["task_blocks"] == rt.stats["task_resumes"] > 0


def test_nested_single_worker_deadlock_regression_continuation():
    """§5 with block_mode="nested" and ONE worker: the blocked task's
    stack serves the engine's service (dispatching ready callbacks)
    while it helps, so the multi-round blocking collective completes
    without spare threads."""
    n = 3
    _, coll = _world(n)
    results = {}

    def make(r):
        def body():
            results[r] = coll.allreduce(np.float64(r), rank=r, op="sum",
                                        algorithm="ring", mode="blocking",
                                        key="nc")
        return body

    with TaskRuntime(num_workers=1, block_mode="nested") as rt:
        for r in range(n):
            rt.submit(make(r))
        rt.taskwait()
    assert all(float(results[r]) == 3.0 for r in range(n))
    assert rt.stats["threads_spawned"] == 1   # no spares in nested mode


class _PushLess:
    """A view of a handle with only the base protocol — no
    ``on_complete`` — so the engine re-tests it from its fallback poll
    list, as it does the JAX arrays a device step returns."""

    def __init__(self, handle):
        self._h = handle

    def test(self):
        return self._h.test()

    def wait(self):
        return self._h.wait()

    @property
    def result(self):
        return self._h.result


@pytest.mark.parametrize("intake", ["push", "fallback"])
def test_wait_iwait_routing(intake):
    """tac.wait pauses/resumes and tac.iwait(all) defers release through
    the continuation engine, whether the handles push their completion
    or ride the fallback poll list."""
    done = {}
    view = (lambda h: h) if intake == "push" else _PushLess

    def producer(handles):
        def body():
            for i, h in enumerate(handles):
                h.complete(i)
        return body

    h_wait = tac.EventHandle()
    h_i1, h_i2, h_i3 = (tac.EventHandle() for _ in range(3))

    def waiter():
        done["wait"] = tac.wait(view(h_wait))

    def binder():
        tac.iwait(view(h_i1))
        tac.iwaitall([view(h_i2), view(h_i3)])

    def consumer():
        done["iwait"] = (h_i1.result, h_i2.result, h_i3.result)

    with TaskRuntime(num_workers=2) as rt:
        rt.submit(binder, out=["b"])
        rt.submit(waiter, out=["w"])
        rt.submit(producer([h_wait, h_i1, h_i2, h_i3]))
        rt.submit(consumer, in_=["b"])
        rt.taskwait()
    assert done["wait"] == 0
    assert done["iwait"] == (1, 2, 3)
    if intake == "push":
        assert rt.continuations.stats["tests"] == 0
    else:
        assert rt.continuations.stats["tests"] > 0


def test_failing_collective_releases_and_teardown_clean():
    """A raising schedule must not leave services registered after close
    (the leak-fix satellite): stress with failing machines, then assert
    zero registered services.  n=2 so every rank's combine raises and
    releases (a failed peer stalling the others is separate, documented
    MPI-like behaviour)."""
    n = 2
    _, coll = _world(n)
    handles = {}

    def capture(r):
        def body():
            handles[r] = coll.allreduce(
                np.zeros(3 if r == 0 else 4), rank=r, op="sum",
                algorithm="doubling", mode="event", key="bad")
        return body

    rt = TaskRuntime(num_workers=2)
    with rt:
        for r in range(n):
            rt.submit(capture(r))
        rt.taskwait()                         # must not hang
    failed = [r for r in range(n) if handles[r].error is not None]
    assert failed
    with pytest.raises(ValueError):
        _ = handles[failed[0]].result
    assert rt.polling.num_services == 0, "services leaked past close()"


def test_close_unregisters_every_runtime_service():
    rt = TaskRuntime(num_workers=1, speculative_timeout=60.0)
    rt.start()
    _ = rt.continuations                      # engine + its one service
    h = tac.EventHandle()

    def body():
        tac.iwait(h)

    rt.submit(body)
    h.complete(None)
    rt.taskwait()
    # continuation engine / straggler watch both registered
    assert rt.polling.num_services >= 2
    rt.close()
    assert rt.polling.num_services == 0


def test_one_service_total_not_one_per_operation():
    """100 attached operations: still exactly ONE registered service."""
    with TaskRuntime(num_workers=2) as rt:
        before = rt.polling.num_services
        hs = [tac.EventHandle() for _ in range(100)]

        def body():
            tac.iwaitall(hs)

        rt.submit(body)
        mid = rt.polling.num_services
        for h in hs:
            h.complete(None)
        rt.taskwait()
        assert mid == before  # attaching 100 ops registered nothing new


# ---------------------------------------------------------------------------
# neighbourhood + chained waits end-to-end
# ---------------------------------------------------------------------------
def test_halo_exchange_event_mode_continuation_backend():
    w = tac.CommWorld(4)
    cart = w.cart_create((2, 2), periodic=False)
    hx = HaloExchange(cart)
    got = {}

    def comm(r):
        def body():
            sends = {d: np.full(2, float(10 * r + i))
                     for i, (d, _) in enumerate(hx.neighbors(r))}
            got[r] = hx.start(sends, rank=r, mode="event", key="h")
        return body

    with TaskRuntime(num_workers=2) as rt:
        for r in range(4):
            rt.submit(comm(r))
        rt.taskwait()
    for r in range(4):
        res = got[r].result
        assert set(res) == {d for d, _ in hx.neighbors(r)}


def test_task_waits_on_chained_continuation():
    """A task blocks on a continuation-of-a-continuation — chaining
    composes with the task-aware wait."""
    out = {}

    def body():
        rt = tac.current_task()._runtime
        h = tac.EventHandle()
        c1 = rt.continuations.attach(h, lambda: out.setdefault("first", 1))
        c2 = rt.continuations.attach(c1, lambda: out.setdefault("second", 2))
        threading.Thread(target=lambda: h.complete("done")).start()
        tac.wait(c2)
        out["result"] = c1.result

    with TaskRuntime(num_workers=1) as rt:
        rt.submit(body)
        rt.taskwait()
    assert out == {"first": 1, "second": 2, "result": "done"}


# ---------------------------------------------------------------------------
# simulator: callback-dispatch cost
# ---------------------------------------------------------------------------
def _two_task_event_graph():
    return [
        SimTask(0, 0, 1.0, kind=COMPUTE),
        SimTask(1, 0, 0.5, kind=COMM_EVENTS, event_deps=[(0, 2.0)]),
    ]


def test_simulator_dispatch_overhead_shifts_release():
    base = Simulator(1, 1).run(_two_task_event_graph()).makespan
    lag = Simulator(1, 1, dispatch_overhead=0.25).run(
        _two_task_event_graph()).makespan
    assert base == pytest.approx(3.0)         # body 1.0 + edge 2.0
    assert lag == pytest.approx(3.25)         # + one dispatch


def test_simulator_dispatch_overhead_zero_is_identity():
    tasks = [SimTask(i, 0, 0.1, kind=COMM_EVENTS if i else COMPUTE,
                     event_deps=[(0, 1.0)] if i else [])
             for i in range(3)]
    a = Simulator(1, 2).run([SimTask(t.id, t.rank, t.compute, kind=t.kind,
                                     event_deps=list(t.event_deps))
                             for t in tasks]).makespan
    b = Simulator(1, 2, dispatch_overhead=0.0).run(tasks).makespan
    assert a == b


# ---------------------------------------------------------------------------
# striped stats cells (repro.obs.registry.Counter): exact reconciliation
# ---------------------------------------------------------------------------
def test_stats_reconcile_exactly_under_concurrency():
    """The lock-per-increment ``stats`` dict became striped registry
    counters — increments are lock-free, yet the totals must stay EXACT:
    after a full drain every attach has a completion and a dispatch, no
    callback error, no lost count."""
    import collections

    eng = ContinuationEngine(queue_capacity=64)
    n_threads, per = 6, 250
    fired = collections.deque()          # deque.append is atomic

    def churn():
        for _ in range(per):
            h = tac.EventHandle()
            eng.attach(h, lambda: fired.append(1))
            h.complete(None)
            eng.dispatch()

    threads = [threading.Thread(target=churn) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    while eng.dispatch():                # drain any queued residue
        pass

    total = n_threads * per
    s = eng.stats
    assert s["attached"] == total
    assert s["completions"] == total
    # every completion is dispatched exactly once (queued or inline when
    # the bounded queue overflows under the 6-thread churn)
    assert s["dispatches"] == total
    assert s["inline_dispatches"] + (total - s["inline_dispatches"]) == total
    assert s["callback_errors"] == 0
    assert len(fired) == total           # callbacks all ran, exactly once
    # reads are snapshots: a fresh, equal dict each time — not a shared
    # mutable mapping callers could race on
    again = eng.stats
    assert again == s and again is not s
