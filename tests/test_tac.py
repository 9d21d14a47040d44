"""Tests for TAC (the TAMPI analogue): blocking + non-blocking modes (§6)."""

import threading
import time

import jax
import jax.numpy as jnp
import pytest

from repro.core import TaskRuntime, tac


@pytest.fixture(autouse=True)
def _task_multiple():
    tac.init(tac.TASK_MULTIPLE)
    yield
    tac.init(tac.TASK_MULTIPLE)


def test_threading_levels():
    assert tac.TASK_MULTIPLE > tac.THREAD_MULTIPLE
    assert tac.init(tac.TASK_MULTIPLE) == tac.TASK_MULTIPLE
    assert tac.is_enabled()
    assert tac.init(tac.THREAD_MULTIPLE) == tac.THREAD_MULTIPLE
    assert not tac.is_enabled()


def test_array_handle_completion():
    x = jnp.arange(8.0)
    h = tac.run_async(jax.jit(lambda v: v * 2), x)
    assert h.wait() is h.result
    assert h.test()
    assert jnp.allclose(h.result, x * 2)


def test_blocking_wait_inside_task_pauses():
    """Fig. 3 path: incomplete handle → attach → pause → push → resume."""
    h = tac.EventHandle()
    got = []

    def comm_task():
        got.append(tac.wait(h))

    with TaskRuntime(num_workers=2) as rt:
        rt.submit(comm_task)
        time.sleep(0.05)           # let it reach the pause
        assert rt.stats.get("task_blocks", 0) == 1
        h.complete("payload")
        rt.taskwait()
    assert got == ["payload"]
    assert rt.stats["task_resumes"] == 1


def test_blocking_wait_completed_handle_no_pause():
    h = tac.EventHandle()
    h.complete(42)
    with TaskRuntime(num_workers=1) as rt:
        t = rt.submit(lambda: tac.wait(h))
        rt.taskwait()
    assert t.result == 42
    assert rt.stats.get("task_blocks", 0) == 0


def test_iwait_defers_release_not_execution():
    """Fig. 4/5: the communication task finishes immediately; the consumer
    runs only once the bound operation completes."""
    h = tac.EventHandle()
    order = []

    def comm_task():
        tac.iwait(h)
        order.append("comm-body-done")

    def consumer():
        order.append("consumer")

    with TaskRuntime(num_workers=4) as rt:
        rt.submit(comm_task, out=["buf"])
        rt.submit(consumer, in_=["buf"])
        deadline = time.time() + 0.3
        while "comm-body-done" not in order and time.time() < deadline:
            time.sleep(0.005)
        assert order == ["comm-body-done"]
        h.complete()
        rt.taskwait()
    assert order == ["comm-body-done", "consumer"]
    assert rt.stats.get("task_blocks", 0) == 0  # no pause: non-blocking mode


def test_iwaitall_multiple_events():
    hs = [tac.EventHandle() for _ in range(3)]
    hs[1].complete()  # one completes immediately — must not be bound
    done = []

    def comm_task():
        tac.iwaitall(hs)

    with TaskRuntime(num_workers=2) as rt:
        rt.submit(comm_task, out=["b"])
        rt.submit(lambda: done.append(1), in_=["b"])
        time.sleep(0.1)
        assert not done
        hs[0].complete()
        time.sleep(0.1)
        assert not done
        hs[2].complete()
        rt.taskwait()
    assert done == [1]


def test_commworld_ordering_and_tags():
    w = tac.CommWorld(2)
    w.isend("a", src=0, dst=1, tag=9)
    w.isend("b", src=0, dst=1, tag=9)
    r1 = w.irecv(src=0, dst=1, tag=9)
    r2 = w.irecv(src=0, dst=1, tag=9)
    assert r1.result == "a" and r2.result == "b"  # non-overtaking

    r3 = w.irecv(src=1, dst=0, tag=5)
    assert not r3.test()
    w.isend("c", src=1, dst=0, tag=5)
    assert r3.test() and r3.result == "c"


def test_ssend_completes_on_match():
    w = tac.CommWorld(2)
    s = w.isend("x", src=0, dst=1, synchronous=True)
    assert not s.test()
    r = w.irecv(src=0, dst=1)
    assert s.test() and r.result == "x"


@pytest.mark.parametrize("mode", ["nested", "spare-thread"])
def test_deadlock_avoidance_section5(mode):
    """Paper §5: one worker, task A does a synchronous-mode send, task B the
    matching receive.  With plain blocking semantics this deadlocks; with
    TASK_MULTIPLE the pause/resume API lets the worker run B while A is
    paused, completing both."""
    w = tac.CommWorld(2)
    results = []

    def sender():
        w.ssend("ping", src=0, dst=1)   # blocks until matched
        results.append("sent")

    def receiver():
        results.append(w.recv(src=0, dst=1))

    with TaskRuntime(num_workers=1, block_mode=mode) as rt:
        rt.submit(sender)
        rt.submit(receiver)
        rt.taskwait()
    assert sorted(results) == ["ping", "sent"]


def test_fallback_is_the_sentinel_world():
    """With only THREAD_MULTIPLE, tac.wait degenerates to a plain blocking
    wait (the PMPI path): the §5 pattern now genuinely deadlocks unless the
    program serialises communication tasks — verify the blocking behaviour
    on a completed handle path (safe) and that no pause is recorded."""
    tac.init(tac.THREAD_MULTIPLE)
    h = tac.EventHandle()
    threading.Timer(0.05, h.complete, args=("late",)).start()
    with TaskRuntime(num_workers=1) as rt:
        t = rt.submit(lambda: tac.wait(h))
        rt.taskwait()
    assert t.result == "late"
    assert rt.stats.get("task_blocks", 0) == 0  # worker blocked in-place


def test_many_inflight_small_messages_nonblocking():
    """Stress the non-blocking mode: many communication tasks, none pause."""
    w = tac.CommWorld(2)
    n = 200
    received = []

    def send_task(i):
        w.isend(i, src=0, dst=1, tag=i)

    def recv_task(i):
        h = w.irecv(src=0, dst=1, tag=i)
        tac.iwait(h)
        # body finishes; release deferred until message arrival

    def collect(i):
        received.append(i)

    with TaskRuntime(num_workers=4) as rt:
        for i in range(n):
            rt.submit(recv_task, i, out=[("buf", i)])
            rt.submit(collect, i, in_=[("buf", i)])
        for i in range(n):
            rt.submit(send_task, i)
        rt.taskwait()
    assert sorted(received) == list(range(n))
    assert rt.stats.get("task_blocks", 0) == 0
