"""Serving tentpole: continuous batching on the collectives runtime.

Covers the PR's acceptance surface:

1. **Request/queue mechanics** — state-machine legality, priority-then-
   FCFS admission, failure re-admission at the head of the class.
2. **Scheduling** — slot-bounded admission order, stepwise priority
   preemption with ``admission_log``/``eviction_log`` evidence, the
   prefill→round handoff of each request's row, one device call per
   decode round, late admissions joining within ``ROUNDS_AHEAD`` rounds,
   and rows reused after finish and eviction.
3. **Completion legs** — the event-bound and blocking-sentinel legs
   emit bit-identical token streams whether the device step returns a
   push-capable future or a push-less device array, and the event leg
   (``tac.iwait`` binding device completion into task dependencies)
   beats the blocking sentinel on tokens/s AND p99 — the claim
   ``benchmarks/serve_bench.py`` gates in CI.  Over the real model path
   (``LMAdapter`` at smoke width) a round's tokens and logits equal the
   batch-1 decode's, row by row.
4. **Failure path** — a rank killed mid-serve surfaces through the
   stepwise taskwait, in-flight requests are evicted to the queue head,
   the world shrinks (ULFM revoke+shrink), and every request still
   finishes with its full, correct token stream.
5. **Deprecation shims** — the pre-``CollectiveOptions`` keyword
   spellings (``hierarchy=``, ``wire=``) warn but keep working.
"""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tac, FaultInjector
from repro.core.collectives import Collectives
from repro.core.executor import TaskRuntime
from repro.core.options import CollectiveOptions, renamed_kwarg
from repro.serving import (Request, RequestQueue, RequestState,
                           ServingEngine, SyntheticAdapter, percentile,
                           token_at)
from repro.serving.engine import ROUNDS_AHEAD


# ---------------------------------------------------------------------------
# 1. request + queue mechanics
# ---------------------------------------------------------------------------
def test_request_state_machine_legality():
    r = Request(rid=0, prompt=1, gen_len=4)
    assert r.state is RequestState.QUEUED
    r.to(RequestState.PREFILL)
    r.to(RequestState.DECODE)
    with pytest.raises(RuntimeError, match="illegal"):
        r.to(RequestState.QUEUED)
    r.to(RequestState.EVICTED)
    r.reset_for_requeue()
    assert r.state is RequestState.QUEUED
    assert r.incarnation == 1 and r.evictions == 1
    assert r.tokens == [] and r.slot is None
    assert r.chain != Request(rid=0, prompt=1, gen_len=4).chain


def test_queue_priority_then_fcfs_and_push_front():
    q = RequestQueue()
    a = Request(rid=0, prompt=0, gen_len=1, priority=1)
    b = Request(rid=1, prompt=0, gen_len=1, priority=0)
    c = Request(rid=2, prompt=0, gen_len=1, priority=0)
    d = Request(rid=3, prompt=0, gen_len=1, priority=0)
    for r in (a, b, c):
        q.push(r)
    q.push_front(d)      # failure re-admission: head of its class
    assert [q.pop().rid for _ in range(4)] == [3, 1, 2, 0]
    assert q.pop() is None and not q


# ---------------------------------------------------------------------------
# 2. scheduling: admission order, handoff, preemption
# ---------------------------------------------------------------------------
class RecordingAdapter:
    """Synchronous adapter that logs every protocol call."""

    def __init__(self, slots=4):
        self.slots = slots
        self.calls = []
        self.rows = {}          # row -> (request, last step it made)
        self.rounds = []        # row_steps of every round

    def prefill(self, req):
        self.calls.append(("prefill", req.rid, req.incarnation))
        self.rows[req.slot] = (req, 0)
        return np.array([token_at(req.prompt, 0)])

    def decode_round(self, row_steps):
        self.rounds.append(list(row_steps))
        toks = np.zeros(self.slots, np.int64)
        for row, step in enumerate(row_steps):
            if step is None:
                continue
            # the handoff contract: a row decodes the step after the one
            # its state last made (the prefill's for step 1)
            req, last = self.rows[row]
            assert step == last + 1
            self.rows[row] = (req, step)
            self.calls.append(("decode", req.rid, step))
            toks[row] = token_at(req.prompt, step)
        return toks

    def detok(self, req, step, tok):
        return int(tok)


@pytest.mark.parametrize("completion", ["event", "blocking"])
def test_prefill_decode_handoff(completion):
    ad = RecordingAdapter()
    eng = ServingEngine(ad, slots=2, completion=completion, num_workers=2)
    reqs = [Request(rid=i, prompt=10 * i, gen_len=3) for i in range(2)]
    rep = eng.run(reqs)
    for r in reqs:
        assert r.state is RequestState.DONE
        assert rep.outputs[r.rid] == [token_at(10 * r.rid, s)
                                      for s in range(3)]
    for rid in (0, 1):
        assert [c for c in ad.calls if c[1] == rid] == [
            ("prefill", rid, 0), ("decode", rid, 1), ("decode", rid, 2)]
    # both rows advance in the same rounds: one call per step
    assert ad.rounds == [[1, 1, None, None], [2, 2, None, None]]


def test_slot_bounded_admission_order():
    ad = RecordingAdapter()
    eng = ServingEngine(ad, slots=2, completion="blocking", num_workers=2,
                        sync_every=1)
    reqs = [Request(rid=i, prompt=i, gen_len=2) for i in range(5)]
    eng.run(reqs)
    # FCFS admission, never more than `slots` in flight at once
    assert eng.admission_log == [0, 1, 2, 3, 4]
    assert eng.eviction_log == []


def test_priority_preemption_stepwise():
    """A high-priority arrival evicts the worst in-flight request when
    no slot is free (stepwise mode); the victim re-queues to the back
    of its class, re-runs under a new incarnation, and still emits its
    full stream."""

    class SlowAdapter(RecordingAdapter):
        # pace the rounds so the high-priority arrival deterministically
        # lands while the low-priority request is mid-decode
        def decode_round(self, row_steps):
            import time
            time.sleep(0.02)
            return super().decode_round(row_steps)

    ad = SlowAdapter()
    eng = ServingEngine(ad, slots=1, completion="blocking", num_workers=2,
                        sync_every=1)
    low = Request(rid=0, prompt=5, gen_len=6, priority=1)
    high = Request(rid=1, prompt=7, gen_len=2, priority=0,
                   arrival_s=0.05)
    rep = eng.run([low, high])
    assert eng.eviction_log == [0]
    assert low.evictions == 1 and low.state is RequestState.DONE
    assert rep.outputs[0] == [token_at(5, s) for s in range(6)]
    assert rep.outputs[1] == [token_at(7, s) for s in range(2)]
    # the victim restarted from prefill under a new incarnation
    assert ("prefill", 0, 1) in ad.calls


def test_explicit_evict_requires_inflight():
    eng = ServingEngine(RecordingAdapter(), slots=1)
    with pytest.raises(KeyError):
        eng.evict(99)


def test_engine_refuses_adapter_with_fewer_rows():
    with pytest.raises(ValueError, match="rows"):
        ServingEngine(RecordingAdapter(slots=2), slots=3)


@pytest.mark.parametrize("completion", ["event", "blocking"])
def test_one_device_call_per_round(completion):
    """Four requests admitted together: four prefills, then each round
    is ONE device call that decodes every row."""
    with SyntheticAdapter(dev_ms=1.0, host_rounds=1, slots=4) as ad:
        ad.warmup()
        calls = []
        real = ad._device

        def device(seeds, steps):
            calls.append(len(seeds))
            return real(seeds, steps)
        ad._device = device
        reqs = [Request(rid=i, prompt=3 * i, gen_len=5) for i in range(4)]
        rep = ServingEngine(ad, slots=4, completion=completion,
                            num_workers=3).run(reqs)
    assert calls.count(1) == 4 and calls.count(4) == 4
    assert len(calls) == 8
    for r in reqs:
        assert rep.outputs[r.rid] == [token_at(r.prompt, s)
                                      for s in range(5)]


@pytest.mark.parametrize("completion", ["event", "blocking"])
def test_late_admission_joins_within_rounds_ahead(completion):
    """A request admitted while rounds run decodes its first step in
    one of the ``ROUNDS_AHEAD`` rounds dispatched after its prefill."""
    with SyntheticAdapter(dev_ms=2.0, host_rounds=1, slots=2) as ad:
        ad.warmup()
        log = []
        real_prefill, real_round = ad.prefill, ad.decode_round

        def prefill(req):
            log.append(("prefill", req.rid, req.slot))
            return real_prefill(req)

        def decode_round(row_steps):
            log.append(("round", list(row_steps)))
            return real_round(row_steps)
        ad.prefill, ad.decode_round = prefill, decode_round
        early = Request(rid=0, prompt=4, gen_len=40)
        late = Request(rid=1, prompt=9, gen_len=5, arrival_s=0.03)
        rep = ServingEngine(ad, slots=2, completion=completion,
                            num_workers=3).run([early, late])
    at = next(i for i, e in enumerate(log) if e[:2] == ("prefill", 1))
    rounds = [e[1] for e in log[at + 1:] if e[0] == "round"]
    row = log[at][2]
    first = next(i for i, rs in enumerate(rounds) if rs[row] is not None)
    assert first < ROUNDS_AHEAD and rounds[first][row] == 1
    # the early request kept decoding while the late one was admitted
    assert any(rs[1 - row] is not None for rs in rounds[:first + 1])
    assert rep.outputs[1] == [token_at(9, s) for s in range(5)]
    assert rep.outputs[0] == [token_at(4, s) for s in range(40)]


@pytest.mark.parametrize("completion", ["event", "blocking"])
def test_round_accounting_under_thread_churn(completion):
    """More workers than cores and a tiny switch interval: every token
    still arrives once, in step order, and the count of rounds in flight
    returns to zero (a lost update of it would stall or overrun)."""
    import os
    import sys
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with SyntheticAdapter(dev_ms=0.0, host_rounds=1, slots=4) as ad:
            ad.warmup()
            eng = ServingEngine(ad, slots=4, completion=completion,
                                num_workers=(os.cpu_count() or 4) + 2)
            reqs = [Request(rid=i, prompt=5 * i, gen_len=3 + i % 7)
                    for i in range(24)]
            rep = eng.run(reqs)
    finally:
        sys.setswitchinterval(old)
    assert eng._ahead == 0 and sorted(eng._free) == [0, 1, 2, 3]
    for r in reqs:
        assert [s for s, _ in r.tokens] == list(range(r.gen_len))
        assert rep.outputs[r.rid] == [token_at(r.prompt, s)
                                      for s in range(r.gen_len)]


def test_rows_reused_after_finish_and_eviction():
    """Rows go back to the free list: an evicted request's row goes to
    the next admission, and a finished request's row to the one after."""

    class RowLog(RecordingAdapter):
        def __init__(self):
            super().__init__(slots=2)
            self.admitted = []

        def prefill(self, req):
            self.admitted.append((req.rid, req.slot))
            return super().prefill(req)

    ad = RowLog()

    def on_round(eng, rnd):
        if rnd == 2:
            eng.evict(0)

    eng = ServingEngine(ad, slots=2, completion="blocking", num_workers=2,
                        sync_every=1, on_round=on_round)
    reqs = [Request(rid=0, prompt=1, gen_len=6),
            Request(rid=1, prompt=2, gen_len=6),
            Request(rid=2, prompt=3, gen_len=2)]
    rep = eng.run(reqs)
    # 0 and 1 take rows 0 and 1; evicting 0 frees row 0 for 2; 2's
    # finish frees it for 0's second incarnation
    assert ad.admitted == [(0, 0), (1, 1), (2, 0), (0, 0)]
    assert eng.eviction_log == [0]
    for r in reqs:
        assert r.state is RequestState.DONE
        assert rep.outputs[r.rid] == [token_at(r.prompt, s)
                                      for s in range(r.gen_len)]


# ---------------------------------------------------------------------------
# 3. completion legs: parity and performance
# ---------------------------------------------------------------------------
class _ArrayTokens(SyntheticAdapter):
    """The synthetic adapter returning each step's tokens as a device
    array, as :class:`repro.serving.lm.LMAdapter` does on the chip: a
    push-less handle the continuation engine re-tests from its fallback
    poll list.  A jitted busy loop keeps the array in flight for a while
    after dispatch, so the wait task usually finds it not yet ready."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self._busy = jnp.eye(128, dtype=jnp.float32)

        def tokens(busy, seeds, steps):
            busy = jax.lax.fori_loop(0, 64, lambda _, b: jnp.tanh(b @ b),
                                     busy)
            tok = (seeds + 31 * steps + 7) % 997
            return jnp.where(jnp.isnan(busy[0, 0]), -1, tok)

        self._tokens = jax.jit(tokens)

    def warmup(self):
        for n in (1, self.slots):
            z = jnp.zeros((n,), jnp.int32)
            self._tokens(self._busy, z, z).block_until_ready()

    def prefill(self, req):
        seed = int(req.prompt)
        self._seeds[req.slot] = seed
        return self._tokens(self._busy, jnp.array([seed], jnp.int32),
                            jnp.zeros(1, jnp.int32))

    def decode_round(self, row_steps):
        steps = [0 if s is None else s for s in row_steps]
        return self._tokens(self._busy, jnp.asarray(self._seeds),
                            jnp.array(steps, jnp.int32))


@pytest.mark.parametrize("tokens", ["future", "array"])
@pytest.mark.parametrize("completion", ["event", "blocking"])
def test_token_parity_mode_x_tokens(completion, tokens):
    """Both completion legs produce the same (deterministic) token
    streams, whether the adapter's device step returns a push-capable
    future or a push-less device array."""
    adapter = {"future": SyntheticAdapter, "array": _ArrayTokens}[tokens]
    with adapter(dev_ms=1.0, host_rounds=1) as ad:
        ad.warmup()
        with TaskRuntime(num_workers=3) as rt:
            eng = ServingEngine(ad, slots=4, completion=completion,
                                runtime=rt)
            reqs = [Request(rid=i, prompt=30 + 7 * i, gen_len=4)
                    for i in range(6)]
            rep = eng.run(reqs)
            stats = rt.continuations.stats
    for r in reqs:
        assert rep.outputs[r.rid] == [token_at(r.prompt, s)
                                      for s in range(4)]
    assert rep.tokens == 24 and rep.recoveries == 0
    if completion == "event":
        assert stats["attached"] > 0
        if tokens == "future":
            assert stats["tests"] == 0      # every handle pushed
        else:
            assert stats["tests"] > 0       # rode the fallback poll list


def test_event_leg_outperforms_blocking_sentinel():
    """The PR's acceptance claim, asserted: with asynchronous device
    latency and 16 rows of host detokenisation per round, the
    event-bound leg sustains at least the blocking sentinel's throughput
    with no worse p99 — the blocking leg holds the device chain through
    every device wait and detok, the event leg frees it at dispatch
    (tac.iwait -> continuation engine) and detoks while the next rounds
    run."""
    with SyntheticAdapter(dev_ms=25.0, host_rounds=8, slots=16) as ad:
        ad.warmup()
        reports = {}
        for leg in ("event", "blocking"):
            # warm pass: pools, runtime, code paths
            ServingEngine(ad, slots=16, completion=leg, num_workers=4) \
                .run([Request(rid=900 + i, prompt=i, gen_len=2)
                      for i in range(4)])
            # a full collection (~70 ms with JAX loaded) must not land in
            # one leg's timed run alone
            gc.collect()
            eng = ServingEngine(ad, slots=16, completion=leg,
                                num_workers=4)
            reports[leg] = eng.run(
                [Request(rid=i, prompt=100 + 17 * i, gen_len=6)
                 for i in range(16)])
    ev, bl = reports["event"], reports["blocking"]
    assert ev.tokens == bl.tokens == 96
    assert ev.tokens_per_s >= bl.tokens_per_s, (
        f"event {ev.tokens_per_s:.0f} < blocking {bl.tokens_per_s:.0f}")
    assert ev.p99_ms <= bl.p99_ms, (
        f"event p99 {ev.p99_ms:.1f} > blocking p99 {bl.p99_ms:.1f}")


def test_event_leg_pushes_through_continuation_engine():
    """The event leg's device handles are push-capable futures: the
    continuation engine must see real attaches (iwait on in-flight
    device work), not the always-ready fast path."""
    with SyntheticAdapter(dev_ms=3.0, host_rounds=1) as ad:
        ad.warmup()
        rt = TaskRuntime(num_workers=3)
        eng = ServingEngine(ad, slots=4, completion="event", runtime=rt)
        eng.run([Request(rid=i, prompt=i, gen_len=3) for i in range(4)])
        stats = rt.continuations.stats
        rt.close()
    assert stats["attached"] > 0
    assert stats["completions"] >= stats["attached"]


@pytest.mark.parametrize("completion", ["event", "blocking"])
def test_engine_traces_queue_wait_and_slots_in_flight(completion):
    """One ``serving/queued`` span per request, one
    ``serving.inflight_at_dispatch`` observation per device step (a
    prefill or a round), never above ``slots``, and one
    ``serving.rows_per_round`` observation per round; nothing observed
    with tracing off."""
    from repro import obs
    hist = obs.REGISTRY.histogram("serving.inflight_at_dispatch", base=1.0)
    rows = obs.REGISTRY.histogram("serving.rows_per_round", base=1.0)
    with SyntheticAdapter(dev_ms=1.0, host_rounds=1) as ad:
        ad.warmup()
        hist.reset()
        rows.reset()
        with obs.tracing() as tr:
            ServingEngine(ad, slots=3, completion=completion,
                          num_workers=3).run(
                [Request(rid=i, prompt=i, gen_len=4) for i in range(7)])
        seen, seen_rows = hist.summary(), rows.summary()
        ServingEngine(ad, slots=3, completion=completion,
                      num_workers=3).run([Request(rid=9, prompt=9,
                                                  gen_len=2)])
    evs = tr.events()
    assert obs.validate_trace(evs) == []
    queued = [e for e in evs if e["cat"] == "serving"
              and e["name"] == "queued"]
    assert sorted(e["args"]["rid"] for e in queued) == list(range(7))
    steps = [e for e in evs if e["cat"] == "serving"
             and e["name"] == "device_step"]
    prefills = [e for e in steps if e["args"]["kind"] == "prefill"]
    rounds = [e for e in steps if e["args"]["kind"] == "decode"]
    assert len(prefills) == 7 and len(prefills) + len(rounds) == len(steps)
    assert all(e["args"]["rows"] == 1 for e in prefills)
    assert sum(e["args"]["rows"] for e in rounds) == 7 * 3
    assert seen["count"] == len(steps)
    assert 1 <= seen["min"] and seen["max"] <= 3
    assert seen_rows["count"] == len(rounds)
    assert seen_rows["sum"] == 7 * 3 and seen_rows["max"] <= 3
    assert hist.summary() == seen and rows.summary() == seen_rows


def test_lm_prefill_traces_pad_cache():
    from repro import configs, obs
    from repro.launch import mesh as meshlib
    from repro.models import model
    from repro.runtime.sharding import ShardingPolicy
    from repro.serving.lm import LMAdapter
    cfg = configs.smoke("granite-3-2b")
    params = jax.jit(lambda k: model.init(cfg, k))(jax.random.PRNGKey(0))
    ad = LMAdapter(cfg, meshlib.local_mesh(model=1),
                   ShardingPolicy(fsdp=False, tp=False, sp=False,
                                  remat=None),
                   params, prompt_len=8, gen_len=2)
    req = Request(rid=5, prompt=1, gen_len=2)
    req.slot = 3
    with obs.tracing() as tr:
        ad.prefill(req)
    pads = [e for e in tr.events() if e["name"] == "pad_cache"]
    assert [(e["cat"], e["args"]["rid"]) for e in pads] == [("serving", 5)]


def test_percentile_nearest_rank():
    assert percentile([3.0, 1.0, 2.0], 50) == 2.0
    assert percentile([1.0], 99) == 1.0
    with pytest.raises(ValueError):
        percentile([], 50)


# ---------------------------------------------------------------------------
# 4. failure path: eviction under injected rank failure
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("completion", ["event", "blocking"])
def test_eviction_under_rank_failure(completion):
    """Kill a rank mid-serve: the TP collective fails the micro-step,
    the stepwise taskwait surfaces it, in-flight requests evict to the
    queue head, the world shrinks, and every request re-runs to a full
    correct stream under a new incarnation."""
    with SyntheticAdapter(dev_ms=1.0, host_rounds=1) as ad:
        ad.warmup()
        w = tac.CommWorld(3)
        inj = FaultInjector(w)
        killed = []

        def on_round(eng, rnd):
            if rnd == 4 and not killed:
                inj.kill(2)
                killed.append(2)

        eng = ServingEngine(ad, slots=3, completion=completion,
                            num_workers=4, sync_every=1, world=w,
                            tp_elems=4, on_round=on_round)
        reqs = [Request(rid=i, prompt=50 + 11 * i, gen_len=5)
                for i in range(6)]
        rep = eng.run(reqs)
    assert killed and rep.recoveries == 1
    assert rep.evictions > 0 and eng.eviction_log
    # the engine rebuilt its collectives over the shrunken world
    assert eng._coll.world.size == 2
    for r in reqs:
        assert r.state is RequestState.DONE
        assert rep.outputs[r.rid] == [token_at(r.prompt, s)
                                      for s in range(5)]
    # evicted requests re-ran under a bumped incarnation
    assert any(r.incarnation > 0 for r in reqs)


def test_failed_step_does_not_finish_request():
    """A request whose micro-step failed must NOT retire DONE with a
    short stream (the force-released finish task runs anyway); it stays
    in flight for the failure sweep."""

    class FailOnce(RecordingAdapter):
        def __init__(self):
            super().__init__()
            self.failed = False

        def decode_round(self, row_steps):
            if 2 in row_steps and not self.failed:
                self.failed = True
                raise tac.RankFailedError("injected")
            return super().decode_round(row_steps)

    ad = FailOnce()
    w = tac.CommWorld(2)
    eng = ServingEngine(ad, slots=1, completion="blocking",
                        num_workers=2, sync_every=1, world=w)
    req = Request(rid=0, prompt=3, gen_len=4)
    rep = eng.run([req])
    assert req.state is RequestState.DONE
    assert rep.outputs[0] == [token_at(3, s) for s in range(4)]
    assert req.evictions == 1 and eng.recoveries == 1


# ---------------------------------------------------------------------------
# 5. deprecation shims (pre-CollectiveOptions spellings)
# ---------------------------------------------------------------------------
def test_collectives_hierarchy_kwarg_warns():
    w = tac.CommWorld(4)
    with pytest.warns(DeprecationWarning, match="hierarchy"):
        c = Collectives(w, hierarchy=2)
    assert c.hierarchy == 2


def test_renamed_kwarg_contract():
    with pytest.warns(DeprecationWarning, match="old_k"):
        assert renamed_kwarg("old_k", 5, "new_k", None) == 5
    assert renamed_kwarg("old_k", None, "new_k", 7) == 7
    with pytest.raises(TypeError, match="both"):
        renamed_kwarg("old_k", 5, "new_k", 7)


def test_lowering_wire_kwarg_warns():
    """``lowering.allreduce(wire=...)`` maps onto ``stage_wire=``: the
    shim warns, and the value lands where ``stage_wire`` lands (the
    native path rejects BOTH spellings with the same message — proof
    the deprecated kwarg reached the canonical slot).  The multi-device
    numeric path is covered by tests/test_lowering.py."""
    from repro.core import lowering
    x = jnp.ones((8,), jnp.float32)
    with pytest.warns(DeprecationWarning, match="stage_wire"):
        with pytest.raises(ValueError, match="stage_impl=/stage_wire="):
            lowering.allreduce(x, ("data",), algorithm="native",
                               wire="bf16")
    with pytest.raises(ValueError, match="stage_impl=/stage_wire="):
        lowering.allreduce(x, ("data",), algorithm="native",
                           stage_wire="bf16")


def test_sync_grads_wire_kwarg_warns():
    from repro.core.overlap import sync_grads
    x = jnp.ones((4,), jnp.float32)
    with pytest.warns(DeprecationWarning, match="reduce_dtype"):
        out = sync_grads({"w": x}, axes=(), mode="fused", wire="fp32")
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(x))


def test_collective_options_spec():
    opts = CollectiveOptions(algorithm="ring", segments=4)
    algorithm, segments = CollectiveOptions.merge(
        opts, algorithm=None, segments=1)
    assert (algorithm, segments) == ("ring", 4)
    # explicit keyword beats the spec
    [algorithm] = CollectiveOptions.merge(
        CollectiveOptions(algorithm="ring"), algorithm="recursive")
    assert algorithm == "recursive"
    with pytest.raises(ValueError, match="not.*applicable"):
        CollectiveOptions(stage_wire="bf16").take(algorithm=None)
