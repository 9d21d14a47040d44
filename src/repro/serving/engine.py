"""Continuous-batching inference engine on the task runtime.

The serving tentpole: requests flow through a
:class:`repro.serving.queue.RequestQueue`, an admission/eviction
scheduler keeps at most ``slots`` of them in flight, each in a row of the
adapter's decode cache, and EVERY unit of work — each prefill, each
decode round, each host detokenisation — is one
:class:`repro.core.executor.TaskRuntime` task.  A decode round advances
every row of the cache in one device program, so the weights are read
once per round, not once per token.  Prefills and rounds update the same
cache, so one dataflow token (the engine's device chain) orders them; a
request's own token (``Request.chain``) orders its emissions and its
retirement.  A request admitted while rounds run joins the next round
after its prefill.

Two completion legs, selected by ``completion=``:

* ``"event"`` (the paper's discipline) — the prefill or round task only
  *dispatches* device work and returns, so the device chain is released
  at dispatch and the round's tokens feed the next round on the device;
  a wait task binds the tokens' completion through the unified
  :class:`repro.core.tac.AsyncHandle` protocol (``tac.iwait`` →
  continuation engine), and a detok task that depends on it emits every
  row in step order (Fig. 5: consumers declare dependencies).  At most
  ``ROUNDS_AHEAD`` rounds are dispatched ahead of their tokens.
* ``"blocking"`` (the sentinel baseline of paper §7.1) — the round task
  synchronises the device result and detokenises every row inline,
  chaining host work into the device chain exactly like the artificial
  sentinel dependency the paper removes.

Both legs emit identical tokens (asserted by
``tests/test_serving.py``); ``benchmarks/serve_bench.py`` measures the
throughput/latency gap.

Failure handling reuses the ULFM path of :mod:`repro.core.resilience`:
run the engine stepwise (``sync_every=1``) over a
:class:`repro.core.tac.CommWorld` and a tensor-parallel allreduce rides
every device step; when a rank dies, the collective surfaces
:class:`~repro.core.tac.RankFailedError` out of ``taskwait``, the
scheduler evicts every in-flight request back to the queue head,
revokes + shrinks the world (:func:`repro.core.resilience.recover`),
rebuilds the collectives over the survivors, and re-admits — each
request restarts from prefill under a fresh incarnation, so its state
machine survives the failure.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from ..core import resilience, tac
from ..core.collectives import Collectives
from ..core.executor import TaskError, TaskRuntime
from ..core.tac import CommRevokedError, RankFailedError
from ..obs import trace as _tr
from ..obs.metrics import MetricSink, TokenRecord
from ..obs.registry import REGISTRY as _REGISTRY
from .metrics import ServeReport
from .queue import RequestQueue
from .request import Request, RequestState

__all__ = ["ServingEngine", "ROUNDS_AHEAD"]

#: decode rounds dispatched ahead of their tokens' emission: enough to
#: hide the host's turnaround behind the device, few enough that a newly
#: admitted request joins within that many rounds.
ROUNDS_AHEAD = 2


class ServingEngine:
    """Admission/eviction scheduler + task-graph executor for serving.

    ``adapter`` supplies the model, over a decode cache of
    ``adapter.slots`` rows: ``prefill(request)`` dispatches the prompt
    pass into the request's row (``request.slot``) and returns its first
    token (device value, one element); ``decode_round(row_steps)``
    dispatches one decode step of every row (``row_steps[r]`` is the
    step row r decodes, None for an empty row) and returns the rows'
    tokens; ``detok(request, step, tok)`` is the host-side
    detokenisation of one device-complete token.

    ``slots`` bounds concurrent in-flight requests and may not exceed
    the adapter's rows; ``priority`` decides preemption — in stepwise
    mode a queued request preempts (evicts) a strictly lower-priority
    in-flight one when no slot is free.

    ``world=`` + ``tp_elems>0`` adds a tensor-parallel allreduce over
    the communicator to every device step; with ``sync_every=1`` a rank
    failure is recovered ULFM-style (see module docstring).
    """

    def __init__(self, adapter: Any, *, slots: int = 4,
                 completion: str = "event",
                 runtime: Optional[TaskRuntime] = None,
                 num_workers: Optional[int] = None,
                 sync_every: int = 0,
                 world: Any = None, tp_elems: int = 0,
                 on_round: Optional[Callable[["ServingEngine", int],
                                             None]] = None) -> None:
        if completion not in ("event", "blocking"):
            raise ValueError(f"unknown completion leg {completion!r}; "
                             f"one of ['event', 'blocking']")
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if adapter.slots < slots:
            raise ValueError(f"the adapter decodes {adapter.slots} rows, "
                             f"fewer than the engine's {slots} slots")
        if sync_every < 0:
            raise ValueError(f"sync_every must be >= 0, got {sync_every}")
        self.adapter = adapter
        self.slots = slots
        self.completion = completion
        if completion == "event":
            # The event leg NEEDS the TASK_MULTIPLE interoperability
            # level: without it tac.iwait degrades to a blocking wait
            # inside the wait task body (the legacy-library fallback
            # of §6.3) and the leg silently becomes the sentinel.
            tac.init(tac.TASK_MULTIPLE)
        self.sync_every = sync_every
        self.on_round = on_round
        self._ext_runtime = runtime
        self._num_workers = num_workers or max(4, slots + 2)

        self._world = world
        self._tp_elems = tp_elems
        self._comm = world
        self._coll = (Collectives(world) if world is not None
                      and tp_elems > 0 else None)

        self._lock = threading.Lock()
        self.queue = RequestQueue()
        self.active: Dict[int, Request] = {}
        self.metrics = MetricSink()
        self.recoveries = 0
        self.admission_log: List[int] = []   # rids, in admission order
        self.eviction_log: List[int] = []    # rids, in eviction order
        self._t0 = 0.0
        self._free = list(range(slots))      # rows no request holds
        self._chain = f"device-{id(self)}"   # orders prefills and rounds
        self._steps = 0                      # device steps submitted
        self._toks: Dict[int, Any] = {}      # device step -> its tokens
        self._ahead = 0                      # rounds not yet emitted
        self._wake = threading.Event()       # a round's tokens emitted

    # -- task bodies --------------------------------------------------------
    def _tp_allreduce(self, k: int) -> None:
        """The tensor-parallel leg of one device step (optional)."""
        if self._coll is None:
            return
        n = self._coll.world.size
        val = np.ones(self._tp_elems, np.float32)
        self._coll.run_group(
            "allreduce", [{"value": val} for _ in range(n)],
            op="sum", key=("tp", k))

    def _device_step(self, k: int, kind: str, entries: List[tuple],
                     dispatch: Callable[[], Any]) -> None:
        """One prefill or decode round on the engine's device chain.

        ``entries`` are the ``(request, incarnation, step, index)`` of
        the tokens it makes, ``index`` into the tokens ``dispatch()``
        returns.  Event leg: dispatch and return — the chain is released
        at dispatch, and the wait task binds the tokens' completion.
        Blocking-sentinel leg: OS-blocking ``handle.wait()`` (the PMPI
        path) plus host detok INSIDE the device chain — the artificial
        serialisation of paper §7.1.
        """
        t0 = 0.0
        if _tr.TRACING:
            t0 = time.monotonic()
            _REGISTRY.histogram("serving.inflight_at_dispatch",
                                base=1.0).observe(len(self.active))
            if kind == "decode":
                _REGISTRY.histogram("serving.rows_per_round",
                                    base=1.0).observe(len(entries))
        try:
            self._tp_allreduce(k)
            toks = dispatch()
            if self.completion == "event":
                self._toks[k] = toks
            else:
                self._emit_all(entries, tac.as_handle(toks).wait())
        finally:
            if self.completion == "blocking" and kind == "decode":
                self._round_emitted()
        if _tr.TRACING:
            _tr.TRACER.span("serving", "device_step", t0, time.monotonic(),
                            kind=kind, rows=len(entries),
                            completion=self.completion)

    def _prefill(self, req: Request) -> Any:
        tok = self.adapter.prefill(req)
        with self._lock:
            if req.state is RequestState.PREFILL:
                req.to(RequestState.DECODE)
        return tok

    def _wait_task(self, k: int) -> None:
        """Event leg: bind this task's release to the tokens' completion
        (``tac.iwait``), so the detok task's dependency opens exactly
        when they are ready and nobody ever blocks a worker."""
        toks = self._toks.get(k)
        if toks is not None:        # None: the producing step failed
            tac.iwait(tac.as_handle(toks))

    def _detok_task(self, k: int, kind: str, entries: List[tuple]) -> None:
        """Event-leg host consumer: runs once the wait task released —
        i.e. once the device values completed — and emits every row."""
        try:
            toks = self._toks.pop(k, None)
            if toks is None:
                return      # the producing step failed; nothing to emit
            t0 = time.monotonic() if _tr.TRACING else 0.0
            self._emit_all(entries, tac.as_handle(toks).wait())
            if _tr.TRACING:
                _tr.TRACER.span("serving", "detok", t0, time.monotonic(),
                                kind=kind, rows=len(entries))
        finally:
            if kind == "decode":
                self._round_emitted()

    def _round_emitted(self) -> None:
        with self._lock:
            self._ahead -= 1
        self._wake.set()

    def _emit_all(self, entries: List[tuple], toks: Any) -> None:
        vals = np.asarray(toks)
        for req, incarnation, step, index in entries:
            if req.incarnation == incarnation:  # not evicted since
                self._emit(req, step, vals[index])

    def _emit(self, req: Request, step: int, tok: Any) -> None:
        val = self.adapter.detok(req, step, tok)
        now = time.monotonic() - self._t0
        with self._lock:
            req.tokens.append((step, val))
            self.metrics.emit(TokenRecord(
                rid=req.rid, step=step,
                t_submit=req._t_submit[step],    # type: ignore[attr-defined]
                t_emit=now))

    def _finish(self, req: Request) -> None:
        """Retire the request and free its row — but only if every token
        actually emitted.  A failed device step force-releases its
        dependents (so the graph drains instead of hanging), which means
        this task can run on an incomplete request: leave it in flight
        and let the failure sweep of ``_handle_failure`` evict + re-admit
        it."""
        with self._lock:
            if req.state is RequestState.DECODE \
                    and len(req.tokens) == req.gen_len:
                req.to(RequestState.DONE)
                req.finished_at = time.monotonic() - self._t0
                self.active.pop(req.rid, None)
                self._free.append(req.slot)

    # -- scheduling ---------------------------------------------------------
    def _admit(self, req: Request) -> None:
        now = time.monotonic()
        if _tr.TRACING:
            _tr.TRACER.span("serving", "queued", self._t0 + req.arrival_s,
                            now, rid=req.rid)
        with self._lock:
            req.to(RequestState.PREFILL)
            req.admitted_at = now - self._t0
            req._t_submit = {}          # type: ignore[attr-defined]
            req.slot = min(self._free)
            self._free.remove(req.slot)
            self.active[req.rid] = req
            self.admission_log.append(req.rid)

    def _evict(self, req: Request, *, front: bool) -> None:
        """Free the request's row and return it to the queue."""
        with self._lock:
            req.to(RequestState.EVICTED)
            req.reset_for_requeue()
            self.active.pop(req.rid, None)
            self._free.append(req.slot)
            req.slot = None
            self.eviction_log.append(req.rid)
        (self.queue.push_front if front else self.queue.push)(req)

    def evict(self, rid: int) -> None:
        """Explicit preemption hook (stepwise mode: call from
        ``on_round``, between fully-drained rounds)."""
        req = self.active.get(rid)
        if req is None:
            raise KeyError(f"request {rid} is not in flight")
        self._evict(req, front=False)

    def _preempt(self) -> None:
        """Evict the worst in-flight request when the queue head is
        strictly more urgent and no slot is free (stepwise only)."""
        while True:
            head = self.queue.peek()
            if head is None or len(self.active) < self.slots:
                return
            with self._lock:
                victim = max(self.active.values(),
                             key=lambda r: (r.priority, r.rid),
                             default=None)
            if victim is None or victim.priority <= head.priority:
                return
            self._evict(victim, front=False)

    def _submit_device(self, rt: TaskRuntime, kind: str,
                       entries: List[tuple],
                       dispatch: Callable[[], Any]) -> None:
        k = self._steps
        self._steps += 1
        emits = [req.chain for req, *_ in entries]
        if self.completion == "event":
            # The device task WRITES the step's tokens; the wait task
            # reads them and iwaits the device handle, so the detok task's
            # read of ``ready`` opens at device completion.  Device steps
            # depend only on the device chain — detok never sits on it.
            made, ready = (self._chain, "tok", k), (self._chain, "ready", k)
            rt.submit(self._device_step, k, kind, entries, dispatch,
                      inout=[self._chain], out=[made], name=f"{kind}:{k}")
            rt.submit(self._wait_task, k, in_=[made], out=[ready],
                      name=f"wait:{k}")
            rt.submit(self._detok_task, k, kind, entries, in_=[ready],
                      inout=emits, name=f"detok:{k}")
        else:
            rt.submit(self._device_step, k, kind, entries, dispatch,
                      inout=[self._chain] + emits, name=f"{kind}:{k}")
        for req, _, step, _ in entries:
            if step + 1 == req.gen_len:
                # finish orders after the request's emissions, so the
                # completeness check in _finish sees every token.
                rt.submit(self._finish, req, inout=[req.chain],
                          name=f"finish:{req.rid}")

    def _submit_prefill(self, rt: TaskRuntime, req: Request) -> None:
        now = time.monotonic() - self._t0
        req._t_submit[0] = now          # type: ignore[attr-defined]
        req.submitted_steps = 1
        self._submit_device(rt, "prefill", [(req, req.incarnation, 0, 0)],
                            lambda: self._prefill(req))

    def _submit_round(self, rt: TaskRuntime,
                      runnable: List[Request]) -> None:
        now = time.monotonic() - self._t0
        row_steps: List[Optional[int]] = [None] * self.adapter.slots
        entries = []
        for req in sorted(runnable, key=lambda r: r.slot):
            step = req.submitted_steps
            req._t_submit[step] = now       # type: ignore[attr-defined]
            req.submitted_steps = step + 1
            row_steps[req.slot] = step
            entries.append((req, req.incarnation, step, req.slot))
        with self._lock:
            self._ahead += 1
        self._submit_device(rt, "decode", entries,
                            lambda: self.adapter.decode_round(row_steps))

    def _handle_failure(self, err: BaseException) -> None:
        """ULFM recovery: evict in-flight requests, shrink, rebuild."""
        if not isinstance(err, (RankFailedError, CommRevokedError)) \
                or self._world is None:
            raise err
        with self._lock:
            inflight = [r for r in self.active.values()
                        if r.state in (RequestState.PREFILL,
                                       RequestState.DECODE)]
        for req in inflight:
            self._evict(req, front=True)
        group = resilience.recover(self._world)
        self._comm = group
        self._coll = Collectives(group) if self._tp_elems > 0 else None
        self.recoveries += 1

    # -- the driver loop ----------------------------------------------------
    def run(self, requests: Sequence[Request]) -> ServeReport:
        """Serve ``requests`` (arrival times honoured) to completion."""
        pending = sorted(requests, key=lambda r: (r.arrival_s, r.rid))
        rt = self._ext_runtime or TaskRuntime(num_workers=self._num_workers)
        rt.start()
        self._t0 = time.monotonic()
        rounds = 0
        try:
            while pending or self.queue or self.active:
                now = time.monotonic() - self._t0
                while pending and pending[0].arrival_s <= now:
                    self.queue.push(pending.pop(0))
                if not self.queue and not self.active:
                    # idle until the next arrival
                    time.sleep(min(max(pending[0].arrival_s - now, 0.0),
                                   0.005))
                    continue
                if self.on_round is not None:
                    self.on_round(self, rounds)
                if self.sync_every:
                    self._preempt()
                while len(self.active) < self.slots and self.queue:
                    req = self.queue.pop()
                    self._admit(req)
                    self._submit_prefill(rt, req)
                self._wake.clear()
                with self._lock:
                    runnable = [r for r in self.active.values()
                                if 0 < r.submitted_steps < r.gen_len]
                    room = self._ahead < ROUNDS_AHEAD
                if runnable and room:
                    self._submit_round(rt, runnable)
                rounds += 1
                if self.sync_every and rounds % self.sync_every == 0:
                    try:
                        rt.taskwait()
                    except TaskError as exc:
                        self._handle_failure(exc.error)
                elif not (runnable and room):
                    # nothing to dispatch: wait for a round's tokens
                    self._wake.wait(0.001)
            rt.taskwait()
        finally:
            if self._ext_runtime is None:
                rt.close()
        wall = time.monotonic() - self._t0
        outputs = {}
        evictions = 0
        for req in requests:
            outputs[req.rid] = [v for _, v in sorted(req.tokens)]
            evictions += req.evictions
        return ServeReport.build(self.completion, self.metrics.records,
                                 wall, outputs, evictions,
                                 self.recoveries)
