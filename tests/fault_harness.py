"""Reusable fault-injection harness for failure-recovery tests.

Drives one collective across all ranks of a fresh world on a real
:class:`~repro.core.TaskRuntime`, kills a chosen rank at a chosen
operation count (mid-send / mid-collective / between rounds — see
:meth:`repro.core.resilience.FaultInjector.arm`), harvests how the
failure surfaced on every rank, then runs the full ULFM recovery
(revoke + shrink) and a post-recovery collective on the survivors.

The harness asserts the protocol's *shape* (no hangs, leak-free
teardown, every rank either a result or a failure error); the caller
asserts the *semantics* (which ranks failed, survivor numerics).  A
death point past the victim's last posted operation never fires: the
outcome says so (``fired``) and the harness then asserts the clean
result instead.  Used by tests/test_resilience.py both directly and
under hypothesis.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

from repro.core import Collectives, TaskRuntime, tac
from repro.core.executor import TaskError
from repro.core.resilience import FaultInjector, recover

ALGORITHMS = ("ring", "doubling", "bruck", "tree")


@dataclass
class FaultOutcome:
    """What one injected failure did to an n-rank collective."""
    world: tac.CommWorld
    values: List[np.ndarray]
    fired: bool = False            # the armed trap killed the victim
    results: Dict[int, Any] = field(default_factory=dict)   # rank -> value
    errors: Dict[int, BaseException] = field(default_factory=dict)
    survivors: Any = None          # CommGroup after recovery, or None
    recovered: Dict[int, Any] = field(default_factory=dict)

    @property
    def failed_ranks(self):
        return sorted(self.errors)

    @property
    def ok_ranks(self):
        return sorted(self.results)


def _resolve(store: Dict[int, Any], outcome: FaultOutcome) -> None:
    for r, v in store.items():
        try:
            if isinstance(v, tac.AsyncHandle):
                v = v.result
            outcome.results[r] = v
        except tac.RankFailedError as exc:    # includes CommRevokedError
            outcome.errors[r] = exc


def run_with_failure(*, n_ranks: int, victim: int, after_ops: int = 1,
                     algorithm: str = "ring", mode: str = "event",
                     op: str = "allreduce",
                     hierarchical: Optional[int] = None, workers: int = 2,
                     recover_after: bool = True,
                     seed: int = 0) -> FaultOutcome:
    """One allreduce over ``n_ranks`` with ``victim`` dying at its
    ``after_ops``-th posted operation; returns the harvested outcome.

    Guarantees checked here, for every parameter combination:

    * the taskwait returns (failure propagation is hang-free — the
      machine observing the dead peer revokes the communicator);
    * every rank lands in exactly one of ``results`` / ``errors``;
    * the runtime closes leak-free (no registered polling services).

    If the victim posts fewer than ``after_ops`` operations the trap
    never fires (``outcome.fired`` is False): then no rank may fail and
    every rank's result must equal the numpy reference, and there is
    nothing to recover from.  Otherwise, with ``recover_after``, the
    survivors revoke + shrink and re-run the collective on the shrunken
    group (sequential driver), filling ``outcome.survivors`` /
    ``outcome.recovered``.
    """
    tac.init(tac.TASK_MULTIPLE)
    world = tac.CommWorld(n_ranks)
    coll = Collectives(world)
    injector = FaultInjector(world)
    rng = np.random.default_rng(seed)
    values = [rng.standard_normal(4) for _ in range(n_ranks)]
    outcome = FaultOutcome(world=world, values=values)
    store: Dict[int, Any] = {}
    kw: Dict[str, Any] = ({"hierarchical": hierarchical}
                          if hierarchical else {"algorithm": algorithm})

    def body(r):
        def run():
            store[r] = coll.allreduce(values[r], rank=r, mode=mode,
                                      key="fh", **kw)
        return run

    rt = TaskRuntime(num_workers=workers)
    rt.start()
    try:
        injector.arm(victim, after_ops=after_ops)
        for r in range(n_ranks):
            rt.submit(body(r), name=f"coll[{r}]")
        try:
            rt.taskwait()       # must NOT hang for any combination
        except TaskError as exc:
            # blocking mode: the raising body never filled its slot
            root = exc.error
            assert isinstance(root, tac.RankFailedError), exc
        # drain stragglers (other blocking bodies may error too)
        while True:
            try:
                rt.taskwait()
                break
            except TaskError:
                continue
    finally:
        outcome.fired = not injector.armed
        injector.disarm()
        rt.close()
    assert rt.polling.num_services == 0, "leaked polling services"
    _resolve(store, outcome)
    claimed = set(outcome.results) | set(outcome.errors)
    # blocking-mode errored bodies never stored anything: their absence
    # from both maps IS the error record
    if mode == "event":
        assert claimed == set(range(n_ranks)), claimed
    if not outcome.fired:
        assert not outcome.errors, outcome.errors
        ref = np.sum(values, axis=0)
        assert outcome.ok_ranks == list(range(n_ranks)), outcome.ok_ranks
        for v in outcome.results.values():
            np.testing.assert_allclose(v, ref, rtol=1e-10, atol=1e-12)
        return outcome
    assert outcome.errors or len(outcome.results) < n_ranks, \
        "injected failure was not observed anywhere"

    if recover_after:
        survivors = recover(world)
        outcome.survivors = survivors
        assert victim not in survivors.ranks
        assert survivors.size == n_ranks - 1
        scoll = Collectives(survivors)
        # the shrunken size may not divide a hierarchical pod shape —
        # recovery re-picks a flat algorithm in that case
        rkw = {} if hierarchical else {"algorithm": algorithm}
        out = scoll.run_group(
            "allreduce",
            [{"value": values[wr]} for wr in survivors.ranks],
            op="sum", key="fh-rec", **rkw)
        outcome.recovered = {gr: out[gr] for gr in range(survivors.size)}
        ref = np.sum([values[wr] for wr in survivors.ranks], axis=0)
        for gr, v in outcome.recovered.items():
            np.testing.assert_allclose(v, ref, rtol=1e-10, atol=1e-12)
    return outcome
