"""Hypothesis property sweep for failure recovery (tentpole suite).

Samples the full cross-product the deterministic tests grid-spot-check:
death point (operation count) × collective algorithm (ring / doubling /
bruck / tree / hierarchical) × interoperability mode (blocking / event),
asserting through tests/fault_harness.py that every combination

* surfaces the injected death as ``RankFailedError`` without hanging the
  taskwait,
* tears the runtime down leak-free (zero registered polling services),
* completes the shrink agreement on exactly the survivors, and
* converges the survivors' post-recovery allreduce to the numpy
  reference at the shrunken size.

A death point past the victim's last posted operation never fires; the
harness then holds the run to the clean result (no failed rank, every
allreduce equal to numpy), and the survivor assertions are skipped.

``REPRO_FAULTS_SOAK=<n>`` raises the example count (the CI fault-soak
job sets it); the default stays small so tier-1 wall time is bounded.
"""

import os

import pytest

pytest.importorskip(
    "hypothesis",
    reason="property sweep needs hypothesis (pip install -r "
           "requirements-dev.txt)")

from hypothesis import assume, given, settings, HealthCheck
import hypothesis.strategies as st

from fault_harness import ALGORITHMS, run_with_failure

pytestmark = pytest.mark.faults

_SOAK = int(os.environ.get("REPRO_FAULTS_SOAK", "0"))
_SETTINGS = dict(deadline=None, max_examples=_SOAK or 10,
                 suppress_health_check=[HealthCheck.too_slow])


@settings(**_SETTINGS)
@given(n_ranks=st.integers(min_value=2, max_value=6),
       victim=st.integers(min_value=0, max_value=5),
       after_ops=st.integers(min_value=1, max_value=6),
       algorithm=st.sampled_from(ALGORITHMS + ("hierarchical",)),
       mode=st.sampled_from(["blocking", "event"]),
       seed=st.integers(min_value=0, max_value=2 ** 16))
def test_property_any_failure_point_recovers(n_ranks, victim, after_ops,
                                             algorithm, mode, seed):
    """For ANY death point in ANY algorithm under EITHER interop mode:
    the taskwait returns, teardown is leak-free, the shrink agreement
    produces the survivor set, and the survivors' post-recovery
    allreduce matches the numpy reference — all asserted inside the
    harness."""
    victim %= n_ranks
    hierarchical = None
    if algorithm == "hierarchical":
        hierarchical = 2 if n_ranks % 2 == 0 else 1
        algorithm = "ring"
    out = run_with_failure(n_ranks=n_ranks, victim=victim,
                           after_ops=after_ops, algorithm=algorithm,
                           hierarchical=hierarchical, mode=mode, seed=seed)
    assume(out.fired)
    assert out.survivors.size == n_ranks - 1
    assert victim not in out.survivors.ranks
