"""One generator for every traffic mix: a mix file of parameters in, requests out.

A mix (``bench/mixes/<name>.json``) fixes the prompt length, the
distribution of output lengths, the loop (``open``: Poisson arrivals at a
rate; ``closed``: a number of clients, each sending its next request when
the last one finished) and the warm-up before the measured window.  A
cell's own file (``bench/cells/<workload>.json``) adds what depends on the
configuration, such as the rate.

Every seed gets the same work: the output lengths are the quantiles of the
mix's distribution at evenly spaced probabilities, and the gaps between
arrivals the quantiles of the exponential distribution at the rate, both
shuffled.  A mix that names a ``schedule_seed`` is shuffled by that seed,
so that every run sends the same schedule and the run's seed changes only
the prompts and the weights: in an open loop of a few tens of long
requests the order alone sets how many overlap, and with it the
inter-token gaps.  Otherwise the run's seed shuffles them, and two seeds
differ in how requests overlap, not in how much there is to do.
"""

from __future__ import annotations

import dataclasses
import statistics
from typing import Any, Dict, List

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class Plan:
    """What one run sends.  Times are seconds from the engine's start."""

    loop: str
    prompt_len: int
    max_output: int
    window: tuple                       # (start, end) of the measured window
    arrivals: List[float]               # open loop: one per request
    outputs: List[int]                  # open loop: one per request
    clients: List[List[int]] = dataclasses.field(default_factory=list)

    @property
    def n_prompts(self) -> int:
        """Distinct prompts the run may use (one per request)."""
        return len(self.outputs) + sum(len(c) for c in self.clients)


def output_lengths(out: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` lognormal quantiles (median, sigma), capped and at least 1."""
    p = (np.arange(n) + 0.5) / n
    z = np.array([_NORMAL.inv_cdf(float(x)) for x in p])
    lens = np.rint(out["median"] * np.exp(out["sigma"] * z))
    return np.clip(lens, 1, out["max"]).astype(int)


def _gaps(rate: float, n: int) -> np.ndarray:
    p = (np.arange(n) + 0.5) / n
    return -np.log1p(-p) / rate


def plan(mix: Dict[str, Any], cell: Dict[str, Any], seed: int,
         seconds: float) -> Plan:
    order = int(mix.get("schedule_seed", seed))
    rng = np.random.default_rng(np.random.SeedSequence([order, 7]))
    warm = float(mix["warmup_s"])
    window = (warm, warm + float(seconds))
    common = dict(loop=mix["loop"], prompt_len=int(mix["prompt_len"]),
                  max_output=int(mix["output"]["max"]), window=window)
    if mix["loop"] == "open":
        rate = float(cell["rate_per_s"])
        arrivals: List[float] = []
        outputs: List[int] = []
        for start, span in ((0.0, warm), (warm, float(seconds))):
            n = max(1, int(round(rate * span)))
            gaps = rng.permutation(_gaps(rate, n))
            arrivals += list(start + np.concatenate([[0.0],
                                                     np.cumsum(gaps)[:-1]]))
            outputs += list(rng.permutation(output_lengths(mix["output"], n)))
        return Plan(arrivals=arrivals, outputs=[int(o) for o in outputs],
                    **common)
    if mix["loop"] == "closed":
        c, per = int(mix["clients"]), int(mix["requests_per_client"])
        lens = rng.permutation(output_lengths(mix["output"], c * per))
        clients = [[int(x) for x in lens[i::c]] for i in range(c)]
        return Plan(arrivals=[], outputs=[], clients=clients, **common)
    raise ValueError(f"unknown loop {mix['loop']!r}; one of open, closed")
