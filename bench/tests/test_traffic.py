"""The traffic generator: every seed the same work, in another order."""

from __future__ import annotations

import json

import numpy as np
import pytest

import traffic
from conftest import BENCH

MIXES = {p.stem: json.loads(p.read_text())
         for p in (BENCH / "mixes").glob("*.json")}


def test_lognormal_quantiles():
    out = {"median": 128, "sigma": 0.8, "max": 512}
    lens = traffic.output_lengths(out, 101)
    assert lens[50] == 128                       # the median
    assert lens.min() >= 1 and lens.max() == 512  # capped
    assert np.all(np.diff(lens) >= 0)


@pytest.mark.parametrize("name", ["chat", "chat-reordered"])
def test_open_loop_same_work_every_seed(name):
    """With a ``schedule_seed`` every seed sends the same schedule; without
    one, the same work in another order."""
    mix = dict(MIXES["chat"])
    fixed = name == "chat"
    if not fixed:
        del mix["schedule_seed"]
    a = traffic.plan(mix, {"rate_per_s": 0.5}, 1, 40)
    b = traffic.plan(mix, {"rate_per_s": 0.5}, 2**40 + 3, 40)
    w0, w1 = a.window
    assert (w0, w1) == (mix["warmup_s"], mix["warmup_s"] + 40)
    for p in (a, b):
        assert p.arrivals == sorted(p.arrivals)
        assert all(g <= mix["output"]["max"] for g in p.outputs)
        assert len(p.outputs) == len(p.arrivals) == p.n_prompts
    ina = [i for i, t in enumerate(a.arrivals) if w0 <= t < w1]
    inb = [i for i, t in enumerate(b.arrivals) if w0 <= t < w1]
    assert len(ina) == len(inb) == 20            # rate x window
    assert sorted(a.outputs[i] for i in ina) == \
        sorted(b.outputs[i] for i in inb)
    assert ([a.outputs[i] for i in ina] == [b.outputs[i] for i in inb]) \
        == fixed
    assert (a.arrivals == b.arrivals) == fixed
    assert a.arrivals[ina[-1]] < w1


def test_same_seed_same_plan():
    mix = MIXES["chat"]
    a = traffic.plan(mix, {"rate_per_s": 0.4}, 9, 30)
    b = traffic.plan(mix, {"rate_per_s": 0.4}, 9, 30)
    assert a == b


def test_closed_loop_clients():
    mix = MIXES["batch"]
    a = traffic.plan(mix, {}, 5, 40)
    b = traffic.plan(mix, {}, 6, 40)
    assert len(a.clients) == mix["clients"]
    assert all(len(c) == mix["requests_per_client"] for c in a.clients)
    assert sorted(sum(a.clients, [])) == sorted(sum(b.clients, []))
    assert a.n_prompts == mix["clients"] * mix["requests_per_client"]


def test_unknown_loop_is_refused():
    with pytest.raises(ValueError):
        traffic.plan(dict(MIXES["chat"], loop="spiral"), {"rate_per_s": 1},
                     1, 10)
