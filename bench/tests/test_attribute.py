"""Idle attribution: each idle instant of the device goes to the
highest-ranked program span open on any thread, on a clock linked to the
profile's through the ``profile/window`` span.

The planes and the tracer's events are built by hand, so every expected
number is exact.  The last test reads the small trace recorded on a TPU
v5e (``data/v5e_small.xplane.pb``).
"""

from __future__ import annotations

import pathlib
from types import SimpleNamespace as NS

import pytest

import attribute
import xplane

DATA = pathlib.Path(__file__).resolve().parent / "data"
MS = 1_000_000
WIN_TS_US = 1_000_000.0      # the profile/window span opens 1 s after epoch


def planes(scale=1.0):
    """The window is trace ms 10-110; the device runs in 0-20, 40-60,
    90-100 and 105-125, so it idles 20-40, 60-90 and 100-105 (55 ms).
    ``scale`` stretches the trace's clock against the tracer's."""
    def ev(name, start_ms, dur_ms):
        return NS(name=name, start_ns=round(start_ms * MS * scale),
                  duration_ns=round(dur_ms * MS * scale))
    host = NS(name="/host:CPU", lines=[
        NS(name="python", events=[ev(xplane.WINDOW, 10, 100)]),
        NS(name="python", events=[ev("bench.decode", 21, 10)]),
    ])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            ev("jit_prefill(1)", 0, 20), ev("jit_decode(2)", 40, 20),
            ev("jit_decode(2)", 90, 10), ev("jit_prefill(1)", 105, 20)]),
        NS(name="XLA Ops", events=[]),
    ])
    return [host, dev]


def window():
    return {"ph": "X", "cat": "profile", "name": "window", "ts": WIN_TS_US,
            "dur": 100_000.0, "pid": 0, "tid": 3,
            "args": {"annotation": xplane.WINDOW, "enter_us": 2.0,
                     "exit_us": 1.5}}


def span(cat, name, start_ms, end_ms, tid=1, **args):
    """A tracer span at trace ms [start_ms, end_ms] of the unscaled clock."""
    return {"ph": "X", "cat": cat, "name": name,
            "ts": WIN_TS_US + (start_ms - 10) * 1000.0,
            "dur": (end_ms - start_ms) * 1000.0, "pid": 0, "tid": tid,
            "args": args}


def events():
    return [
        window(),
        # before the window, and a kind of span that claims nothing
        span("task", "run", 0, 5, task="decode:0@1"),
        span("handle", "inflight", 0, 110, tid=4),
        # gap 20-40: a decode task, its dispatch, a collection inside it
        span("task", "run", 15, 45, task="decode:1@3"),
        span("serving", "device_step", 22, 30, rid=1, step=3,
             kind="decode"),
        span("host", "gc", 25, 27, tid=0, generation=0, collected=0),
        # gap 60-90: detok on one worker while another pauses, then a
        # prefill dispatch with the cache handoff nested in it, a compile
        span("serving", "detok", 60, 70, rid=1, step=2),
        span("task", "pause", 65, 80, tid=2, task="decode:2@1"),
        span("serving", "device_step", 72, 85, tid=2, rid=3, step=0,
             kind="prefill"),
        span("serving", "pad_cache", 75, 78, tid=2, rid=3),
        span("host", "compile", 86, 88, tid=5,
             event="/jax/core/compile/backend_compile_duration"),
        # gap 100-105: no span at all
    ]


EXPECTED_MS = {"task.decode": 12, "dispatch.decode": 6, "host/gc": 2,
               "serving/detok": 10, "task/pause": 2, "dispatch.prefill": 10,
               "serving/pad_cache": 3, "host/compile": 2, "runtime": 8}


def test_priority_order_nested_and_concurrent_spans():
    r = attribute.attribute(planes(), window(), events())
    assert r["idle_by_span"] == {
        k: pytest.approx(v * 1e-3) for k, v in EXPECTED_MS.items()}
    assert r["misfit_us"] == pytest.approx(0.0)
    assert (r["enter_us"], r["exit_us"]) == (2.0, 1.5)


def test_parts_sum_to_window_less_busy():
    pl = planes()
    r = attribute.attribute(pl, window(), events())
    red = xplane.reduce_planes(pl)
    idle = red["window_s"] - red["busy_s"]
    assert r["idle_s"] == pytest.approx(idle)
    assert sum(r["idle_by_span"].values()) == pytest.approx(idle)
    assert r["window_s"] == pytest.approx(red["window_s"])


def test_gap_with_no_span_is_runtime():
    r = attribute.attribute(planes(), window(), [window()])
    assert r["idle_by_span"] == {"runtime": pytest.approx(0.055)}


def test_one_rank_goes_to_the_span_opened_first():
    spans = [(0, 10, 3, "dispatch.decode"), (5, 15, 3, "dispatch.prefill"),
             (12, 14, 6, "task.detok")]
    assert attribute.split([(0, 20)], spans) == {
        "dispatch.decode": 10, "dispatch.prefill": 5, "runtime": 5}


def test_clock_map_absorbs_a_drift():
    drift = 4e-4                    # the trace's clock runs 400 ppm fast
    r = attribute.attribute(planes(1 + drift), window(), events())
    assert r["misfit_us"] == pytest.approx(100_000 * drift)
    assert r["idle_by_span"] == {
        k: pytest.approx(v * 1e-3 * (1 + drift), rel=1e-6)
        for k, v in EXPECTED_MS.items()}
    clock = attribute.ClockMap(ts_us=1e6, dur_us=2e6, start_ns=5 * MS,
                               end_ns=5 * MS + 2_000_040_000)
    assert clock.misfit_us == pytest.approx(40.0)
    assert clock(1e6 + 1e6) == pytest.approx(5 * MS + 1_000_020_000)


def test_misfit_over_the_limit_raises():
    drift = 2 * attribute.MISFIT_LIMIT_US / 100_000
    with pytest.raises(attribute.ClockMisfit):
        attribute.attribute(planes(1 + drift), window(), events())
    with pytest.raises(attribute.ClockMisfit):
        attribute.ClockMap(ts_us=0.0, dur_us=1e6, start_ns=0,
                           end_ns=1_000_000_000 - 101_000, limit_us=100.0)


def test_merge_gives_shares_of_the_traced_time():
    one = attribute.attribute(planes(), window(), events())
    m = attribute.merge([one, one])
    assert m["window_s"] == pytest.approx(0.2)
    assert m["idle_s"] == pytest.approx(0.110)
    assert m["idle_by_span"][0] == ("task.decode", pytest.approx(0.024))
    assert m["idle_dispatch_share"] == pytest.approx(100 * 0.038 / 0.2)
    assert m["idle_runtime_share"] == pytest.approx(100 * 0.016 / 0.2)


def test_no_request_marks_split_runtime():
    """Request 1 is in flight from trace ms 15 (its admission) to 95 (its
    finish task's end): of the 8 ms of runtime, the 5 ms gap at 100-105
    has no request in flight."""
    evs = events() + [
        span("serving", "queued", 12, 15, rid=1),
        span("task", "run", 94, 95, tid=6, task="finish:1")]
    marks = attribute.no_request(evs)
    r = attribute.attribute(planes(), window(), evs + marks)
    want = dict(EXPECTED_MS, runtime=3)
    want[attribute.NO_REQUEST] = 5
    assert r["idle_by_span"] == {
        k: pytest.approx(v * 1e-3) for k, v in want.items()}
    m = attribute.merge([r])
    assert m["idle_no_request_s"] == pytest.approx(0.005)
    assert dict(m["idle_by_span"])["runtime"] == pytest.approx(0.008)


def test_recorded_v5e_trace():
    path = DATA / "v5e_small.xplane.pb"
    from jax.profiler import ProfileData
    pl = list(ProfileData.from_file(str(path)).planes)
    red = xplane.reduce_planes(pl)
    (lo, hi), idle = attribute.idle_intervals(pl)
    assert len(idle) == 1
    assert (hi - lo) * 1e-9 == pytest.approx(red["window_s"])
    # The reduction's busy time, from the same events the same way.
    assert sum(e - s for s, e in idle[0]) * 1e-9 == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-9)
    win = dict(window(), ts=0.0, dur=(hi - lo) * 1e-3)
    r = attribute.attribute(pl, win, [win])
    assert r["idle_by_span"] == {
        "runtime": pytest.approx(red["window_s"] - red["busy_s"])}
