"""The trace reduction: device busy and idle, program and kernel times, and
idle gaps named by the host activity that covers them.

The first test builds planes by hand, so every expected number is exact.
The second reads a small trace recorded on a TPU v5e
(``data/v5e_small.xplane.pb``, made by ``record_trace.py``) and checks the
reduction against a count made here from the same events.
"""

from __future__ import annotations

import pathlib
from types import SimpleNamespace as NS

import pytest

import xplane

DATA = pathlib.Path(__file__).resolve().parent / "data"
MS = 1_000_000


def ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS)


def planes():
    host = NS(name="/host:CPU", lines=[
        NS(name="python", events=[ev(xplane.WINDOW, 10, 100)]),
        NS(name="python", events=[ev("bench.decode", 30, 10),
                                  ev("PjitFunction(decode)", 31, 2),
                                  ev("bench.detok", 60, 25)]),
    ])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            ev("jit_prefill(123)", 0, 20),        # half inside the window
            ev("jit_decode(9)", 40, 10),
            ev("jit_decode(9)", 50, 10),          # back to back
            ev("jit_decode(9)", 90, 10),
            ev("jit_prefill(123)", 105, 20),      # runs past the window
        ]),
        NS(name="XLA Ops", events=[
            ev("%fusion.1 = bf16[2048]{0} fusion(...)", 41, 1),
            ev("%c.3 = bf16[1,32,1024,64]{3,2,1,0} custom-call(%a)", 42, 2),
            ev("%c.3 = bf16[1,32,1024,64]{3,2,1,0} custom-call(%a)", 109, 3),
        ]),
    ])
    return [host, NS(name="/host:metadata", lines=[]), dev]


def test_reduction_by_hand():
    r = xplane.reduce_planes(planes())
    assert r["window_s"] == pytest.approx(0.100)
    # busy: [10,20] + [40,60] + [90,100] + [105,110]
    assert r["busy_s"] == pytest.approx(0.045)
    assert r["modules"] == {"jit_decode": [0.010, 0.010, 0.010]}
    assert [s for _, s in r["custom_calls"]] == [pytest.approx(0.002)]
    gaps = dict(r["idle_gaps"])
    # [20,40]: bench.decode covers 30-40 -> named by our annotation;
    # [60,90]: bench.detok 60-85; [100,105]: nothing
    assert gaps == {"bench.decode": pytest.approx(0.020),
                    "bench.detok": pytest.approx(0.030),
                    "none": pytest.approx(0.005)}
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])
    assert r["top_modules"] == [("jit_decode", pytest.approx(0.030))]


def test_slices_merge_as_one_window():
    one = xplane.reduce_planes(planes())
    r = xplane.merge([one, one])
    assert r["window_s"] == pytest.approx(0.200)
    assert r["busy_s"] == pytest.approx(0.090)
    assert r["modules"] == {"jit_decode": [0.010] * 6}
    assert len(r["custom_calls"]) == 2
    assert dict(r["idle_gaps"])["bench.detok"] == pytest.approx(0.060)
    assert r["top_modules"] == [("jit_decode", pytest.approx(0.060))]


def test_no_device_plane_is_an_error():
    with pytest.raises(RuntimeError):
        xplane.reduce_planes([planes()[0]])


def test_recorded_v5e_trace():
    path = DATA / "v5e_small.xplane.pb"
    if not path.exists():
        pytest.fail(f"{path} is missing: record it with record_trace.py")
    from jax.profiler import ProfileData
    planes_ = list(ProfileData.from_file(str(path)).planes)
    r = xplane.reduce_planes(planes_)
    # The same numbers, counted here the plain way.
    host = [p for p in planes_ if p.name == "/host:CPU"][0]
    lo, hi = [(int(e.start_ns), int(e.start_ns + e.duration_ns))
              for line in host.lines for e in line.events
              if e.name == xplane.WINDOW][0]
    dev = [p for p in planes_ if p.name == "/device:TPU:0"][0]
    mods = [e for line in dev.lines if line.name == "XLA Modules"
            for e in line.events]
    busy = set()
    for e in mods:
        s, d = int(e.start_ns), int(e.duration_ns)
        a, b = max(s, lo), min(s + d, hi)
        busy.update(range(a // 1000, b // 1000))      # microsecond cells
    assert r["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert r["busy_s"] == pytest.approx(len(busy) * 1e-6, abs=2e-5)
    assert 0 < r["busy_s"] < r["window_s"]
    inside = [e for e in mods if lo <= e.start_ns
              and e.start_ns + e.duration_ns <= hi]
    assert sum(len(v) for v in r["modules"].values()) == len(inside)
    assert "jit_step" in r["modules"] and "jit_kernel" in r["modules"]
    # one Pallas call in each run of the kernel program
    assert len(r["custom_calls"]) == len(r["modules"]["jit_kernel"]) >= 2
    names = dict(r["idle_gaps"])
    assert "bench.host_work" in names
    assert sum(names.values()) + r["busy_s"] == pytest.approx(r["window_s"],
                                                              rel=1e-6)
