"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the metrics read.

On a TPU the trace holds one plane per chip (``/device:TPU:<n>``) whose
``XLA Modules`` line has one event per program run and whose ``XLA Ops``
line has one event per HLO operation (nested inside loops), and a host
plane (``/host:CPU``) with one line per thread, where the benchmark's own
``jax.profiler.TraceAnnotation`` spans sit.  Host and device events share
one clock.

The reduction keeps, within the window marked by the host annotation
``WINDOW``:

* ``busy_s``: the union of program runs on the device (averaged over the
  chips), and ``window_s``;
* ``modules``: the device seconds of each run, by program name;
* ``custom_calls``: (HLO text, seconds) of each custom call, which is how
  a Pallas kernel appears;
* ``idle_gaps``: the device's idle seconds, each gap named by the host
  activity that covers most of it (a ``bench.*`` annotation first, else
  the JAX dispatch or other host event, else ``none``);
* ``top_modules``: device seconds by program name, largest first.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Any, Dict, List, Tuple

WINDOW = "bench.traced_window"
_HASH = re.compile(r"\(\d+\)$")


def find(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def module_name(event_name: str) -> str:
    """``jit_decode(5039529186182799904)`` -> ``jit_decode``."""
    return _HASH.sub("", event_name)


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _clip(s: int, e: int, lo: int, hi: int):
    return max(s, lo), min(e, hi)


def reduce_planes(planes: List[Any]) -> Dict[str, Any]:
    """Reduce ``ProfileData``-like planes (objects with ``name`` and
    ``lines``; lines with ``name`` and ``events``; events with ``name``,
    ``start_ns`` and ``duration_ns``)."""
    host = [p for p in planes if p.name == "/host:CPU"]
    devices = [p for p in planes if p.name.startswith("/device:TPU:")]
    if not devices:
        raise RuntimeError("the trace holds no TPU device plane")
    host_events = []    # (start, end, name) on host threads
    window = None
    for plane in host:
        for line in plane.lines:
            for ev in line.events:
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                if ev.name == WINDOW:
                    window = (s, e)
                else:
                    host_events.append((s, e, ev.name))
    if window is None:
        raise RuntimeError(f"the trace holds no {WINDOW!r} annotation")
    lo, hi = window
    cover = _Cover([ev for ev in host_events if ev[1] > lo and ev[0] < hi])

    modules: Dict[str, List[float]] = {}
    custom_calls: List[Tuple[str, float]] = []
    busy_ns = 0
    gaps: Dict[str, float] = {}
    for dev in devices:
        runs = []
        for line in dev.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    s = int(ev.start_ns)
                    e = s + int(ev.duration_ns)
                    if e <= lo or s >= hi:
                        continue
                    runs.append(_clip(s, e, lo, hi))
                    if lo <= s and e <= hi:
                        modules.setdefault(module_name(ev.name), []).append(
                            ev.duration_ns * 1e-9)
            elif line.name == "XLA Ops":
                for ev in line.events:
                    if "custom-call(" not in ev.name:
                        continue
                    s = int(ev.start_ns)
                    if lo <= s and s + int(ev.duration_ns) <= hi:
                        custom_calls.append((ev.name, ev.duration_ns * 1e-9))
        busy = _union(runs)
        busy_ns += sum(e - s for s, e in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                name = cover(s, e)
                gaps[name] = gaps.get(name, 0.0) + (e - s) * 1e-9 / len(devices)
    top = sorted(((k, sum(v)) for k, v in modules.items()),
                 key=lambda kv: -kv[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * 1e-9 / len(devices),
        "modules": modules,
        "custom_calls": custom_calls,
        "idle_gaps": sorted(gaps.items(), key=lambda kv: -kv[1]),
        "top_modules": top,
    }


class _Cover:
    """Names an interval by the host activity that overlaps it most: a
    ``bench.*`` annotation if any overlaps, else any other host event,
    else ``none``."""

    LONGEST_NS = 500_000_000  # longer host events say nothing of a gap

    def __init__(self, events: List[Tuple[int, int, str]]) -> None:
        self.events = sorted(ev for ev in events
                             if ev[1] - ev[0] <= self.LONGEST_NS)
        self.starts = [ev[0] for ev in self.events]
        self.longest = max((e - s for s, e, _ in self.events), default=0)

    def __call__(self, s: int, e: int) -> str:
        best: Dict[str, int] = {}
        i = bisect.bisect_left(self.starts, s - self.longest)
        j = bisect.bisect_left(self.starts, e)
        for hs, he, name in self.events[i:j]:
            o = min(he, e) - max(hs, s)
            if o > 0:
                best[name] = best.get(name, 0) + o
        ours = {k: v for k, v in best.items() if k.startswith("bench.")}
        pool = ours or best
        if not pool:
            return "none"
        return max(pool.items(), key=lambda kv: kv[1])[0]


def reduce_file(path: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData
    return reduce_planes(list(ProfileData.from_file(path).planes))


def merge(parts: List[Dict[str, Any]]) -> Dict[str, Any]:
    """One reduction of several traced windows, as if they were one."""
    modules: Dict[str, List[float]] = {}
    gaps: Dict[str, float] = {}
    for part in parts:
        for name, times in part["modules"].items():
            modules.setdefault(name, []).extend(times)
        for name, s in part["idle_gaps"]:
            gaps[name] = gaps.get(name, 0.0) + s
    return {
        "window_s": sum(p["window_s"] for p in parts),
        "busy_s": sum(p["busy_s"] for p in parts),
        "modules": modules,
        "custom_calls": [c for p in parts for c in p["custom_calls"]],
        "idle_gaps": sorted(gaps.items(), key=lambda kv: -kv[1]),
        "top_modules": sorted(((k, sum(v)) for k, v in modules.items()),
                              key=lambda kv: -kv[1]),
    }
